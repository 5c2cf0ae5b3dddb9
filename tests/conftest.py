"""Test configuration: JAX on a virtual 8-device CPU mesh.

The unit suite never touches an accelerator.  ``JAX_PLATFORMS=cpu`` is
set before jax is imported (and pinned again through ``jax.config``, so
a caller's environment cannot undo it), with eight virtual CPU devices
(``--xla_force_host_platform_device_count=8``) for sharding and mesh
coverage; Pallas kernels run in interpret mode.

What the CPU cannot say comes from two other places:

* ``tests/test_tpu_compile.py`` compiles the kernels of the served path
  for a *described* v5e with the TPU compiler installed here.  It
  describes the topology inside its own module fixture — never here,
  never at import: one process at a time may hold the TPU library, and
  the suite runs under several xdist workers.
* ``chip_smoke.py`` at the repo root is the run on the chip (one
  process per chip; see .claude/skills/verify/SKILL.md).

The persistent compile cache is the one every process uses
(``compile_cache.enable``: ``JAX_COMPILATION_CACHE_DIR`` if set, else
``<checkout>/.jax_cache/<host fingerprint>``).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (import after env setup)

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
# Persistent compilation cache (keyed per host CPU — foreign AOT entries
# mis-execute): the P-256 verify ladder is a large program whose XLA:CPU
# compile dominates suite time; cache it across runs.
from upow_tpu import compile_cache  # noqa: E402

compile_cache.enable()

sys.path.insert(0, os.path.dirname(__file__))  # for `import ref_loader`

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running scenarios (50-node swarms, long partitions) "
        "excluded from the tier-1 run via -m 'not slow'")


# ---------------------------------------------------------------------------
# Runtime concurrency sanitizer (upow_tpu.lint.sanitizer)
#
# Installed once per session: wraps asyncio's callback dispatch to time
# every event-loop step (blocked-loop watchdog), patches the loop
# exception handler to catch un-retrieved task exceptions, and arms the
# thread-affinity hook at the device-runtime submit/drain seam.  Each
# test drains findings at teardown and FAILS on product-attributed ones
# — test code blocking its own loop (jax compiles, sync fixtures) is
# reported by the sanitizer but does not gate.
#
#   UPOW_SANITIZER=0                  disable entirely
#   UPOW_SANITIZER_THRESHOLD=<secs>   blocked-loop threshold (default 2.0
#                                     under the full tier-1 suite, where
#                                     cold jax compiles legitimately run
#                                     long inside loop callbacks; chaos
#                                     CI pins a strict 0.5)
# ---------------------------------------------------------------------------

_SANITIZER_ON = os.environ.get("UPOW_SANITIZER", "1") != "0"


@pytest.fixture(scope="session")
def _sanitizer_session():
    if not _SANITIZER_ON:
        yield None
        return
    from upow_tpu.lint import sanitizer

    threshold = float(os.environ.get("UPOW_SANITIZER_THRESHOLD", "2.0"))
    san = sanitizer.install(blocked_loop_threshold=threshold)
    try:
        yield san
    finally:
        sanitizer.uninstall()


@pytest.fixture(autouse=True)
def _sanitizer_gate(_sanitizer_session, recwarn, request):
    """Drain sanitizer findings after every test; fail the test on
    product-attributed ones.  ``recwarn`` keeps refcount-dropped
    'coroutine ... was never awaited' warnings visible to the gate
    (they fire mid-test, before the GC flush at teardown)."""
    san = _sanitizer_session
    if san is None:
        yield
        return
    san.drain()                       # start clean (cross-test bleed)
    yield
    san.flush_never_awaited()
    for w in recwarn.list:
        san.record_never_awaited(str(w.message))
    findings = san.drain()
    gating = [f for f in findings if f.product]
    benign = [f for f in findings if not f.product]
    for f in benign:
        sys.stderr.write(f"[sanitizer] note ({request.node.nodeid}): "
                         f"{f.detail}\n")
    if gating:
        lines = "\n\n".join(str(f) for f in gating)
        pytest.fail(
            f"concurrency sanitizer: {len(gating)} product finding(s)\n"
            f"{lines}", pytrace=False)


@pytest.fixture(autouse=True)
def _fresh_sig_verdicts():
    """The process-level signature-verdict cache must not leak verdicts
    across tests (a test asserting a backend runs would silently pass on
    another test's cache hits)."""
    from upow_tpu.verify import txverify

    txverify.clear_sig_verdicts()
    yield


@pytest.fixture
def span_events(monkeypatch):
    """Every telemetry span as the profiler would hear it, in order and
    with its thread: a fake annotation class (no ``is_enabled``, so
    always heard) that appends (open|close, name, thread name)."""
    import threading

    from upow_tpu.telemetry import tracing

    events: list = []

    class Annotation:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            events.append(("open", self.name,
                           threading.current_thread().name))
            return self

        def __exit__(self, *exc):
            events.append(("close", self.name,
                           threading.current_thread().name))
            return False

    monkeypatch.setattr(tracing, "_annotation_cls", Annotation)
    return events
