"""TPU/kernel telemetry: dispatch latency, compile hit/miss, occupancy.

The batch kernels (P-256 verify, sha256 txid) pad every batch up to a
block multiple before dispatch; how much of each dispatched batch is
*real* work was invisible until now.  ``record_batch`` feeds, per
kernel:

- ``kernel.<name>.dispatch_seconds``   latency histogram
- ``kernel.<name>.occupancy``          real/padded-lane ratio histogram
- ``kernel.<name>.lanes_real``         counters (padding waste =
  ``kernel.<name>.lanes_padded``       padded - real)
- ``kernel.<name>.compile_cache_hits`` jit in-process cache proxy:
  ``kernel.<name>.compile_cache_misses``  the first dispatch of a
  given compile key (padded shape / static args) compiles, later
  ones reuse the traced program.

``record_hoist`` adds, for the sha256 search kernels, once a job,
``kernel.<name>.rounds_hoisted`` and ``kernel.<name>.words_hoisted``;
``record_exact_steps``, once a round, ``kernel.<name>.exact_steps``.

Device memory gauges are best-effort: ``memory_stats()`` is populated
on TPU/GPU backends and typically absent on CPU; we never import jax
here — if the caller hasn't, there is nothing to report."""

from __future__ import annotations

import functools
import sys
import threading
from typing import Dict, Hashable, Optional, Set, Tuple

from ..logger import get_logger
from . import metrics

log = get_logger("telemetry")

OCCUPANCY_BUCKETS = (0.1, 0.25, 0.5, 0.75, 0.9, 1.0)
DISPATCH_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0)

_lock = threading.Lock()
_seen_keys: Dict[str, Set[Hashable]] = {}
_MAX_KEYS_PER_KERNEL = 4096


def preregister(kernel: str) -> None:
    """Create the kernel's metric families so /metrics exports them
    (all-zero) before the first dispatch."""
    metrics.ensure_histogram("kernel.%s.occupancy" % kernel,
                             OCCUPANCY_BUCKETS)
    metrics.ensure_histogram("kernel.%s.dispatch_seconds" % kernel,
                             DISPATCH_BUCKETS)
    for c in ("lanes_real", "lanes_padded",
              "compile_cache_hits", "compile_cache_misses"):
        metrics.ensure_counter("kernel.%s.%s" % (kernel, c))


_BATCH_FAMILIES = ("lanes_real", "lanes_padded", "occupancy",
                   "dispatch_seconds", "compile_cache_hits",
                   "compile_cache_misses")


@functools.lru_cache(maxsize=None)
def _batch_names(kernel: str) -> tuple:
    """``kernel.<kernel>.<family>`` in ``_BATCH_FAMILIES``' order (the
    kernels are a handful of literals; a round formats no name)."""
    return tuple("kernel.%s.%s" % (kernel, f) for f in _BATCH_FAMILIES)


def _batch_update(kernel: str, real: int, padded: int,
                  seconds: Optional[float],
                  compile_key: Optional[Hashable]) -> Tuple[list, list]:
    """What one batch dispatch adds to ``kernel.<kernel>.*``, as the
    (incs, observations) of one ``metrics.update``."""
    (lanes_real, lanes_padded, occupancy, dispatch_seconds, hits,
     misses) = _batch_names(kernel)
    padded = max(int(padded), 1)
    real = min(max(int(real), 0), padded)
    incs = [(lanes_real, real), (lanes_padded, padded)]
    observations = [(occupancy, real / padded, OCCUPANCY_BUCKETS)]
    if seconds is not None:
        observations.append((dispatch_seconds, seconds, DISPATCH_BUCKETS))
    if compile_key is not None:
        with _lock:
            seen = _seen_keys.setdefault(kernel, set())
            hit = compile_key in seen
            if not hit and len(seen) < _MAX_KEYS_PER_KERNEL:
                seen.add(compile_key)
        incs.append((hits if hit else misses, 1))
    return incs, observations


def record_batch(kernel: str, real: int, padded: int,
                 seconds: Optional[float] = None,
                 compile_key: Optional[Hashable] = None) -> None:
    """Record one batch dispatch. ``real`` lanes of ``padded`` total."""
    metrics.update(*_batch_update(kernel, real, padded, seconds,
                                  compile_key))


def record_hoist(kernel: str, rounds: int, words: int) -> None:
    """Record, once a job, how much of the tail block's compression the
    host finished for the job's nonce placement and the search kernel
    therefore leaves out: ``rounds`` of the 64 and ``words`` of the 48
    schedule words (``crypto/sha256.hoisted_counts``; 10 and 4 for a v2
    header).  Over the kernel's jobs: 0 would say the kernel hashes
    every round of every nonce again."""
    metrics.inc("kernel.%s.rounds_hoisted" % kernel, int(rounds))
    metrics.inc("kernel.%s.words_hoisted" % kernel, int(words))


def record_exact_steps(kernel: str, steps: int) -> None:
    """Record, once a round, the grid steps of the sha256 search kernel
    that took the exact pass: a step in which some lane passed the first
    masked compare hashes its tiles again under the whole test
    (``crypto/sha256._search_step``).  Beside ``mine.rounds`` it says
    what share of the kernel's work the exact pass is at the difficulty
    mined: of a one-chip round's 32 steps ~0.004 at 11.0, ~1 at 6.0,
    all at 4.0 and under."""
    metrics.inc("kernel.%s.exact_steps" % kernel, int(steps))


def preregister_stage(stage: str) -> None:
    """Create a pipeline stage's metric families (all-zero) so /metrics
    exports them before the first block flows through the pipeline."""
    metrics.ensure_histogram("pipeline.%s.seconds" % stage,
                             DISPATCH_BUCKETS)
    metrics.ensure_histogram("pipeline.%s.occupancy" % stage,
                             OCCUPANCY_BUCKETS)
    metrics.ensure_counter("pipeline.%s.items" % stage)


def record_stage(stage: str, seconds: float, items: Optional[int] = None,
                 wall: Optional[float] = None) -> None:
    """Record one pipeline stage pass (ISSUE 7: pipelined block verify).

    ``seconds`` is the stage's busy time; ``wall`` (when given) is the
    whole pipeline's wall time for the same pass, making
    ``pipeline.<stage>.occupancy`` the fraction of the pipeline the
    stage kept busy — overlap shows up as stage occupancies summing
    past 1.0, a serialized pipeline as fractions that add to ~1.0.
    """
    metrics.observe("pipeline.%s.seconds" % stage, max(seconds, 0.0),
                    buckets=DISPATCH_BUCKETS)
    if items:
        metrics.inc("pipeline.%s.items" % stage, items)
    if wall is not None and wall > 0:
        metrics.observe("pipeline.%s.occupancy" % stage,
                        min(max(seconds, 0.0) / wall, 1.0),
                        buckets=OCCUPANCY_BUCKETS)


RUNTIME_SOURCES = ("block", "mempool", "mine", "index", "verify",
                   "bench", "other")
RUNTIME_QUEUE_DEPTH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 1024,
                               4096)
RUNTIME_COALESCE_BUCKETS = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 64)


def preregister_runtime(sources=RUNTIME_SOURCES) -> None:
    """Create the device-runtime queue families (device/runtime.py) so
    /metrics exports them before the first submission: per-source
    queue-wait histograms and submission counters, the queue-depth and
    submissions-per-dispatch histograms, and a ``device_runtime``
    kernel occupancy series for the shared dispatches."""
    metrics.ensure_histogram("runtime.queue_depth",
                             RUNTIME_QUEUE_DEPTH_BUCKETS)
    metrics.ensure_histogram("runtime.coalesced", RUNTIME_COALESCE_BUCKETS)
    for c in ("submissions", "dispatches", "faults"):
        metrics.ensure_counter("runtime.%s" % c)
    for s in sources:
        metrics.ensure_histogram("runtime.queue_wait.%s" % s,
                                 DISPATCH_BUCKETS)
        metrics.ensure_counter("runtime.source.%s" % s)
    preregister("device_runtime")


def record_runtime_dispatch(n_submissions: int,
                            waits_by_source: Dict[str, float],
                            depth: int, real: int, padded: int,
                            seconds: float) -> None:
    """Record one device-runtime drain: how many submissions shared the
    dispatch, how long each source's items queued, the queue depth seen
    at pop time, and the occupancy of the padded batch.  One update: a
    miner's ``call`` item pays this once a round."""
    incs, observations = _batch_update("device_runtime", real, padded,
                                       seconds, None)
    incs.append(("runtime.dispatches", 1))
    observations.append(("runtime.coalesced", n_submissions,
                         RUNTIME_COALESCE_BUCKETS))
    observations.append(("runtime.queue_depth", max(depth, 1),
                         RUNTIME_QUEUE_DEPTH_BUCKETS))
    for source, wait in waits_by_source.items():
        observations.append(("runtime.queue_wait.%s" % source,
                             max(wait, 0.0), DISPATCH_BUCKETS))
    metrics.update(incs, observations)


INDEX_KERNELS = ("utxo_probe", "utxo_apply", "accept_fused")


def preregister_index() -> None:
    """Create the HBM-resident UTXO index families (state/device_index.py)
    so /metrics exports them before the first probe: the probe/apply/
    fused kernel series plus the probe counters — ``shadow_consults``
    is the accept path's zero-host-round-trip acceptance signal (it
    stays 0 on collision-free blocks)."""
    for kernel in INDEX_KERNELS:
        preregister(kernel)
    for c in ("probes", "probe_outpoints", "shadow_consults",
              "ambiguous_probes",
              # a block's update (ISSUE 50): rows created + spent that
              # were applied; host -> device bytes of builds, applies
              # and probes' queries; capacity changes after the build;
              # folds of the host mirror's delta into its base (O(N))
              "apply_rows", "upload_bytes", "relayouts", "folds"):
        metrics.ensure_counter("index.%s" % c)


def record_index_probe(outpoints: int, shadow_consults: int,
                       ambiguous: int = 0) -> None:
    """Record one resident-index probe batch: how many outpoints it
    answered, and how many needed the host shadow map (fingerprint
    ambiguity — the steady-state target is zero)."""
    metrics.inc("index.probes")
    metrics.inc("index.probe_outpoints", max(int(outpoints), 0))
    if shadow_consults:
        metrics.inc("index.shadow_consults", int(shadow_consults))
    if ambiguous:
        metrics.inc("index.ambiguous_probes", int(ambiguous))


HIT_LATENCY_BUCKETS = (0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 90.0)


def preregister_mine() -> None:
    """Create the mesh mining families (mine/mesh_engine.py) so /metrics
    exports them before the first round: the ``mine_mesh`` kernel series
    (occupancy = real nonces vs shard capacity; compile-cache counters
    are the no-recompile-job-swap signal) plus the per-shard range
    occupancy and time-to-hit histograms."""
    preregister("mine_mesh")
    metrics.ensure_histogram("mine.shard_occupancy", OCCUPANCY_BUCKETS)
    metrics.ensure_histogram("mine.hit_latency", HIT_LATENCY_BUCKETS)


def record_mine_round(shard_spans, batch_per_device: int,
                      seconds: Optional[float] = None,
                      compile_key: Optional[Hashable] = None) -> None:
    """Record one mesh search round: ``shard_spans`` is the per-shard
    planned nonce count; capacity per shard is ``batch_per_device``.
    The compile key is (batch, n_shards, nonce_spec) — job fields are
    deliberately absent, so a chain-tip change that recompiles would
    surface as a new key = a ``compile_cache_misses`` increment."""
    spans = [max(int(s), 0) for s in shard_spans]
    cap = max(int(batch_per_device), 1)
    incs, observations = _batch_update(
        "mine_mesh", sum(spans), cap * len(spans), seconds, compile_key)
    for span in spans:
        observations.append(("mine.shard_occupancy", min(span / cap, 1.0),
                             OCCUPANCY_BUCKETS))
    metrics.update(incs, observations)


def record_mine_hit(latency_seconds: float) -> None:
    """Record time from job load to winning nonce (mine.hit_latency)."""
    metrics.observe("mine.hit_latency", max(float(latency_seconds), 0.0),
                    buckets=HIT_LATENCY_BUCKETS)


def device_memory() -> Dict[str, dict]:
    """Best-effort per-device memory stats; {} when jax isn't loaded
    or the backend doesn't expose memory_stats (CPU)."""
    if "jax" not in sys.modules:
        return {}
    out: Dict[str, dict] = {}
    try:
        import jax
        # HBM stat sampling from already-initialized devices (called
        # post-arm from the telemetry exporter; never first-touch)
        for dev in jax.local_devices():  # upowlint: disable=DR001
            try:
                stats = dev.memory_stats()
            except Exception as e:
                log.debug("memory_stats failed for %s: %s", dev, e)
                stats = None
            if not stats:
                continue
            label = "%s_%d" % (dev.platform, dev.id)
            out[label] = {k: v for k, v in stats.items()
                          if isinstance(v, (int, float))}
    except Exception as e:
        log.debug("device memory stats unavailable: %s", e)
    return out


def reset() -> None:
    with _lock:
        _seen_keys.clear()
