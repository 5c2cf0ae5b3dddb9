"""Per-process device-runtime service: one owner for the TPU.

ROADMAP item 3, the kernel-server refactor.  Every device dispatch in
the package flows through this module's single drainer thread:

* **One arm.**  The runtime owns backend arming — one probe per
  process (:func:`probe_platform`, thread-boxed: a backend init that
  hangs must not wedge the process), under a deadline, with the
  structured ``arm_failure_reason`` captured and the persistent compile
  cache enabled (compile_cache.enable — the one place any process turns it
  on).  Nothing is compiled at arm: the first dispatch of each program
  compiles it, under the caller's compile allowance.  What a failed
  probe means is decided by config ``device.device`` (:func:`start`):
  ``tpu`` exits, ``auto`` serves every source on the host paths.
* **One queue, many sources.**  Subsystems submit typed work items —
  P-256 sig batches (``submit_sig_checks``), boxed device calls
  (``run_boxed``), generic dispatch closures (``submit_call``) — tagged
  with a *source* (``block``, ``mempool``, ``mine``, ``index``,
  ``bench``...).  Per-source FIFO queues are drained by weighted
  fair-share scheduling (stride accounting: each served item charges
  ``cost / weight`` to its source's virtual pass), so a saturating
  miner stream cannot starve block verify past a bounded wait.
* **Cross-source coalescing.**  When a sig batch is served, every
  queued sig batch with the same dispatch key — across ALL sources —
  rides in the same ``run_sig_checks`` call, generalizing what
  verify/dispatch.py (now a thin client of this service) did per event
  loop.  Verdict semantics are byte-identical to the serial paths: the
  runtime changes WHO shares a dispatch, never what is computed.
* **One choke point.**  resilience/degrade.py's state is consulted at
  execution time, not submission time: a degrade flip mid-flight means
  the already-queued items execute on the host path (run_sig_checks'
  own backend resolution), with byte-identical verdicts.  The
  ``device.runtime`` fault site fires before every dispatch; injected
  faults degrade and drain to the host instead of failing callers.

Telemetry (telemetry/device.py): per-source queue-wait histograms, a
queue-depth histogram, submissions-per-dispatch coalescing, and a
``device_runtime`` kernel occupancy series for the shared dispatches.
"""

from __future__ import annotations

import contextlib
import contextvars
import hashlib
import os
import sys
import threading
import time
from collections import deque
from concurrent.futures import Future, InvalidStateError
from concurrent.futures import TimeoutError as FutureTimeoutError
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..logger import get_logger
from ..telemetry import device as ktel
from ..telemetry import metrics, tracing

log = get_logger("device.runtime")


def _sanitizer_check(site: str) -> None:
    """Thread-affinity assertion at the submit/drain seam: under the
    test/CI concurrency sanitizer, a blocking boxed wait entered from
    an event-loop thread is recorded as a finding.  The sanitizer
    module is imported lazily so plain production imports pay nothing;
    once imported, the inactive path is a single None check."""
    sanitizer = sys.modules.get("upow_tpu.lint.sanitizer")
    if sanitizer is not None:
        sanitizer.check_blocking_wait(f"device.runtime.{site}")


def boxed_call(fn: Callable[[], Any], timeout: float):
    """Run ``fn`` on a daemon thread with a deadline.

    Returns ("ok", result) | ("err", exception) | ("timeout", None).
    The one home of the hang-survival idiom: a call stuck inside the
    PJRT client can neither be interrupted nor joined — the daemon
    thread is abandoned and the caller decides what degraded mode means.
    """
    _sanitizer_check("boxed_call")

    box: dict = {}
    # carry the caller's contextvars into the worker so telemetry
    # emitted inside the boxed call (fault events, spans) keeps the
    # caller's trace ID — a bare Thread starts with an empty context
    ctx = contextvars.copy_context()

    def run():
        try:
            box["ok"] = ctx.run(fn)
        except Exception as e:
            box["err"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout)
    if "ok" in box:
        return "ok", box["ok"]
    if "err" in box:
        return "err", box["err"]
    return "timeout", None


def text_fingerprint(text: str) -> str:
    """Short stable hash of diagnostic text (stderr tails, frame lists)
    so repeated arm failures can be grouped without comparing full
    tracebacks."""
    return hashlib.sha256(text.encode("utf-8", "replace")).hexdigest()[:12]


def traceback_fingerprint(exc: BaseException) -> str:
    """Fingerprint of an exception's traceback SHAPE (file:function per
    frame, no line numbers or message text): two arm attempts that died
    on the same code path share a fingerprint even when addresses or
    timeouts in the message differ."""
    import traceback as _tb

    frames = _tb.extract_tb(exc.__traceback__) if exc.__traceback__ else []
    sig = "|".join("%s:%s" % (f.filename.rsplit("/", 1)[-1], f.name)
                   for f in frames[-8:])
    return text_fingerprint("%s|%s" % (type(exc).__name__, sig))


def probe_platform(timeout: float) -> dict:
    """The backend probe: ``jax.devices()[0].platform`` under
    :func:`boxed_call`, because backend init can HANG inside
    ``jax.devices()`` (exceptions are the easy case).  Returns
    ``{status, platform, seconds, error, traceback_fingerprint}``:
    ``status`` is the boxed_call outcome ("ok" / "err" / "timeout"),
    ``platform`` is None unless ok, and ``error`` is the actual
    exception text.  Only :meth:`DeviceRuntime.arm` calls it, once a
    process; everything else asks :meth:`DeviceRuntime.platform`."""
    import jax

    t0 = time.perf_counter()
    status, value = boxed_call(lambda: jax.devices()[0].platform, timeout)
    record = {"status": status, "platform": None,
              "seconds": round(time.perf_counter() - t0, 3),
              "error": None, "traceback_fingerprint": None}
    if status == "ok":
        record["platform"] = value
    elif status == "timeout":
        record["error"] = ("backend init still inside jax.devices() after "
                           "%.0fs (native hang; no Python exception to "
                           "show)" % timeout)
    else:  # "err": value IS the exception boxed_call caught
        record["error"] = repr(value)
        if isinstance(value, BaseException):
            record["traceback_fingerprint"] = traceback_fingerprint(value)
    return record


#: the process's one probe record, whichever DeviceRuntime arms first
#: (tests build several): a hung backend costs a process ONE timeout
_PROBE: Optional[dict] = None
_PROBE_LOCK = threading.Lock()


_WAITS_CAP = 8192  # per-source queue-wait samples kept for stats()


class _Item:
    __slots__ = ("kind", "key", "checks", "precomputed", "fn", "timeout",
                 "kernel", "source", "fut", "t0", "ctx")

    def __init__(self, kind, *, key=None, checks=None, precomputed=None,
                 fn=None, timeout=None, kernel="call", source="other"):
        self.kind = kind            # "sig" | "call"
        self.key = key              # sig coalescing key
        self.checks = checks
        self.precomputed = precomputed
        self.fn = fn
        self.timeout = timeout      # not None -> boxed execution
        self.kernel = kernel
        self.source = source
        self.fut: Future = Future()
        self.t0 = time.perf_counter()
        # the drainer executes in the submitter's contextvars so
        # telemetry emitted inside the dispatch (degrade events, fault
        # records, spans) keeps the submitter's trace ID
        self.ctx = contextvars.copy_context()

    @property
    def cost(self) -> int:
        return max(1, len(self.checks)) if self.kind == "sig" else 1


def _resolve(fut: Future, value) -> None:
    try:
        fut.set_result(value)
    except InvalidStateError:  # cancelled by an abandoning awaiter
        pass


def _fail(fut: Future, exc: BaseException) -> None:
    try:
        fut.set_exception(exc)
    except InvalidStateError:
        pass


class DeviceRuntime:
    """The per-process device owner: queues in, results out."""

    def __init__(self, cfg=None):
        if cfg is None:
            from ..config import DeviceRuntimeConfig

            cfg = DeviceRuntimeConfig.from_env()
        self.cfg = cfg
        self._weights = cfg.parsed_weights()
        self._cv = threading.Condition()
        self._queues: Dict[str, deque] = {}
        self._passes: Dict[str, float] = {}
        self._vtime = 0.0
        self._holds = 0
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._arm_lock = threading.Lock()
        self._arm_done = threading.Event()
        self._arm_info: Dict[str, Any] = {
            "armed": False, "platform": None, "attempt": None,
            "arm_failure_reason": None, "probe_seconds": None,
            "device_kind": None, "device_count": 0,
            "compile_cache_dir": "",
        }
        # introspection for tests/benches
        self.submissions = 0
        self.dispatches = 0
        self.source_submissions: Dict[str, int] = {}
        self._waits: Dict[str, List[float]] = {}

    # ------------------------------------------------------------ arming --

    def arm(self, deadline: Optional[float] = None,
            attempt: str = "runtime") -> dict:
        """Probe/initialize the backend once, under a deadline.

        Returns the arm-info dict (platform, device_kind, device_count,
        arm_failure_reason, compile_cache_dir).  Idempotent: whoever
        calls first arms (a ``device=tpu`` process at start-up, any
        other the drainer thread before it serves its first item)."""
        with self._arm_lock:
            info = self._arm_info
            if info["armed"]:
                return dict(info)
            from .. import compile_cache

            # before the backend exists, so every program this process
            # compiles goes through the one persistent cache
            info["compile_cache_dir"] = compile_cache.enable()
            timeout = self.cfg.arm_timeout if deadline is None else deadline
            t0 = time.perf_counter()
            global _PROBE
            with _PROBE_LOCK:
                if _PROBE is None:
                    _PROBE = probe_platform(timeout)
                probe = _PROBE
            elapsed = time.perf_counter() - t0
            platform = probe["platform"]
            info.update(platform=platform, attempt=attempt,
                        probe_seconds=round(elapsed, 3), armed=True)
            if platform is None:
                # the probe's ACTUAL failure text (exception repr or
                # explicit-hang note), not a generic "hung/failed"
                info["arm_failure_reason"] = probe["error"]
                info["probe_status"] = probe["status"]
                info["traceback_fingerprint"] = \
                    probe["traceback_fingerprint"]
                log.warning("device runtime armed WITHOUT a backend (%s)",
                            info["arm_failure_reason"])
            else:
                import jax

                devices = jax.devices()
                info.update(arm_failure_reason=None,
                            device_kind=devices[0].device_kind,
                            device_count=len(devices))
            self._arm_done.set()
            try:
                from ..telemetry import events

                events.emit("device_runtime_armed",
                            platform=platform or "none",
                            device_kind=info["device_kind"] or "",
                            device_count=info["device_count"],
                            attempt=attempt,
                            reason=info["arm_failure_reason"] or "")
            except Exception as e:
                log.debug("arm telemetry event not recorded: %s", e)
            return dict(info)

    def platform(self) -> Optional[str]:
        """Armed platform string ("tpu"/"cpu"/...; None = probe failed).
        Blocks until the arm resolves the platform."""
        self._ensure_thread()
        self._arm_done.wait(timeout=self.cfg.arm_timeout + 30.0)
        return self._arm_info["platform"]

    def devices(self) -> list:
        """Post-arm ``jax.devices()`` ([] when the probe failed) — the
        one sanctioned enumeration point (upowlint DR001)."""
        if self.platform() is None:
            return []
        import jax

        return jax.devices()

    # -------------------------------------------------------- submission --

    def submit_sig_checks(self, checks: Sequence[tuple], *,
                          backend: str = "auto", pad_block: int = 128,
                          device_timeout: float = 240.0,  # operational timeout  # upowlint: disable=CP001
                          mesh_devices: int = 1,
                          precomputed: Optional[dict] = None,
                          source: str = "other") -> Future:
        """Queue one P-256 sig batch; the Future resolves to its verdict
        list (txverify.run_sig_checks semantics, byte-identical).
        Batches sharing (backend, pad_block, device_timeout,
        mesh_devices, precomputed identity) coalesce into one dispatch
        across ALL sources."""
        if not checks:
            fut: Future = Future()
            fut.set_result([])
            return fut
        key = (backend, pad_block, device_timeout, mesh_devices,
               id(precomputed) if precomputed is not None else None)
        item = _Item("sig", key=key, checks=list(checks),
                     precomputed=precomputed, source=source)
        self._enqueue(item)
        return item.fut

    def submit_call(self, fn: Callable[[], Any], *, kernel: str = "call",
                    source: str = "other",
                    timeout: Optional[float] = None) -> Future:
        """Queue a device-dispatch closure.  With ``timeout`` the call
        is thread-boxed and the Future resolves to boxed_call's
        (status, value) tuple; without it the Future carries ``fn()``'s
        result (or exception).  Called from the drainer thread itself
        (a dispatch nested inside a dispatch) it executes inline —
        queueing would deadlock the single drainer."""
        if threading.current_thread() is self._thread:
            fut: Future = Future()
            try:
                if timeout is not None:
                    fut.set_result(boxed_call(fn, timeout))
                else:
                    fut.set_result(fn())
            # the exception travels to the caller inside the future
            except Exception as e:  # upowlint: disable=BE001
                fut.set_exception(e)
            return fut
        item = _Item("call", fn=fn, timeout=timeout, kernel=kernel,
                     source=source)
        self._enqueue(item)
        return item.fut

    def run_boxed(self, fn: Callable[[], Any], timeout: float, *,
                  kernel: str = "call", source: str = "other"):
        """Blocking boxed dispatch through the queue: returns
        ("ok", result) | ("err", exc) | ("timeout", None) exactly like
        boxed_call, but serialized through the device owner.  The safety
        margin on the outer wait covers arm + queue time; if even that
        is exceeded the caller sees a plain timeout."""
        _sanitizer_check("run_boxed")
        fut = self.submit_call(fn, kernel=kernel, source=source,
                               timeout=timeout)
        try:
            return fut.result(timeout=timeout + self.cfg.arm_timeout + 60.0)
        except FutureTimeoutError:
            return "timeout", None

    @contextlib.contextmanager
    def hold(self):
        """Pause draining (tests/benches: build a coalescing window
        deterministically).  Items queue while held; release drains."""
        with self._cv:
            self._holds += 1
        try:
            yield self
        finally:
            with self._cv:
                self._holds -= 1
                self._cv.notify_all()

    def stats(self) -> dict:
        """Queue/dispatch introspection snapshot (benches, tests)."""
        with self._cv:
            depths = {s: len(q) for s, q in self._queues.items() if q}
            waits = {s: list(w) for s, w in self._waits.items()}
        return {
            "submissions": self.submissions,
            "dispatches": self.dispatches,
            "per_source": dict(self.source_submissions),
            "queue_depth": depths,
            "queue_waits": waits,
            "arm": dict(self._arm_info),
        }

    # ----------------------------------------------------------- drainer --

    def _enqueue(self, item: _Item) -> None:
        with self._cv:
            if self._stop:
                raise RuntimeError("device runtime stopped")
            q = self._queues.setdefault(item.source, deque())
            if len(q) >= self.cfg.queue_max:
                raise RuntimeError(
                    "device runtime queue overflow for source %r "
                    "(max %d)" % (item.source, self.cfg.queue_max))
            if not q:
                # a source waking from idle starts at the current
                # virtual time — banked idleness must not let it
                # monopolize the device once it bursts
                self._passes[item.source] = max(
                    self._passes.get(item.source, 0.0), self._vtime)
            q.append(item)
            self.submissions += 1
            self.source_submissions[item.source] = \
                self.source_submissions.get(item.source, 0) + 1
            metrics.update((("runtime.submissions", 1),
                            ("runtime.source.%s" % item.source, 1)))
            self._cv.notify_all()
        self._ensure_thread()

    def _ensure_thread(self) -> None:
        with self._cv:
            if self._stop or (self._thread is not None
                              and self._thread.is_alive()):
                return
            self._thread = threading.Thread(
                target=self._drain_loop, daemon=True,
                name="upow-device-runtime")
            self._thread.start()

    def _drain_loop(self) -> None:
        try:
            self.arm()
        except Exception as e:  # arm must never kill the drainer
            log.warning("device runtime arm failed: %s", e)
            self._arm_info.update(
                armed=True, platform=None,
                arm_failure_reason="arm raised: %r" % (e,))
        finally:
            self._arm_done.set()
        while True:
            with self._cv:
                while not self._stop and (
                        self._holds > 0
                        or not any(self._queues.values())):
                    self._cv.wait(timeout=1.0)
                if self._stop:
                    return
                depth = sum(len(q) for q in self._queues.values())
                group = self._pop_group_locked()
            if not group:
                continue
            try:
                self._execute(group, depth)
            except Exception as e:  # belt: futures already failed below
                log.warning("device runtime dispatch raised: %s", e)
                for m in group:
                    _fail(m.fut, e)

    def _pop_group_locked(self) -> List[_Item]:
        active = [s for s, q in self._queues.items() if q]
        if not active:
            return []
        # weighted fair share (stride): serve the source with the least
        # accumulated virtual pass; ties break on source name for
        # determinism
        src = min(active, key=lambda s: (self._passes.get(s, 0.0), s))
        head = self._queues[src].popleft()
        group = [head]
        if head.kind == "sig":
            # cross-source coalescing: pull every queued compatible sig
            # batch (same dispatch key) into this dispatch, scan order
            # fixed for determinism
            for s in sorted(self._queues):
                q = self._queues[s]
                if not q:
                    continue
                keep: deque = deque()
                while q:
                    cand = q.popleft()
                    if (len(group) < self.cfg.max_coalesce
                            and cand.kind == "sig"
                            and cand.key == head.key):
                        group.append(cand)
                    else:
                        keep.append(cand)
                self._queues[s] = keep
        for m in group:
            w = self._weights.get(m.source,
                                  self._weights.get("other", 1))
            self._passes[m.source] = self._passes.get(m.source, 0.0) \
                + m.cost / max(w, 1)
        self._vtime = self._passes.get(src, 0.0)
        return group

    def _record_waits(self, group: List[_Item], now: float) -> None:
        with self._cv:
            for m in group:
                wait = max(0.0, now - m.t0)
                lst = self._waits.setdefault(m.source, [])
                if len(lst) >= _WAITS_CAP:
                    del lst[: _WAITS_CAP // 2]
                lst.append(wait)

    def _execute(self, group: List[_Item], depth: int) -> None:
        now = time.perf_counter()
        self._record_waits(group, now)
        self.dispatches += 1
        if group[0].kind == "sig":
            self._execute_sig(group, depth, now)
        else:
            self._execute_call(group[0], depth, now)

    def _execute_sig(self, group: List[_Item], depth: int,
                     t0: float) -> None:
        flat: List[tuple] = []
        slices: List[Tuple[int, int]] = []
        for m in group:
            slices.append((len(flat), len(flat) + len(m.checks)))
            flat.extend(m.checks)
        backend, pad_block, device_timeout, mesh_devices, _ = group[0].key
        # module-attr lookup so established monkeypatch seams on
        # txverify.run_sig_checks keep intercepting the shared dispatch
        from ..verify import txverify

        waits = {m.source: time.perf_counter() - m.t0 for m in group}
        def dispatch(be: str):
            sources = ",".join(sorted({m.source for m in group}))
            self._fire_fault("sig:" + sources)
            with tracing.span("runtime.sig", sources=sources, n=len(flat)):
                return txverify.run_sig_checks(
                    flat, backend=be, pad_block=pad_block,
                    device_timeout=device_timeout,
                    precomputed=group[0].precomputed,
                    mesh_devices=mesh_devices)

        try:
            # run inside the triggering submitter's contextvars so
            # degrade/fault events raised by the shared dispatch carry
            # a real trace ID instead of the drainer's empty context
            verdicts = group[0].ctx.run(dispatch, backend)
        except Exception as e:
            from ..resilience.faultinject import FaultInjected

            if isinstance(e, FaultInjected):
                # the choke point: an injected dispatch fault degrades
                # the device path and drains this group onto the host —
                # byte-identical verdicts, callers never see the fault
                txverify.DEGRADE.record_failure(e)
                metrics.inc("runtime.faults")
                log.warning("device.runtime fault injected; group of %d "
                            "drains to host", len(group))
                try:
                    verdicts = group[0].ctx.run(
                        txverify.run_sig_checks,
                        flat, backend="host", pad_block=pad_block,
                        device_timeout=device_timeout,
                        precomputed=group[0].precomputed,
                        mesh_devices=mesh_devices)
                # exceptions travel to every submitter inside the futures
                except Exception as e2:  # upowlint: disable=BE001
                    for m in group:
                        _fail(m.fut, e2)
                    return
            else:
                for m in group:
                    _fail(m.fut, e)
                return
        finally:
            padded = max(pad_block, 1) * (
                (len(flat) + max(pad_block, 1) - 1) // max(pad_block, 1))
            ktel.record_runtime_dispatch(
                n_submissions=len(group), waits_by_source=waits,
                depth=depth, real=len(flat), padded=padded,
                seconds=time.perf_counter() - t0)
        for m, (lo, hi) in zip(group, slices):
            _resolve(m.fut, verdicts[lo:hi])

    def _execute_call(self, item: _Item, depth: int, t0: float) -> None:
        waits = {item.source: time.perf_counter() - item.t0}

        def wrapped():
            self._fire_fault("call:%s" % item.kernel)
            # light: a miner submits one call a round
            with tracing.span("runtime.call", light=True,
                              kernel=item.kernel, source=item.source):
                return item.fn()

        try:
            if item.timeout is not None:
                # boxed mode: faults/hangs become the status tuple, the
                # caller applies its own degrade policy (txverify,
                # sha256 crossover).  Entered inside the submitter's
                # context so boxed_call's own context copy carries the
                # submitter's trace ID into the worker thread.
                result = item.ctx.run(boxed_call, wrapped, item.timeout)
                _resolve(item.fut, result)
            else:
                _resolve(item.fut, item.ctx.run(wrapped))
        # the exception travels to the caller inside the future
        except Exception as e:  # upowlint: disable=BE001
            _fail(item.fut, e)
        finally:
            ktel.record_runtime_dispatch(
                n_submissions=1, waits_by_source=waits, depth=depth,
                real=1, padded=1, seconds=time.perf_counter() - t0)

    def _fire_fault(self, key: str) -> None:
        from ..resilience.faultinject import get_injector

        injector = get_injector()
        if injector is not None:
            injector.fire_sync("device.runtime", key=key)

    def close(self) -> None:
        """Stop the drainer and fail anything still queued (tests)."""
        with self._cv:
            self._stop = True
            pending = [m for q in self._queues.values() for m in q]
            self._queues.clear()
            self._cv.notify_all()
        for m in pending:
            _fail(m.fut, RuntimeError("device runtime stopped"))
        t = self._thread
        if t is not None and t.is_alive() \
                and t is not threading.current_thread():
            t.join(timeout=2.0)


_RUNTIME: Optional[DeviceRuntime] = None
_RUNTIME_LOCK = threading.Lock()


DEVICE_MODES = ("auto", "tpu", "cpu")
_DEVICE_MODE = "auto"


class DeviceUnavailable(RuntimeError):
    """``device=tpu`` was asked for and the arm found no TPU; the text
    is the arm's own failure reason."""


def start(device: str) -> dict:
    """Apply config ``device.device`` once, where a process starts
    (node/run.py, the miner CLI) — before it listens or fetches a job.

    * ``tpu``  — arm now; raise :class:`DeviceUnavailable` unless
      ``jax.devices()[0].platform == "tpu"``.  From here on the process
      has no road back to the host: a Pallas lowering/compile error
      raises instead of falling to the jnp program, and a failed or
      timed-out device verify raises instead of re-running on the host
      (:func:`tpu_required` is what those sites ask).
    * ``cpu``  — pin JAX to the CPU backend; no TPU backend is ever
      initialised by this process.
    * ``auto`` — nothing here: the drainer arms lazily and a missing or
      failing device degrades to the host paths (resilience/degrade.py).

    Returns the arm info (empty for ``cpu``/``auto``)."""
    global _DEVICE_MODE
    if device not in DEVICE_MODES:
        raise ValueError("device.device must be one of %s, not %r"
                         % ("|".join(DEVICE_MODES), device))
    _DEVICE_MODE = device
    if device == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "jax" in sys.modules:
            sys.modules["jax"].config.update("jax_platforms", "cpu")
        return {}
    if device == "auto":
        return {}
    info = get_runtime().arm()
    if info["platform"] != "tpu":
        raise DeviceUnavailable(
            "device=tpu but %s" % (
                info["arm_failure_reason"]
                or "jax.devices()[0].platform is %r" % info["platform"]))
    return info


def device_line(info: dict) -> str:
    """The one start-up line that says which device serves (printed by
    the node and the miner; ``chip_smoke.py`` reads it back)."""
    return ("device: platform=%s kind=%s count=%d compile_cache=%s" % (
        info["platform"], info["device_kind"], info["device_count"],
        info["compile_cache_dir"]))


def tpu_required() -> bool:
    """True in a ``device=tpu`` process: no fallback to the host or to
    the jnp program after a device failure (see :func:`start`)."""
    return _DEVICE_MODE == "tpu"


def get_runtime() -> DeviceRuntime:
    """The process-wide device runtime (lazily created; the drainer
    thread starts on first submission)."""
    global _RUNTIME
    with _RUNTIME_LOCK:
        if _RUNTIME is None:
            _RUNTIME = DeviceRuntime()
        return _RUNTIME


def reset_runtime() -> None:
    """Tear down the singleton (tests): stops the drainer, fails queued
    futures, and lets the next get_runtime() build a fresh service."""
    global _RUNTIME, _DEVICE_MODE
    with _RUNTIME_LOCK:
        rt, _RUNTIME = _RUNTIME, None
        _DEVICE_MODE = "auto"
    if rt is not None:
        rt.close()
