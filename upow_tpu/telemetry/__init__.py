"""Telemetry subsystem: spans → trace trees, metrics, events, /metrics.

Grown out of the original single-module ``trace.py`` (which remains as
a thin compatibility shim re-exporting this package).  Layers:

* :mod:`.metrics` — flat process-wide aggregates (span stats, counters,
  fixed-bucket histograms) with a cardinality cap.
* :mod:`.tracing` — contextvar-based request-scoped trace trees with a
  bounded ring buffer (recent + slowest) and cross-node trace-ID
  propagation via the ``X-Upow-Trace`` header.
* :mod:`.events` — structured event ring buffer (reorgs, breaker
  trips, degrade transitions, fault injections) for ``/debug/events``.
* :mod:`.device` — TPU/kernel telemetry: batch occupancy, dispatch
  latency, jit compile-cache hit/miss, device memory gauges.
* :mod:`.exposition` — Prometheus 0.0.4 text rendering + the format
  validator used by tests and ``make metrics-check``.

The module-level functions below are the stable API every other
subsystem imports (usually via the ``trace`` shim)."""

from __future__ import annotations

from contextlib import contextmanager
from typing import Optional

from ..logger import get_logger
from . import device, events, exposition, metrics, scope, slo, tracing
from .events import emit as event
from .metrics import (counters, ensure_counter, ensure_histogram,  # noqa: F401
                      histograms, inc, observe, stats, update)
from .scope import TelemetryScope  # noqa: F401
from .tracing import (add_span, attached, child_span, close_trace,  # noqa: F401
                      current_span, current_trace_id, finish_child,
                      new_trace_id, open_trace, open_traces, request_trace,
                      span, traces, valid_trace_id)

log = get_logger("telemetry")

#: HTTP header carrying the trace ID across gossip hops.
TRACE_HEADER = "X-Upow-Trace"

__all__ = [
    "TRACE_HEADER", "TelemetryScope", "add_span", "attached",
    "child_span", "close_trace", "configure", "counters", "current_span",
    "current_trace_id", "device", "ensure_counter", "ensure_histogram",
    "event", "events", "exposition", "finish_child", "histograms",
    "inc", "metrics", "new_trace_id", "observe", "open_trace", "open_traces",
    "profile", "request_trace", "reset", "scope", "slo", "span",
    "stats", "traces", "tracing", "update", "valid_trace_id",
]


def configure(cfg=None) -> None:
    """Apply a TelemetryConfig (config.py) and pre-register the metric
    families the acceptance criteria require to exist from scrape #1
    (occupancy / compile-cache series for the batch kernels)."""
    if cfg is not None:
        metrics.set_max_names(cfg.max_metric_names)
        tracing.configure(recent=cfg.trace_recent,
                          slowest=cfg.trace_slowest,
                          max_spans=cfg.max_trace_spans)
        events.configure(cfg.events_buffer)
    # incremental event-cursor loss counter (events.since): exported
    # all-zero from scrape #1 so metrics-check can pin the name
    metrics.ensure_counter(events.ROTATED_UNSEEN)
    device.preregister("p256_verify")
    metrics.ensure_counter("kernel.p256_verify.pallas_fallbacks")
    # who really served: exported at zero from scrape #1 so a client
    # (chip_smoke.py) reads "0 fallbacks", never "no such metric"
    for name in ("compile_cache.persistent_hits",
                 "compile_cache.persistent_misses",
                 "verify.canary_pass", "verify.canary_fail",
                 "resilience.device_fallback"):
        metrics.ensure_counter(name)
    # the write-ahead log's keeper (state/storage.py): checkpoints run,
    # the frames they left folded, and those the committing thread had
    # to run itself because the log had outgrown its bound
    for name in ("state.checkpoints", "state.checkpoint_frames",
                 "state.checkpoint_inline"):
        metrics.ensure_counter(name)
    device.preregister("sha256_txid")
    device.preregister_runtime()
    device.preregister_index()
    device.preregister_mine()
    for stage in ("block_decode", "block_sig_wait", "accept_probe"):
        device.preregister_stage(stage)
    # shared sig dispatch front (verify/dispatch.py) — deferred import:
    # telemetry must stay importable without the verify package
    try:
        from ..verify.dispatch import preregister as _front_preregister

        _front_preregister()
    except Exception as e:  # pragma: no cover - import-cycle guard
        log.debug("dispatch front preregister skipped: %s", e)


def reset() -> None:
    """Clear every registry and buffer (tests)."""
    metrics.reset()
    tracing.reset()
    events.reset()
    device.reset()


@contextmanager
def profile(trace_dir: Optional[str] = None):
    """Capture a JAX profiler trace into ``trace_dir`` (xprof format).

    No-op when trace_dir is falsy or the profiler is unavailable.  Only
    profiler SETUP/TEARDOWN failures are swallowed — exceptions raised
    by the caller's body must propagate untouched (a yield inside a
    try/except would eat them and then crash contextlib)."""
    if not trace_dir:
        yield
        return
    ctx = None
    try:
        import jax

        ctx = jax.profiler.trace(trace_dir)
        ctx.__enter__()
    except Exception as e:  # profiling must never break the caller
        log.warning("jax profiler unavailable: %s", e)
        ctx = None
    try:
        yield
    finally:
        if ctx is not None:
            try:
                ctx.__exit__(None, None, None)
            except Exception as e:
                log.warning("jax profiler teardown failed: %s", e)
