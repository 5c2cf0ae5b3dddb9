"""Opt-in kernel profiling: a ``jax.profiler`` capture session.

Off unless asked for (``UPOW_PROFILE_*`` / ``ProfilingConfig``), and
safe to call when jax is absent or broken — profiling must never take
the process down.

:func:`start` / :func:`stop` / :func:`status` drive one process-wide
``jax.profiler`` capture (xprof trace directory): the node's
``/debug/profile?action=start|stop|status`` endpoint and the miner's
SIGUSR1 / SIGUSR2 hook (``mine/miner.py``) call them.  One capture at a
time; a capture left running past ``max_capture_seconds`` is auto-closed
on the next touch so a forgotten start can't fill the disk.  Every
telemetry span open during a capture is a host event of the trace
(``telemetry/tracing.py``), on the device events' clock.
"""

from __future__ import annotations

import threading
import time
from ..logger import get_logger
from ..telemetry import event as _event

log = get_logger("profiling")

_lock = threading.Lock()
_session: dict = {}  # {trace_dir, started_at, max_seconds} while active


def _expire_locked(now: float) -> None:
    """Close an over-deadline capture (caller holds ``_lock``)."""
    if not _session:
        return
    limit = _session.get("max_seconds") or 0
    if limit and now - _session["started_at"] > limit:
        log.warning("profiler capture exceeded %.0fs; auto-stopping", limit)
        _stop_locked(reason="max_capture_seconds")


def _stop_locked(reason: str = "requested") -> dict:
    info = {"trace_dir": _session.get("trace_dir"),
            "seconds": round(time.monotonic()
                             - _session.get("started_at", 0.0), 3),
            "reason": reason}
    try:
        import jax

        jax.profiler.stop_trace()
    except Exception as e:  # teardown must not propagate to the endpoint
        log.warning("jax profiler stop failed: %s", e)
        info["error"] = f"{type(e).__name__}: {e}"[:200]
    _session.clear()
    _event("profile_capture_stopped", **info)
    return info


def start(trace_dir: str, max_seconds: float = 0.0) -> dict:
    """Begin a capture into ``trace_dir``.  Returns a status dict; on
    failure ``{"error": ...}`` rather than raising."""
    with _lock:
        _expire_locked(time.monotonic())
        if _session:
            return {"error": "capture already active",
                    "trace_dir": _session["trace_dir"]}
        try:
            import jax

            jax.profiler.start_trace(trace_dir)
        except Exception as e:
            log.warning("jax profiler start failed: %s", e)
            return {"error": f"{type(e).__name__}: {e}"[:200]}
        _session.update(trace_dir=trace_dir,
                        started_at=time.monotonic(),
                        max_seconds=max_seconds)
        _event("profile_capture_started", trace_dir=trace_dir)
        return {"active": True, "trace_dir": trace_dir}


def stop() -> dict:
    """End the active capture; {"error": ...} when none is running."""
    with _lock:
        if not _session:
            return {"error": "no capture active"}
        return _stop_locked()


def status() -> dict:
    with _lock:
        _expire_locked(time.monotonic())
        if not _session:
            return {"active": False}
        return {"active": True, "trace_dir": _session["trace_dir"],
                "seconds": round(time.monotonic()
                                 - _session["started_at"], 3)}


def reset() -> None:
    """Forget any active session without touching jax (tests)."""
    with _lock:
        _session.clear()
