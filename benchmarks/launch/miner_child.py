#!/usr/bin/env python3
"""The miner CLI, in this process, with the two things the CLI lacks and
the benchmark's contract needs from the process that holds the chip: the
device's peak memory printed at exit, and (``--trace-dir``) a profiler
trace switched on and off by the parent.

    miner_child.py [--trace-dir DIR] [--fault NAME] -- <arguments of
                                                  upow_tpu.mine.miner>

It calls ``upow_tpu.mine.miner.main(argv)`` with ``UPOW_MINER_CHILD=1``:
exactly the process the CLI's own supervisor would spawn.  SIGUSR1 starts
the trace, SIGUSR2 stops it, SIGTERM ends the miner; each is answered on
stdout (``trace: started unix=...``, ``memory: peak_bytes=...``).
``--fault`` (a control run only, never a run of the benchmark) breaks
the miner as ``faults.py`` says, so that ``correct`` is seen to fail.
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time


def _peak_bytes():
    """Peak bytes in use on the fullest local device; None where the
    backend keeps no such statistic (the CPU)."""
    import jax

    peaks = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _say(line: str) -> None:
    """One write, newline and all: the miner's thread prints meanwhile,
    and ``print`` writes a line and its end apart."""
    sys.stdout.write(line + "\n")
    sys.stdout.flush()


def _tracer(trace_dir: str, start: threading.Event,
            stop: threading.Event) -> None:
    import jax

    start.wait()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # device and runtime events only
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    # one host span over the traced window: its ends put the parent's
    # clock (unix, printed here) on the profiler's
    with jax.profiler.TraceAnnotation("perfbench.window"):
        _say(f"trace: started unix={time.time():.6f}")
        stop.wait()
        stopped = time.time()
    jax.profiler.stop_trace()
    _say(f"trace: stopped unix={stopped:.6f}")


def main() -> int:
    argv = sys.argv[1:]
    trace_dir = fault = None
    while argv and argv[0] != "--":
        if argv[0] == "--trace-dir":
            trace_dir = argv[1]
        elif argv[0] == "--fault":
            fault = argv[1]
        else:
            raise SystemExit(f"miner_child.py: unknown option {argv[0]}")
        argv = argv[2:]
    argv = argv[1:]
    os.environ["UPOW_MINER_CHILD"] = "1"
    start, stop = threading.Event(), threading.Event()
    tracer = None
    if trace_dir:
        tracer = threading.Thread(target=_tracer, daemon=True, name="tracer",
                                  args=(trace_dir, start, stop))
        tracer.start()
        signal.signal(signal.SIGUSR1, lambda *_a: start.set())
        signal.signal(signal.SIGUSR2, lambda *_a: stop.set())

    def on_term(*_a):
        raise SystemExit(0)

    signal.signal(signal.SIGTERM, on_term)
    from upow_tpu.mine import miner

    if fault:
        import faults

        faults.apply(fault)
    rc = 1
    try:
        rc = miner.main(argv)
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 1
    finally:
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        if tracer is not None and start.is_set():
            stop.set()
            tracer.join(timeout=90)
        if rc != miner.RC_NO_DEVICE:   # no device: nothing to read
            try:
                peak = _peak_bytes()
            except Exception as e:  # the backend may be gone; say so
                print(f"memory: unreadable ({type(e).__name__}: {e})",
                      flush=True)
            else:
                print("memory: peak_bytes="
                      f"{'null' if peak is None else peak}", flush=True)
        sys.stdout.flush()
    # the device runtime's threads are not all daemons; do not wait on them
    os._exit(rc or 0)


if __name__ == "__main__":
    main()
