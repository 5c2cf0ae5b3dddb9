#!/usr/bin/env python3
"""The miner CLI, in this process, with the two things the CLI lacks and
the benchmark's contract needs from the process that holds the chip: the
device's peak memory said whenever the parent asks and again at exit,
and (``--trace-dir``) a profiler trace switched on and off by the parent.

    miner_child.py [--trace-dir DIR] [--fault NAME] -- <arguments of
                                                  upow_tpu.mine.miner>

It calls ``upow_tpu.mine.miner.main(argv)`` with ``UPOW_MINER_CHILD=1``:
exactly the process the CLI's own supervisor would spawn.  Four signals,
each answered by one line on stdout:

    SIGUSR1   start the trace         ``trace: started unix=...``
    SIGUSR2   stop it                 ``trace: stopped unix=...``
    SIGRTMIN  say the peak memory     ``memory: peak_bytes=<n>|null`` or
                                      ``memory: unreadable (<why>)``
    SIGTERM   end: an open trace is stopped, the memory line is said
              once more, and the process leaves by ``os._exit(0)``

No signal has a handler.  All four are blocked (``pthread_sigmask``)
while the main thread is the only one, so every thread that Python, jax
or the device runtime starts later inherits the mask, and one thread of
this file takes them with ``signal.sigwait``.  So an answer needs
neither the miner's thread nor a bytecode boundary of it: a main thread
inside a compile, a collection, a ``__del__`` or a late answer of the
device cannot delay or lose it, and nothing is raised into the miner's
frames (a ``SystemExit`` raised by a handler inside a ``gc`` callback or
a ``__del__`` is printed as ``Exception ignored in:`` and dropped: the
miner mined on and no ``memory:`` line was ever written).  The exit is
the launcher's own, as the miner's hang watchdog's is: what
``miner.main``'s ``finally`` prints under ``ProfilingConfig.enabled``
(no cell sets it) is skipped.  Every line said here is one ``write`` of
the whole line: the miner's thread prints meanwhile, and ``print``
writes a line and its end apart.  Run it unbuffered
(``PYTHONUNBUFFERED=1``, as ``harness/procs.py`` does).

``--fault`` (a control run only, never a run of the benchmark) breaks
the miner or this launcher as ``faults.py`` says, so that the run is
seen to fail.
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time

#: "say your peak memory"; ``drivers/mine_sweep.py`` sends it
MEMORY_SIGNAL = signal.SIGRTMIN
SIGNALS = (signal.SIGUSR1, signal.SIGUSR2, MEMORY_SIGNAL, signal.SIGTERM)


def _peak_bytes():
    """Peak bytes in use on the fullest local device; None where the
    backend keeps no such statistic (the CPU)."""
    import jax
    from jax._src import xla_bridge

    # asking for the devices would initialise a backend from this
    # thread, behind a main thread that may be stuck doing just that
    if not xla_bridge.backends_are_initialized():
        raise RuntimeError("the miner has initialised no jax backend")
    peaks = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _say(line: str) -> None:
    """One write, newline and all, past any buffer of Python's."""
    os.write(1, (line + "\n").encode())


def _say_memory() -> None:
    try:
        peak = _peak_bytes()
    except Exception as e:  # the backend may be gone: say why
        why = " ".join(f"{type(e).__name__}: {e}".split())
        _say(f"memory: unreadable ({why})")
    else:
        _say(f"memory: peak_bytes={'null' if peak is None else peak}")


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


def _signals(leave, start: threading.Event, stop: threading.Event) -> None:
    while True:
        sig = signal.sigwait(SIGNALS)
        if sig == signal.SIGUSR1:
            start.set()
        elif sig == signal.SIGUSR2:
            stop.set()
        elif sig == MEMORY_SIGNAL:
            _say_memory()
        else:
            leave(0)


def main() -> None:
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
    # before any other thread exists: they all inherit the mask
    signal.pthread_sigmask(signal.SIG_BLOCK, SIGNALS)
    start, stop = threading.Event(), threading.Event()
    tracer = None
    if trace_dir:
        tracer = threading.Thread(target=_tracer, daemon=True, name="tracer",
                                  args=(trace_dir, start, stop))
        tracer.start()
    leaving = threading.Lock()

    def leave(rc: int, read_memory: bool = True) -> None:
        """The one way out, for whichever thread gets here first: the
        signals' thread on SIGTERM, or this one when the miner returns.
        The other waits on the lock for the process to end."""
        leaving.acquire()
        if tracer is not None and start.is_set():
            stop.set()
            tracer.join(timeout=90)
        if read_memory:
            _say_memory()
        # the device runtime's threads are not all daemons, and the
        # miner's may be anywhere: do not wait on them
        os._exit(rc)

    threading.Thread(target=_signals, daemon=True, name="signals",
                     args=(leave, start, stop)).start()
    from upow_tpu.mine import miner

    if fault:
        import faults

        faults.apply(fault)
    try:
        rc = miner.main(argv)
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 1
    except BaseException:
        import traceback

        traceback.print_exc()
        rc = 1
    # no device: nothing to read
    leave(rc or 0, read_memory=rc != miner.RC_NO_DEVICE)


if __name__ == "__main__":
    main()
