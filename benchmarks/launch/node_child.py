#!/usr/bin/env python3
"""The node, in this process, as an operator starts it, under the
launcher's protocol of ``miner_child.py``: the device's peak memory said
when the parent asks and again at exit, and (``--trace-dir``) a profiler
trace switched on and off by the parent.

    node_child.py [--trace-dir DIR] [--fault NAME] -- --config <file>

It calls ``upow_tpu.node.run.main()`` with the arguments after ``--``:
exactly ``python -m upow_tpu.node.run --config <file>``.  The four
signals, their lines and the reasons are ``miner_child.py``'s, whose
pieces this imports: all four are blocked before any thread exists and
taken by one ``sigwait`` thread, and the exit is ``os._exit``: the node
gets no clean close, so what it acknowledged has to be in its database
already.  ``web.run_app`` is told to leave the signals alone: aiohttp
would set handlers for SIGINT and SIGTERM, and a handler is no use to a
signal that a thread waits for.

``--fault`` (a control run only) breaks the node as ``node_faults.py``
says, so that the run is seen to fail.
"""

from __future__ import annotations

import functools
import importlib
import os
import signal
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import miner_child as launcher  # noqa: E402


def main() -> None:
    argv = sys.argv[1:]
    trace_dir = fault = None
    while argv and argv[0] != "--":
        if argv[0] == "--trace-dir":
            trace_dir = argv[1]
        elif argv[0] == "--fault":
            fault = argv[1]
        else:
            raise SystemExit(f"node_child.py: unknown option {argv[0]}")
        argv = argv[2:]
    # before any other thread exists: they all inherit the mask
    signal.pthread_sigmask(signal.SIG_BLOCK, launcher.SIGNALS)
    start, stop = threading.Event(), threading.Event()
    tracer = None
    if trace_dir:
        tracer = threading.Thread(
            target=launcher._tracer, daemon=True, name="tracer",
            args=(trace_dir, start, stop))
        tracer.start()
    leaving = threading.Lock()

    def leave(rc: int) -> None:
        """The one way out: the signals' thread on SIGTERM, or this one
        when the node returns or fails to start."""
        leaving.acquire()
        if tracer is not None and start.is_set():
            stop.set()
            tracer.join(timeout=90)
        launcher._say_memory()
        os._exit(rc)

    threading.Thread(target=launcher._signals, daemon=True, name="signals",
                     args=(leave, start, stop)).start()
    from aiohttp import web

    web.run_app = functools.partial(web.run_app, handle_signals=False)
    # the package's own ``run`` is a function: take the module
    node_run = importlib.import_module("upow_tpu.node.run")

    if fault:
        import node_faults

        node_faults.apply(fault)
    sys.argv = ["upow_tpu.node.run"] + argv[1:]
    try:
        node_run.main()
        rc = 0
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 1
    except BaseException:
        import traceback

        traceback.print_exc()
        rc = 1
    leave(rc)


if __name__ == "__main__":
    main()
