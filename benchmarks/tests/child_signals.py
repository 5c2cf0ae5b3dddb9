"""What a stand-in for the miner child needs to speak the launcher's
protocol (``launch/miner_child.py``): the memory request and SIGTERM,
blocked in every thread and taken by ``sigwait`` on a thread of its own.
``memory`` says how a request, and the exit, are answered:

    <digits>    ``memory: peak_bytes=<digits>``, as a sound launcher
    null        ``memory: peak_bytes=null``
    unreadable  ``memory: unreadable (RuntimeError: backend gone)``
    inline      the sound line, written behind an unfinished line
    never       no line at all

``ignore_term`` leaves SIGTERM unanswered: the parent has to kill.
"""

import os
import signal
import threading

MEMORY_SIGNAL = signal.SIGRTMIN
_LINES = {"null": "memory: peak_bytes=null\n",
          "unreadable": "memory: unreadable (RuntimeError: backend gone)\n",
          "inline": "a line of the miner's threadmemory: peak_bytes=4096\n",
          "never": ""}


def answer_signals(memory: str = "4096", ignore_term: bool = False) -> None:
    line = _LINES.get(memory, f"memory: peak_bytes={memory}\n").encode()
    signals = (MEMORY_SIGNAL, signal.SIGTERM)
    signal.pthread_sigmask(signal.SIG_BLOCK, signals)

    def serve():
        while True:
            sig = signal.sigwait(signals)
            if sig == signal.SIGTERM and ignore_term:
                continue
            os.write(1, line)
            if sig == signal.SIGTERM:
                os._exit(0)

    threading.Thread(target=serve, daemon=True, name="signals").start()
