"""Children, as ``chip_smoke.py`` proved them on the chip (copied from
it, PR 22, with the miner child turned from ``--once`` into one that
runs until told to stop)."""

from __future__ import annotations

import os
import signal
import subprocess
import threading
import time

from .manifest import ROOT, BenchError

_CHILDREN: list = []


def register(proc: subprocess.Popen) -> None:
    """A child that ``stop_all`` must not leave behind."""
    _CHILDREN.append(proc)


def child_env(extra=None) -> dict:
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)  # the driver's own; no child reads it
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"  # lines are time-stamped as they arrive
    env.update(extra or {})
    return env


class LineChild:
    """One child process whose output lines (stdout and stderr, merged)
    are stamped with ``time.time()`` as they arrive."""

    def __init__(self, argv: list, cwd: str, env=None, log_path=None):
        self.argv = argv
        self.lines: list = []       # (unix seconds, text)
        self.stop_s = None          # SIGTERM to exit, of the first stop
        self.killed = False         # it outlived that stop's wait
        self._cond = threading.Condition()
        self._log = open(log_path, "w") if log_path else None
        self.proc = subprocess.Popen(
            argv, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, bufsize=1, cwd=cwd, env=child_env(env),
            start_new_session=True)
        register(self.proc)
        self._reader = threading.Thread(target=self._read, daemon=True,
                                        name="child-lines")
        self._reader.start()

    def _read(self) -> None:
        for raw in self.proc.stdout:
            now = time.time()
            text = raw.rstrip("\n")
            with self._cond:
                self.lines.append((now, text))
                self._cond.notify_all()
            if self._log:
                self._log.write(f"{now:.4f} {text}\n")
        with self._cond:
            self._cond.notify_all()

    def wait_for(self, predicate, timeout: float, what: str,
                 seen: int = 0):
        """The first line (t, text), of those from the ``seen``-th on,
        for which ``predicate(text)`` holds; BenchError if the child
        exits or ``timeout`` passes first."""
        deadline = time.time() + timeout
        with self._cond:
            while True:
                while seen < len(self.lines):
                    t, text = self.lines[seen]
                    seen += 1
                    if predicate(text):
                        return t, text
                if self.proc.poll() is not None and \
                        not self._reader.is_alive():
                    raise BenchError(
                        f"child exited rc={self.proc.returncode} before "
                        f"{what}: {self.tail()}")
                left = deadline - time.time()
                if left <= 0:
                    raise BenchError(f"no {what} within {timeout:.0f}s: "
                                     f"{self.tail()}")
                self._cond.wait(min(left, 0.5))

    def signal(self, sig: int) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(sig)

    def stop(self, timeout: float = 120.0) -> int:
        """SIGTERM, wait, then kill the whole session; returns the exit
        code.  The reader thread has drained the pipe when this returns.
        ``stop_s`` and ``killed`` keep how the first call went."""
        t0 = time.time()
        self.signal(signal.SIGTERM)
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.killed = True
        if self.stop_s is None:
            self.stop_s = time.time() - t0
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()
        self._reader.join(timeout=10)
        if self._log:
            self._log.close()
            self._log = None
        return self.proc.returncode

    def tail(self, n: int = 12) -> str:
        with self._cond:
            return " | ".join(text for _t, text in self.lines[-n:])[-1500:]


def stop_all() -> None:
    for p in _CHILDREN:
        if p.poll() is None:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                p.kill()
    for p in _CHILDREN:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
