"""TPU → CPU graceful degradation for the verify hot path.

The signature-verify dispatch (verify/txverify.py) already survives a
sick accelerator — errors fall back to the host batch, hangs are
time-boxed — but before this module the policy was a one-way door: a few
consecutive device errors *poisoned* the device path for the life of the
process, so one transient XLA blip (a runtime error, OOM during an unrelated
compile) cost the node its accelerator forever.

:class:`DegradeManager` replaces the globals with a three-state machine:

* **ok** — device dispatches flow.
* **degraded** — after ``failure_limit`` consecutive *raised* errors
  (compile failure, transport error) the device path is benched and the
  CPU reference verifier serves every block; after ``cooldown`` seconds
  ONE dispatch is let through as a re-probe — success restores **ok**,
  failure re-benches for another cooldown.
* **poisoned** — a *hang* (boxed-call timeout) is unrecoverable: the
  stuck daemon thread holds the PJRT client, so the device path stays
  off for the life of the process, exactly as before.

Every transition and every blocked dispatch is counted through
``trace.inc`` so the ``/metrics`` endpoint and the chaos suite can
observe degradation and recovery.

The manager is mutated from executor threads (the verify dispatch runs
off-loop) — all state moves under one lock.
"""

from __future__ import annotations

import threading
import time
from typing import Callable

from ..logger import get_logger

log = get_logger("degrade")

OK = "ok"
DEGRADED = "degraded"
POISONED = "poisoned"

_STATE_GAUGE = {OK: 0, DEGRADED: 1, POISONED: 2}


class DegradeManager:
    """Device-health state machine feeding the verify backend router."""

    def __init__(self, failure_limit: int = 3, cooldown: float = 60.0,
                 clock: Callable[[], float] = time.monotonic):
        self.failure_limit = failure_limit
        self.cooldown = cooldown
        self._clock = clock
        self._lock = threading.Lock()
        self._state = OK
        self._consecutive_failures = 0
        self._degraded_at = 0.0
        self._probe_in_flight = False

    def configure(self, failure_limit: int, cooldown: float) -> None:
        """Apply config knobs (Node startup); state is preserved."""
        with self._lock:
            self.failure_limit = failure_limit
            self.cooldown = cooldown

    # ------------------------------------------------------------ gates ---
    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def state_gauge(self) -> int:
        """0 = ok, 1 = degraded, 2 = poisoned (the /metrics encoding)."""
        return _STATE_GAUGE[self.state]

    def allow(self) -> bool:
        """May the next verify batch go to the device?

        In ``degraded`` this is False until ``cooldown`` has elapsed,
        then True (the re-probe) until that probe resolves via
        :meth:`record_success` / :meth:`record_failure` — the backend
        resolver consults this more than once per dispatch (cached and
        uncached layers), so an in-flight probe keeps answering True
        rather than bouncing its own dispatch back to the host.  Each
        refusal is counted as a CPU fallback.
        """
        from .. import trace

        with self._lock:
            if self._state == OK:
                return True
            if self._state == POISONED:
                trace.inc("resilience.device_fallback")
                return False
            if self._probe_in_flight:
                return True
            if self._clock() - self._degraded_at < self.cooldown:
                trace.inc("resilience.device_fallback")
                return False
            self._probe_in_flight = True
            trace.inc("resilience.device_reprobe")
            log.info("device cooldown elapsed; re-probing the device "
                     "verify path")
            return True

    # --------------------------------------------------------- outcomes ---
    def record_success(self) -> None:
        from .. import trace

        with self._lock:
            self._consecutive_failures = 0
            self._probe_in_flight = False
            if self._state == DEGRADED:
                self._state = OK
                trace.inc("resilience.device_recovered")
                trace.event("degrade", state=OK, previous=DEGRADED)
                log.warning("device verify path recovered; leaving "
                            "CPU-degraded mode")

    def record_failure(self, error: BaseException = None) -> None:
        from .. import trace

        with self._lock:
            trace.inc("resilience.device_error")
            self._consecutive_failures += 1
            self._probe_in_flight = False
            if self._state == DEGRADED:
                self._degraded_at = self._clock()  # failed probe: re-bench
                return
            if self._state == OK and \
                    self._consecutive_failures >= self.failure_limit:
                self._state = DEGRADED
                self._degraded_at = self._clock()
                trace.inc("resilience.device_degraded")
                trace.event("degrade", state=DEGRADED, previous=OK,
                            failures=self._consecutive_failures,
                            error=str(error) if error else None)
                log.warning(
                    "device verify path degraded after %d consecutive "
                    "errors (%s); falling back to the CPU reference "
                    "verifier, re-probe in %.0fs",
                    self._consecutive_failures, error, self.cooldown)

    def poison(self, reason: str = "") -> None:
        """A hang: the stuck thread cannot be reclaimed — device off for
        the life of the process."""
        from .. import trace

        with self._lock:
            if self._state != POISONED:
                prev = self._state
                self._state = POISONED
                trace.inc("resilience.device_poisoned")
                trace.event("degrade", state=POISONED, previous=prev,
                            reason=reason or None)
                log.warning("device verify path poisoned%s; CPU path for "
                            "the rest of this process",
                            f" ({reason})" if reason else "")

    def snapshot(self) -> dict:
        with self._lock:
            return {"state": self._state,
                    "consecutive_failures": self._consecutive_failures,
                    "cooldown": self.cooldown,
                    "failure_limit": self.failure_limit}
