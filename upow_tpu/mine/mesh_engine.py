"""Persistent mesh-sharded nonce search: one resident SPMD program.

The static-target dispatcher in :mod:`.engine` (``--device pallas``)
recompiles nothing per round but traces and compiles a program for
every tip, and holds no mesh: on a v5e-8 seven chips idle while one
scans.  This module owns the path the miner runs on a chip: ``--device
tpu`` on a mesh of one device, ``--device mesh`` over
``device.mesh_devices`` of them:

* **One compiled program** — ``parallel.mesh._pow_search_mesh_resident``
  is jitted once per (batch_per_device, nonce_spec, mesh) at arm time.
  Every
  job-specific field — midstate, tail words, per-shard ranges, packed
  target — rides as runtime data, so a new job or chain-tip change is a
  pure dispatch: zero recompilation, asserted by the ``mine_mesh``
  compile-cache counters.  Its per-shard body is the Pallas sha256
  kernel on a TPU mesh and the jnp one elsewhere
  (``parallel.mesh.resident_body``); ``stats()["body"]`` and the
  ``mine.mesh.rounds_pallas`` counter say which one the rounds ran.
* **A job's arrays laid over the mesh once a job** — :meth:`set_job`
  places midstate, tail and target as committed arrays replicated on
  every device of the engine's mesh, the sharding the program was
  compiled for (the arm's warm dispatch lays its zeros the same way), so
  a round hands pjit nothing to re-lay; only ``ranges`` crosses a round.
  ``mine.mesh.job_layouts`` counts the placements, and
  ``stats()["jit_entries"]`` stays 1.  Two jobs stay laid
  (``JOBS_LAID``): the miner lays the next job and issues its first
  rounds while the last rounds of the job in hand are in flight (never
  more than the round loop's one depth over both jobs), so a
  job's rounds (:meth:`dispatcher`) are bound to the arrays of their own
  job, whichever was set last.
* **Disjoint shard ranges** — each round's [start, start+count) window
  is split across the mesh with :func:`parallel.mesh.shard_bounds`; the
  per-round plan is retained in the dispatch accounting so tests (and
  operators) can prove disjoint, exact coverage.  A ``pmin`` collective
  reduces per-shard hits to the global winner on device.
* **Single dispatch owner** — every dispatch goes through
  ``device/runtime.py`` ``submit_call`` under the weighted-fair source
  "mine", so mining rounds co-reside with block verify and mempool
  coalescing instead of racing them for the chip.
* **One arm, in the runtime** — :meth:`MeshEngine.arm` arms through
  ``device/runtime.py`` and nothing else: no second attempt under a
  changed environment, no probing child (a child would want the chip
  this process holds).  The attempt's actual exception text and
  traceback fingerprint land in ``stats()["arm_ladder"]``.

Multi-host runs split the nonce space first via
``parallel.multihost.plan_nonce_ranges`` (each process mines its own
planned range through this engine), then shard within the process's
mesh — DCN never sees the hot loop.
"""

from __future__ import annotations

import functools
import logging
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from .. import telemetry
from ..crypto import sha256 as sha_kernel
from ..telemetry import device as _ktel

log = logging.getLogger("upow.mine.mesh")

#: rounds of per-shard range accounting retained (oldest dropped);
#: totals keep counting past the window
ACCOUNTING_WINDOW = 4096
#: jobs whose arrays stay on the mesh: the one in hand and the one the
#: miner issues behind its last rounds (engine.py ``mine``)
JOBS_LAID = 2


class LaidJob(NamedTuple):
    """One job as the resident program takes it."""

    arrays: tuple       # midstate, tail, target: a page each, replicated
    nonce_spec: tuple
    t0: float           # perf_counter when it was laid: note_hit's base
    serial: int         # 1 for the engine's first layout; the ``job`` of
                        # the round records


def _job_key(job) -> tuple:
    return (job.prefix, job.previous_hash, str(job.difficulty))


def _arm_attempt(name: str, ok: bool, seconds: float,
                 error: Optional[BaseException] = None,
                 detail: Optional[str] = None) -> dict:
    """The arm attempt's record, with the real failure text captured."""
    from ..device.runtime import traceback_fingerprint

    rec = {"attempt": name, "ok": bool(ok), "seconds": round(seconds, 3)}
    if error is not None:
        rec["error"] = repr(error)
        rec["traceback_fingerprint"] = traceback_fingerprint(error)
    elif detail is not None and not ok:
        rec["error"] = detail
    elif detail is not None:
        rec["detail"] = detail
    return rec


class MeshEngine:
    """A resident, mesh-sharded nonce-search service.

    Lifecycle: construct (cheap) → :meth:`arm` (compiles the resident
    program once) → :meth:`set_job` / :meth:`dispatch` per round (pure
    dispatches).  :func:`get_mesh_engine` keeps one engine per process so
    the compiled program survives across jobs and block templates.
    """

    def __init__(self, mesh_devices: int = 0,
                 batch_per_device: Optional[int] = None,
                 round_hint: Optional[int] = None,
                 interpret: bool = False):
        self._mesh_devices = int(mesh_devices)
        self._batch_per_device = batch_per_device
        self._round_hint = round_hint
        # tests only: the Pallas body in interpret mode on a CPU mesh
        self._interpret = bool(interpret)
        self._body: Optional[str] = None   # "pallas" | "jnp", set at arm
        self._mesh = None
        self._n_dev = 0
        self._armed = False
        self.arm_ladder: List[dict] = []
        self.arm_failure_reason: Optional[str] = None
        self._laid: Dict[tuple, LaidJob] = {}   # the newest JOBS_LAID
        self._job: Optional[LaidJob] = None     # the last one set
        self._rounds: List[dict] = []
        self._dispatches = 0
        self._nonces_planned = 0
        self._job_layouts = 0

    # ------------------------------------------------------------- arm ---

    @property
    def armed(self) -> bool:
        return self._armed

    @property
    def n_devices(self) -> int:
        return self._n_dev

    @property
    def batch_per_device(self) -> int:
        return int(self._batch_per_device or 0)

    def mesh_devices(self) -> list:
        """The devices of the dp mesh, in shard order ([] before arm)."""
        return [] if self._mesh is None else list(self._mesh.devices.flat)

    @property
    def capacity(self) -> int:
        """Max nonces a single dispatch can cover (n_dev * batch)."""
        return self._n_dev * self.batch_per_device

    def arm(self, timeout: Optional[float] = None) -> dict:
        """Arm the runtime (once per process — idempotent there) and
        compile the resident program.  The attempt's actual exception
        text is kept on the engine (and returned) so callers can log it
        verbatim instead of a generic "hung/failed"."""
        if self._armed:
            return {"armed": True, "ladder": self.arm_ladder,
                    "devices": self._n_dev}
        from ..device.runtime import get_runtime

        t0 = time.perf_counter()
        try:
            runtime = get_runtime()
            runtime.arm(deadline=timeout)
            if runtime.platform() is None:
                info = runtime.stats().get("arm", {})
                rung = _arm_attempt(
                    "runtime", False, time.perf_counter() - t0,
                    detail=info.get("arm_failure_reason")
                    or "backend probe returned no platform")
            else:
                self._build_mesh_and_warm()
                rung = _arm_attempt(
                    "runtime", True, time.perf_counter() - t0,
                    detail=f"{runtime.platform()} x{self._n_dev}")
                self._armed = True
        except Exception as e:
            log.debug("mesh arm failed", exc_info=True)
            rung = _arm_attempt("runtime", False,
                                time.perf_counter() - t0, error=e)
        self.arm_ladder = [rung]
        self.arm_failure_reason = None if self._armed else (
            f"runtime: {rung.get('error') or rung.get('detail', '?')}")
        return {"armed": self._armed, "ladder": self.arm_ladder,
                "devices": self._n_dev,
                "arm_failure_reason": self.arm_failure_reason}

    def _build_mesh_and_warm(self) -> None:
        """Build the dp mesh from the armed runtime's device view and
        compile the resident program with an all-invalid dummy dispatch
        (submitted through the runtime like every later round)."""
        from ..config import DeviceConfig, _apply_env_fields
        from ..device.runtime import get_runtime
        from ..parallel.mesh import (make_mesh, pow_search_resident,
                                     resident_body)

        runtime = get_runtime()
        devices = runtime.devices()
        if not devices:
            raise RuntimeError("runtime armed but exposes no devices")
        if self._mesh_devices:
            devices = devices[: self._mesh_devices]
        self._n_dev = len(devices)
        self._mesh = make_mesh(devices)
        self._body = resident_body(self._mesh, self._interpret)
        # exported at zero from the arm on: a CPU mesh reads 0, not "no
        # such counter"
        telemetry.ensure_counter("mine.mesh.rounds_pallas")
        telemetry.ensure_counter("mine.mesh.job_layouts")
        telemetry.ensure_counter("kernel.mine_mesh.exact_steps")
        # the static-target engine's programs, one a tip: 0 for as long
        # as the process runs this engine alone
        telemetry.ensure_counter("kernel.sha256_search.compile_cache_misses")
        if self._batch_per_device is None:
            if self._round_hint:
                # ceil: one round of round_hint nonces must fit capacity
                self._batch_per_device = max(
                    1, (int(self._round_hint) + self._n_dev - 1)
                    // self._n_dev)
            else:
                cfg = DeviceConfig()
                _apply_env_fields(cfg, "device")
                self._batch_per_device = max(
                    1, cfg.search_batch // self._n_dev)
        # dummy template: zero midstate/tail/target, every shard empty
        # (base == limit == 0) — compiles the exact program real jobs
        # dispatch (the kernel runs no tile of it).  The zeros are
        # laid as a job's arrays are: jit keys a program on the shardings
        # of committed arguments, and the first job must find this one
        spec = sha_kernel.make_template(bytes(104)).nonce_spec
        zeros, = self._lay_over_mesh([0])
        no_ranges = sha_kernel.resident_operand(
            np.zeros((self._n_dev, 2), np.uint32))

        def warm():
            return np.asarray(pow_search_resident(
                zeros, zeros, no_ranges, zeros, self._batch_per_device,
                spec, self._mesh, self._interpret))

        runtime.submit_call(
            warm, kernel="sha256_search_mesh", source="mine").result()

    # ------------------------------------------------------------- job ---

    def _lay_over_mesh(self, *operands) -> tuple:
        """Each of ``operands`` padded to a page
        (``sha256.resident_operand``) as a committed array replicated on
        every device of the engine's mesh: the sharding the resident
        program takes its three job operands in, so a dispatch hands
        pjit nothing to lay again.  One placement for all of them: a
        placement holds back the rounds already queued for about as long
        as it takes, and three apart cost a bare loop of rounds on four
        chips 4.2 ms where one costs 2.6 (PERF.md section 6, PR 44)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        # data placement onto the armed runtime's own devices, not a
        # dispatch and no enumeration
        return tuple(jax.device_put(  # upowlint: disable=DR001
            [sha_kernel.resident_operand(words) for words in operands],
            NamedSharding(self._mesh, P())))

    def set_job(self, job) -> LaidJob:
        """Load a :class:`..mine.engine.MiningJob`: host-side midstate +
        packed target are laid over the mesh, once a job; the resident
        program is NOT recompiled (all job fields are traced arguments
        of the sharding the arm's warm dispatch compiled for).  Rounds
        of an earlier job, in flight or still to be issued through its
        own :meth:`dispatcher`, keep the arrays they were laid with."""
        if not self._armed:
            raise RuntimeError("MeshEngine.set_job before arm()")
        key = _job_key(job)
        laid = self._laid.get(key)
        if laid is None:
            template = sha_kernel.make_template(job.prefix)
            spec = sha_kernel.target_spec(job.previous_hash, job.difficulty)
            arrays = self._lay_over_mesh(
                template.midstate, template.tail_words,
                sha_kernel.pack_target(spec))
            self._job_layouts += 1
            telemetry.inc("mine.mesh.job_layouts")
            _ktel.record_hoist(
                "mine_mesh", *sha_kernel.hoisted_counts(template.nonce_spec))
            laid = LaidJob(arrays, template.nonce_spec,
                           time.perf_counter(), self._job_layouts)
            while len(self._laid) >= JOBS_LAID:
                del self._laid[next(iter(self._laid))]   # the oldest
            self._laid[key] = laid
        self._job = laid
        return laid

    # -------------------------------------------------------- dispatch ---

    def plan_round(self, start: int, count: int) -> List[Tuple[int, int]]:
        """Disjoint per-shard [lo, hi) plan for one round via
        :func:`parallel.mesh.shard_bounds` — also what the accounting
        records, so the test oracle and the dispatch share one source."""
        from ..parallel.mesh import shard_bounds

        return [shard_bounds(start, start + count, i, self._n_dev)
                for i in range(self._n_dev)]

    def dispatch(self, start: int, count: int):
        """Scan [start, start+count) of the job set last across the
        mesh; returns the async device handle (a
        ``sha256.SearchAnswer``: ``int()`` blocks and yields min hit or
        SENTINEL; its words are copied to the host as soon as the round
        has run, asked for here).

        ``count`` must fit one round (<= :attr:`capacity`); the caller's
        loop (engine.mine) sizes rounds accordingly."""
        return self._issue(self._job, start, count)

    def _issue(self, laid: Optional[LaidJob], start: int, count: int):
        # the engine's own Python a round, then the hand-over: the two
        # parts of the caller's mine.round.issue (or mine.first_issue)
        with telemetry.span("mine.round.plan", light=True):
            if not self._armed:
                raise RuntimeError("MeshEngine.dispatch before arm()")
            if laid is None:
                raise RuntimeError("MeshEngine.dispatch before set_job()")
            if count <= 0 or count > self.capacity:
                raise ValueError(
                    f"round of {count} nonces does not fit capacity "
                    f"{self.capacity} ({self._n_dev} shards x "
                    f"{self.batch_per_device})")
            from ..device.runtime import get_runtime
            from ..parallel.mesh import pow_search_resident

            shards = self.plan_round(start, count)
            ranges = sha_kernel.resident_operand(shards)
            self._dispatches += 1
            self._nonces_planned += count
            self._rounds.append(
                {"round": self._dispatches, "job": laid.serial,
                 "lo": start, "hi": start + count, "shards": shards})
            if len(self._rounds) > ACCOUNTING_WINDOW:
                del self._rounds[0]
            mid, tail, target = laid.arrays
            nonce_spec, batch, mesh = (
                laid.nonce_spec, self._batch_per_device, self._mesh)
            interpret = self._interpret
            _ktel.record_mine_round(
                [hi - lo for lo, hi in shards], batch,
                compile_key=(batch, self._n_dev, nonce_spec))
            if self._body == "pallas":
                # the share of rounds that ran the kernel: 1.0 on a chip
                telemetry.inc("mine.mesh.rounds_pallas")
            runtime = get_runtime()
        # enqueue, the drainer's wake-up, runtime.call on its thread (the
        # jitted call with its ranges placement), the future back: less
        # runtime.call, the hop between the two threads
        with telemetry.span("mine.round.submit", light=True):
            words = runtime.submit_call(
                lambda: pow_search_resident(
                    mid, tail, ranges, target,
                    batch, nonce_spec, mesh, interpret),
                kernel="sha256_search_mesh", source="mine").result()
        # the answer's way to the host (0.45 ms on the pod) starts when
        # the round ends, not when the loop asks for it: with it inside
        # the loop's cycle the host is the pod's pace, and a deeper queue
        # holds answers nobody has read (PERF.md section 6, PR 47)
        words.copy_to_host_async()
        return sha_kernel.SearchAnswer(words, "mine_mesh")

    def dispatcher(self, job) -> Callable:
        """dispatch(start, count) of ``job``'s own rounds for
        :func:`engine.mine`'s pipelined round loop — arms lazily, lays
        the job, and routes every round through the runtime.  It stays
        ``job``'s after a later ``set_job``."""
        if not self._armed:
            info = self.arm()
            if not info["armed"]:
                raise RuntimeError(
                    "mesh engine failed to arm: "
                    + (self.arm_failure_reason or "unknown"))
        return functools.partial(self._issue, self.set_job(job))

    def note_hit(self, job=None) -> None:
        """Record time-to-hit (mine.hit_latency) for ``job`` while it is
        laid, for the job set last without one."""
        laid = self._job if job is None else self._laid.get(_job_key(job))
        if laid is not None:
            _ktel.record_mine_hit(time.perf_counter() - laid.t0)

    # ----------------------------------------------------------- stats ---

    def stats(self) -> dict:
        jit_entries = 0
        if self._mesh is not None:
            from ..parallel.mesh import _pow_search_mesh_resident

            # the process's compiled variants of the resident program:
            # 1 for one engine, whatever jobs and targets it was given
            jit_entries = _pow_search_mesh_resident._cache_size()
        return {
            "armed": self._armed,
            "devices": self._n_dev,
            "body": self._body,
            "batch_per_device": self.batch_per_device,
            "capacity": self.capacity,
            "dispatches": self._dispatches,
            "nonces_planned": self._nonces_planned,
            "job_layouts": self._job_layouts,
            "jit_entries": jit_entries,
            "rounds": list(self._rounds),
            "arm_ladder": list(self.arm_ladder),
            "arm_failure_reason": self.arm_failure_reason,
        }


# one resident engine per process: the whole point is that the compiled
# program outlives jobs, so callers share it rather than re-instantiating
_ENGINE: Optional[MeshEngine] = None


def get_mesh_engine(mesh_devices: int = 0,
                    batch_per_device: Optional[int] = None,
                    round_hint: Optional[int] = None) -> MeshEngine:
    """Process-wide resident engine.

    A mesh-size or per-shard-batch change replaces the engine (those are
    compile keys); everything else — jobs, targets, chain tips — reuses
    the resident program.  ``round_hint`` is the total nonces per round
    the caller intends to dispatch: before arm it sizes the per-shard
    batch; after arm an engine whose capacity no longer fits is replaced
    (one recompile), a smaller hint reuses the resident program.
    """
    global _ENGINE
    eng = _ENGINE
    if eng is not None and eng._mesh_devices == int(mesh_devices):
        if not eng._armed:
            if batch_per_device is not None:
                eng._batch_per_device = int(batch_per_device)
            if round_hint is not None and eng._batch_per_device is None:
                eng._round_hint = max(int(round_hint), eng._round_hint or 0)
            return eng
        fits_batch = (batch_per_device is None
                      or int(batch_per_device) == eng._batch_per_device)
        fits_round = round_hint is None or int(round_hint) <= eng.capacity
        if fits_batch and fits_round:
            return eng
    _ENGINE = MeshEngine(mesh_devices=mesh_devices,
                         batch_per_device=batch_per_device,
                         round_hint=round_hint)
    return _ENGINE


def reset_mesh_engine() -> None:
    """Drop the resident engine (tests)."""
    global _ENGINE
    _ENGINE = None


def engine_stats() -> Optional[dict]:
    """Stats of the resident engine, or None before first use — the
    node's /metrics gauges read this without forcing an arm."""
    return _ENGINE.stats() if _ENGINE is not None else None


def planned_range(lo: int = 0, hi: Optional[int] = None) -> Tuple[int, int]:
    """This process's nonce range under the deterministic multi-host
    plan (``multihost.plan_nonce_ranges``) — the mesh shards within it."""
    from ..mine.engine import NONCE_SPACE
    from ..parallel import multihost

    return multihost.my_nonce_range(lo, NONCE_SPACE if hi is None else hi)
