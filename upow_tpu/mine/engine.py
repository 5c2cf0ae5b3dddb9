"""Mining engine: backend-abstracted nonce search over a block template.

The reference mines with N Python processes striding the nonce space and
hashing one candidate at a time (miner.py:83-98, ~0.1-1 Mh/s per core).
Here a template compiles once into a device program that tests a whole
batch per dispatch — fixed-size rounds (XLA wants static shapes; the 90 s
template TTL maps to a wall-clock budget checked between rounds), with the
host polling the round result for an early exit.

Backends:
    mesh    — the resident program (mesh_engine.py): target, template and
              range are data, compiled once a process; the path on a TPU,
              one chip (``--device tpu``) or several (``--device mesh``)
    pallas  — Pallas TPU kernel with the target static: a program a tip
              (``--device pallas``; goes with ROADMAP D2)
    jnp     — pure jax.numpy/XLA, target static (any device)
    native  — C++ midstate loop via ctypes (fast host fallback)
    python  — hashlib loop (reference-shaped, last resort / oracle)
"""

from __future__ import annotations

import contextlib
import hashlib
import time
from collections import deque
from dataclasses import dataclass
from decimal import Decimal
from typing import Callable, Optional

from .. import telemetry
from ..core.difficulty import check_pow_hash, pow_target
from ..core.header import BlockHeader
from ..crypto import sha256 as sha_kernel

NONCE_SPACE = 1 << 32
# Device searchers reserve 0xFFFFFFFF as the no-hit sentinel of their
# min-reduction (crypto/sha256.py SENTINEL), so that one nonce is never
# searched: a hit there would be reported as a miss.  Excluding a single
# candidate out of 2^32 costs ~nothing and keeps every backend's contract
# identical.
MAX_SEARCH_END = NONCE_SPACE - 1


@dataclass
class MiningJob:
    """One immutable search job: a fully-built header prefix + PoW target."""

    prefix: bytes           # header minus the 4-byte nonce
    previous_hash: str
    difficulty: Decimal

    @classmethod
    def from_header_fields(cls, previous_hash: str, address: str,
                           merkle_root: str, timestamp: int,
                           difficulty) -> "MiningJob":
        difficulty = Decimal(str(difficulty))
        header = BlockHeader(
            previous_hash=previous_hash,
            address=address,
            merkle_root=merkle_root,
            timestamp=timestamp,
            difficulty_x10=int(difficulty * 10),
            nonce=0,
        )
        return cls(header.prefix_bytes(), previous_hash, difficulty)

    def block_content(self, nonce: int) -> str:
        return (self.prefix + nonce.to_bytes(4, "little")).hex()

    def check(self, nonce: int) -> bool:
        digest = hashlib.sha256(self.prefix + nonce.to_bytes(4, "little")).hexdigest()
        return check_pow_hash(digest, self.previous_hash, self.difficulty)


@contextlib.contextmanager
def _masked_round(width: int, count: int):
    """The issue of a round of ``count`` nonces on a program of ``width``
    lanes: counted, and under the light span ``mine.round.tail``."""
    telemetry.inc("mine.rounds_masked")
    telemetry.inc("mine.lanes_masked", width - count)
    with telemetry.span("mine.round.tail", light=True):
        yield


def _make_dispatcher(job: MiningJob, backend: str,
                     mesh_devices: int = 0,
                     batch: Optional[int] = None) -> Optional[Callable]:
    """For device backends: dispatch(start, count) -> async device handle.

    The handle resolves via ``int()``; keeping several dispatches in
    flight hides the host↔device round-trip (which otherwise caps the
    hash rate).  ``count`` is the round's live lanes: every round of a
    job runs the one program, of ``batch`` lanes on the pallas and jnp
    backends and of the engine's capacity on the mesh, and one with fewer
    (a masked round: the last of a range that is no multiple of
    ``batch``) passes its end as data (``start + count`` as the limit;
    on the mesh the ``ranges`` row of each shard), is counted in
    ``mine.rounds_masked`` / ``mine.lanes_masked`` and issues under the
    light span ``mine.round.tail``.

    ``backend='mesh'`` routes rounds through the resident mesh engine
    (mesh_engine.py): one compiled SPMD program per process whose
    template/target ride as runtime data, each round split across the
    "dp" mesh by shard_bounds with a pmin hit reduction (config
    device.mesh_devices caps the mesh size, 0 = all visible devices).
    ``batch`` is the round size mine() will dispatch — the engine sizes
    its per-shard capacity from it once, at first use."""
    if backend not in ("pallas", "jnp", "mesh"):
        return None
    from ..device.runtime import get_runtime

    runtime = get_runtime()

    if backend == "mesh":
        from .mesh_engine import get_mesh_engine

        # the engine submits every round through the runtime itself
        # (kernel "sha256_search_mesh", source "mine") and keeps the
        # per-round shard accounting
        engine = get_mesh_engine(mesh_devices=mesh_devices, round_hint=batch)
        issue_round = engine.dispatcher(job)
        capacity = engine.capacity

        def dispatch_mesh(start: int, count: int):
            if count >= capacity:
                return issue_round(start, count)
            with _masked_round(capacity, count):
                return issue_round(start, count)

        return dispatch_mesh
    template = sha_kernel.make_template(job.prefix)
    spec = sha_kernel.target_spec(job.previous_hash, job.difficulty)
    fn = sha_kernel.pow_search_pallas if backend == "pallas" else sha_kernel.pow_search_jnp
    # once a job, not a round: the target is a static argument of the
    # program, so a new tip is a new compile key and counts as a miss
    # (the resident mesh program's twin is kernel.mine_mesh.*)
    lanes = batch or 1
    telemetry.device.record_batch(
        "sha256_search", real=lanes, padded=lanes,
        compile_key=(batch, template.nonce_spec, spec))
    telemetry.device.record_hoist(
        "sha256_search", *sha_kernel.hoisted_counts(template.nonce_spec))
    telemetry.ensure_counter("kernel.sha256_search.exact_steps")

    def issue(start: int, count: int, width: int):
        # dispatch ISSUANCE goes through the device owner (so miner
        # rounds interleave fairly with verify/index batches); XLA's
        # async dispatch returns the device handle immediately, and the
        # caller still blocks on int(handle) — the pipelining depth in
        # mine() keeps its overlap
        return runtime.submit_call(
            lambda: fn(template, spec, nonce_base=start, batch=width,
                       limit=start + count),
            kernel="sha256_search", source="mine").result()

    def dispatch(start: int, count: int):
        # always the one program of `batch` lanes (a caller that names no
        # batch gets a program a count, as before): the range's end is
        # data, so a job's short last round is neither a second trace and
        # compile a tip nor a shape the tiled kernel refuses
        width = batch or count
        if count >= width:
            return issue(start, count, width)
        # a masked round: the kernel runs the tiles [start, start + count)
        # touches and lanes at or past its end never answer
        # (crypto/sha256.py _search_step, _lanes_in_range)
        telemetry.device.record_batch("sha256_search", real=count,
                                      padded=width)
        with _masked_round(width, count):
            return issue(start, count, width)

    return dispatch


def _make_searcher(job: MiningJob, backend: str) -> Callable[[int, int], Optional[int]]:
    """Return search(start, count) -> first hit nonce or None."""
    dispatch = _make_dispatcher(job, backend)
    if dispatch is not None:

        def search(start: int, count: int) -> Optional[int]:
            hit = int(dispatch(start, count))
            return None if hit == int(sha_kernel.SENTINEL) else hit

        return search

    if backend == "native":
        from .. import native

        if native.load() is None:
            raise RuntimeError("native backend requested but no C++ toolchain")
        prefix_hex, _, charset = pow_target(job.previous_hash, job.difficulty)

        def search(start: int, count: int) -> Optional[int]:
            return native.pow_search(job.prefix, prefix_hex, charset, start, count)

        return search

    if backend == "python":

        def search(start: int, count: int) -> Optional[int]:
            for n in range(start, start + count):
                if job.check(n):
                    return n
            return None

        return search

    raise ValueError(f"unknown backend {backend!r}")


@dataclass
class MineResult:
    nonce: Optional[int]          # None -> TTL expired
    hashes_tried: int
    elapsed: float
    first_dispatch: float = 0.0   # seconds the first device dispatch
                                  # took to ISSUE (trace + compile; the
                                  # execution itself is asynchronous)

    @property
    def hashrate(self) -> float:
        return self.hashes_tried / self.elapsed if self.elapsed > 0 else 0.0


#: seconds of queued search the device needs behind the round it runs to
#: outlast the host's routine absences: a job's lay over the pod (2.4
#: ms), the round in which the feed's thread holds the interpreter
#: (2.7), a stall of 2.8 (PERF.md section 6, PR 44)
QUEUE_LEAD_S = 0.005
#: the depth's range.  Two: the device never idles while the host blocks
#: on a result, and where a round outlasts the lead more buys no hash
#: and stands in front of every other dispatch the device owner is
#: handed (one chip: 9-10 ms behind two rounds, 23-24 behind four;
#: PERF.md section 6, PR 47).  Four: nothing deeper was measured, a hit
#: throws away what is queued behind it, and a traced run's lines and
#: device events may lie four rounds apart at either edge (benchmarks/
#: drivers/mine_sweep.py ``TRACE_EDGE_ROUNDS``)
MIN_ROUNDS_IN_FLIGHT, MAX_ROUNDS_IN_FLIGHT = 2, 4
#: how often the queue ran low, a count a fill (:func:`mine`); exported
#: at zero from the miner's first scrape
QUEUE_COUNTERS = QUEUE_LOW, QUEUE_EMPTY = (
    "mine.queue_low", "mine.queue_empty")
#: the depth in force, whichever job's the rounds: the process's, as the
#: device's queue is, so a job's first rounds go out at the depth the
#: last job ended on (:func:`mine` sets it from each job's own answers)
_depth = MIN_ROUNDS_IN_FLIGHT


def depth_for(round_s: float) -> int:
    """Rounds to keep in flight where one takes ``round_s``: the fewest,
    within the range, whose queued part (all but the one that runs)
    lasts ``QUEUE_LEAD_S``."""
    depth = MIN_ROUNDS_IN_FLIGHT
    while (depth < MAX_ROUNDS_IN_FLIGHT
           and (depth - 1) * round_s < QUEUE_LEAD_S):
        depth += 1
    return depth


def rounds_in_flight() -> int:
    """The depth in force."""
    return _depth


def _unfinished(handles) -> int:
    """How many of ``handles`` (oldest first; rounds finish in order) the
    device has not answered yet.  Asked of the host's side of the array
    (``SearchAnswer.ready``, a bare array's ``is_ready``): no transfer; a
    handle that cannot say is an answer already."""
    for done, handle in enumerate(handles):
        ready = getattr(handle, "ready", None) \
            or getattr(handle, "is_ready", None)
        if ready is not None and not ready():
            return len(handles) - done
    return 0


class Sweep:
    """One job's rounds on a device backend: made ready (``mine.prepare``:
    on the mesh the job's arrays laid), issued as deep as :func:`mine`
    says, answered in order.  :func:`mine` drives it, and may be handed
    one whose first rounds an earlier call issued behind the last rounds
    of its own job (``ahead``): the device then goes from one job's last
    round to the next job's first with no host between them.  Its spans
    lie in the tree of the span that was ambient when it was made."""

    def __init__(self, job: MiningJob, backend: str, *, start: int = 0,
                 stride_end: int = NONCE_SPACE, batch: int = 1 << 22,
                 mesh_devices: int = 0):
        self.job, self.backend, self.batch = job, backend, batch
        self.mesh_devices = mesh_devices
        self.cursor, self.end = start, min(stride_end, MAX_SEARCH_END)
        self.root = telemetry.current_span()
        self.t0 = time.monotonic()
        self.tried = 0
        self.first: Optional[float] = None
        self.inflight: deque = deque()  # (handle, count), oldest first
        with telemetry.span("mine.prepare", backend=backend):
            self.dispatch = _make_dispatcher(
                job, backend, mesh_devices=mesh_devices, batch=batch)

    def issue(self, depth: int) -> None:
        """Rounds issued until ``depth`` of this job's are in flight or
        the range is spent."""
        while len(self.inflight) < depth and self.cursor < self.end:
            count = min(self.batch, self.end - self.cursor)
            if self.first is None:
                # on the static-target engine a new tip's trace and
                # compile live in this dispatch
                with telemetry.span("mine.first_issue"):
                    handle = self.dispatch(self.cursor, count)
                self.first = time.monotonic() - self.t0
            else:
                with telemetry.span("mine.round.issue", light=True):
                    handle = self.dispatch(self.cursor, count)
            self.inflight.append((handle, count))
            self.cursor += count

    def wait(self) -> Optional[int]:
        """The oldest round's answer, counted: its hit, checked on the
        host, or None."""
        handle, count = self.inflight.popleft()
        with telemetry.span("mine.round.wait", light=True):
            hit = int(handle)
        self.tried += count
        telemetry.update((("mine.rounds", 1), ("mine.nonces", count)))
        if hit == int(sha_kernel.SENTINEL):
            return None
        if not self.job.check(hit):
            raise AssertionError(f"backend {self.backend} returned nonce "
                                 f"{hit} failing host check")
        if self.backend == "mesh":
            from .mesh_engine import get_mesh_engine

            get_mesh_engine(mesh_devices=self.mesh_devices).note_hit(self.job)
        return hit

    def result(self, nonce: Optional[int]) -> MineResult:
        return MineResult(nonce, self.tried, time.monotonic() - self.t0,
                          self.first or 0.0)


def mine(job: MiningJob, backend: str = "jnp", *, start: int = 0,
         stride_end: int = NONCE_SPACE, batch: int = 1 << 22,
         ttl: float = 90.0, progress: Optional[Callable] = None,
         mesh_devices: int = 0, ahead: Optional[Sweep] = None,
         next_job: Optional[Callable[[], Optional[Sweep]]] = None
         ) -> MineResult:
    """Search [start, stride_end) in fixed rounds until hit or TTL.

    ``start``/``stride_end`` let a coordinator hand disjoint nonce ranges to
    multiple chips/hosts (the reference's worker striding, miner.py:140-148,
    without the per-nonce interleave that would defeat batching).

    A round is ``batch`` nonces but the range's last, which is what is
    left: at the CLI's defaults 255 rounds of 2^24 and one of 2^24 - 1
    (``MAX_SEARCH_END``: the sentinel is never searched).  A device
    backend runs that short round on the program of the whole ones, the
    surplus lanes masked (:func:`_make_dispatcher`); ``hashes_tried``,
    ``mine.nonces`` and the progress callback count live lanes only.

    A device backend keeps :func:`rounds_in_flight` rounds on the device
    (:func:`depth_for` of the mean period of the job's own answers: two
    where a round outlasts ``QUEUE_LEAD_S``, up to four where rounds are
    short; a job's first rounds go out at the depth the last job ended
    on), and the pipeline does not drain at a job's end where the caller
    has the next job ready: once the last round of ``job`` is issued and
    while rounds are in flight, ``next_job()`` is asked, before each
    wait, for the next job's :class:`Sweep` (None: not yet), whose first
    rounds are then issued, under its own root span, into the room
    ``job``'s answers leave: the one bound holds over both jobs.  The
    caller hands that sweep back as ``ahead`` with its job (``start``,
    ``stride_end`` and ``batch`` are then the sweep's own).  A hit
    returns at once: what is queued behind it is never waited for and
    counted nowhere, and a sweep issued ahead is the caller's to drop.
    Host backends keep nothing in flight and never ask.

    An answer read, the queue is filled again (the next round issued, at
    a seam the next job's) before anything else: before ``progress``
    hears of the round, before the queue's own accounting.  That is the
    answer's time into the period, and, of the rounds that were out when
    the answer was read, how many the device had not finished by the end
    of the fill: ``mine.queue_empty`` where none (the device went idle
    before the host had refilled its queue, or within the fill),
    ``mine.queue_low`` where at most one of two or more (two in flight
    would have been at the brink; not counted with one round out, where
    it would be every round).  A sweep the ``ttl`` cuts issues nothing
    after the cut.
    """
    global _depth
    sweep = ahead if ahead is not None else Sweep(
        job, backend, start=start, stride_end=stride_end, batch=batch,
        mesh_devices=mesh_devices)
    if sweep.dispatch is None:
        return _mine_on_host(sweep, ttl, progress)
    following: Optional[Sweep] = None
    first_answer, periods = None, 0

    def fill() -> None:
        nonlocal following
        sweep.issue(_depth)
        if sweep.cursor < sweep.end or next_job is None:
            return
        if following is None and sweep.inflight:
            following = next_job()
        if following is not None:
            with telemetry.attached(following.root):
                following.issue(_depth - len(sweep.inflight))

    fill()
    while sweep.inflight:
        hit = sweep.wait()
        if hit is not None:
            return sweep.result(hit)
        now = time.monotonic()
        elapsed = now - sweep.t0
        cut = elapsed > ttl
        if not cut:
            # before anything is said or counted: the device is waiting
            out = [handle for handle, _count in sweep.inflight]
            if following is not None:
                out += [handle for handle, _count in following.inflight]
            fill()
            if first_answer is None:
                first_answer = now
            else:
                periods += 1
                _depth = depth_for((now - first_answer) / periods)
            unfinished = _unfinished(out)
            if not unfinished:
                telemetry.inc(QUEUE_EMPTY)
            if unfinished <= 1 < len(out):
                telemetry.inc(QUEUE_LOW)
        if progress is not None:
            with telemetry.span("mine.progress", light=True):
                progress(sweep.tried, elapsed)
        if cut:
            break
    return sweep.result(None)


def _mine_on_host(sweep: Sweep, ttl: float,
                  progress: Optional[Callable]) -> MineResult:
    """``native`` and ``python``: a round is searched where it is issued."""
    job, backend = sweep.job, sweep.backend
    search = _make_searcher(job, backend)
    while sweep.cursor < sweep.end:
        count = min(sweep.batch, sweep.end - sweep.cursor)
        hit = search(sweep.cursor, count)
        sweep.tried += count
        telemetry.inc("mine.rounds")
        telemetry.inc("mine.nonces", count)
        if hit is not None:
            # device says hit; host double-checks before shipping (cheap)
            if job.check(hit):
                return MineResult(hit, sweep.tried,
                                  time.monotonic() - sweep.t0)
            raise AssertionError(
                f"backend {backend} returned nonce {hit} failing host check")
        elapsed = time.monotonic() - sweep.t0
        if progress is not None:
            progress(sweep.tried, elapsed)
        if elapsed > ttl:
            break
        sweep.cursor += count
    return MineResult(None, sweep.tried, time.monotonic() - sweep.t0)
