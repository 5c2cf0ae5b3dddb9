"""Mining client CLI — wire-compatible with the reference miner.

Usage:
    python -m upow_tpu.mine.miner <address> [--device tpu|cpu|...]
                                  [--node URL] [--batch N] [--ttl S]
                                  [--shard i/k]

Protocol (miner.py:126-156): GET {node}/get_mining_info → build a template
(merkle over ALL pending tx hashes, miner.py:15-18,68), search nonces, POST
{node}/push_block {block_content, txs, block_no}.  The ``--shard i/k`` flag
assigns this process the i-th of k disjoint nonce ranges — the multi-chip /
multi-host scale-out story (each shard is one device or one host; no
communication needed until a hit).  A job is searched in rounds of
``--batch`` nonces, one progress line a round; the last round of a range
that is no multiple of the batch is short (at the default ``--shard 0/1``
the 256th has 2^24 - 1: nonce 2^32 - 1 is the searchers' no-hit sentinel
and is never tried), runs the same device program with its surplus lanes
masked, and counts its live nonces only.

The header's timestamp is the miner's one extra nonce: the node takes any
second in ``(previous block's timestamp, now]`` (verify/block.py), and a
miner that spends its range in under a second would otherwise build the
header it has just swept once more.  So within one tip, merkle root,
difficulty and address a job is stamped with the newest second of that
window this process has not swept yet (``HeaderRoll``), ``now`` first;
the ``header:`` line after the ``difficulty:`` line says which, how far
behind ``now`` it lies, how long the window is and whether it had to
repeat (the window held no fresh second: then ``now``, as ever).

The loop (``run``) owns the rounds and never fetches: a thread of its
own (``TemplateFeed``) asks the node for the next template while the job
in hand still has its last rounds in flight, and again about twice a
second while the node does not answer (refused, an error envelope, a
request that hangs).  At a job's end the loop takes the newest template
that has arrived; where none has, it builds the next job from the one in
hand, which ``HeaderRoll`` stamps with a second not swept yet (a *held*
job: ``held=1`` at the end of the ``header:`` line, after ``age=``, the
seconds since the fetch that brought the template was sent).  A template
is held only while it is younger than ``--ttl``; past that, and before
the first template, the loop waits for the feed with the device idle.  A
found block is pushed until the node gives a verdict (``ok`` true or
false), a transport error tried again about once a second while the
block's template is younger than ``--ttl``; nothing is mined beside a
pending push, and what was fetched before a push is dropped with it (an
accepted block moves the tip).  ``--once`` fetches in the loop's own
thread, one template, and starts no feed.

The rounds do not drain at a job's end (a *seam*) where the next
template is there in time: once the job in hand has issued its last
round, and while rounds are in flight, the loop builds the next job,
prepares it (on the mesh: lays its arrays) and issues its first rounds
behind them, one for each answer of the job in hand it reads (the rounds
in flight, whichever job's, stay at the engine's depth:
``engine.rounds_in_flight``), so the last answers of the job in hand are read
with the next job's rounds queued behind them
(``mine.jobs_overlapped``).  The lines keep their order all the same: the
next job's ``difficulty:`` and ``header:`` lines are said after the
``template expired`` line of the job in hand, though its timestamp was
chosen a few milliseconds earlier.  Where no fresh template is there at
the seam, after a found block, after a sweep the ``--ttl`` cut, under
``--once`` and on the host backends, which keep nothing in flight, the
next job begins when the job in hand has ended (``mine.jobs_drained``).
A job issued ahead is thrown away where the job in hand finds a block in
its last rounds (``mine.jobs_dropped``): its rounds are counted nowhere,
its header's second stays spent.
"""

from __future__ import annotations

import argparse
import contextlib
import contextvars
import json
import sys
import threading
import time
import urllib.error
import urllib.request
from dataclasses import dataclass
from typing import NamedTuple, Optional

from .. import telemetry
from ..core.clock import timestamp
from ..core.merkle import miner_merkle_root
from .engine import (MAX_SEARCH_END, QUEUE_COUNTERS, MiningJob, Sweep, mine,
                     rounds_in_flight)

GENESIS_PREV_HASH = (18_884_643).to_bytes(32, "little").hex()  # miner.py:37-40


def _http_json(url: str, payload: Optional[dict] = None, timeout: float = 20.0) -> dict:
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode() if payload is not None else None,
        headers={"Content-Type": "application/json"} if payload is not None else {},
        method="POST" if payload is not None else "GET",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def fetch_mining_info(node: str) -> dict:
    res = _http_json(node + "get_mining_info")
    if "result" not in res:  # readable node error, not KeyError
        raise RuntimeError(f"node error: {res.get('error', res)!s:.200}")
    return res["result"]


#: what a request to a node that is away raises: refused, reset, timed
#: out, an HTTP error status, a body that is no JSON
TRANSPORT_ERRORS = (urllib.error.URLError, OSError, ValueError)
#: ... and what a failed ``fetch_mining_info`` raises besides: the node's
#: error envelope (syncing, rate-limited) as RuntimeError.  All transient
FETCH_ERRORS = TRANSPORT_ERRORS + (RuntimeError,)

#: seconds from one try to the next while the node does not answer.  The
#: feed's is the part of "how long after the node's return does a job
#: begin on a fresh template" that is not the sweep in hand
FETCH_RETRY_S = 0.5
PUSH_RETRY_S = 1.0

#: exported at zero from the first scrape, like ``ROLL_COUNTERS``
FEED_COUNTERS = ("mine.jobs_held", "mine.push_retries",
                 "mine.templates_late")
#: how the loop came from one job to the next, one of the first two a
#: job but the process's first: ``OVERLAPPED`` + ``DRAINED`` =
#: ``mine.jobs`` - 1.  A dropped job is no job: ``mine.jobs`` does not
#: count it, but its header's second is spent, so the ``ROLL_COUNTERS``
#: add up to ``mine.jobs`` + ``DROPPED``
SEAM_COUNTERS = OVERLAPPED, DRAINED, DROPPED = (
    "mine.jobs_overlapped", "mine.jobs_drained", "mine.jobs_dropped")


def _say(line: str) -> None:
    """One write, newline and all: the feed's thread, a hook's thread and
    the loop's thread all print, and ``print`` writes a line and its end
    apart (a line cut in two is a line no reader of them parses)."""
    sys.stdout.write(line + "\n")
    sys.stdout.flush()


def _warn(line: str) -> None:
    """``_say`` on stderr."""
    sys.stderr.write(line + "\n")
    sys.stderr.flush()


class Template(NamedTuple):
    """One answer of ``get_mining_info``."""

    info: dict
    fetched: float  # time.monotonic() when its request was sent: upstream
                    # stamps a template at its fetch, and its age runs
                    # from there
    took: float     # seconds the request took

    def age(self) -> float:
        return time.monotonic() - self.fetched


class TemplateFeed:
    """The newest template the node has served, fetched on a thread of
    the feed's own so that the loop that owns the rounds never waits on
    the node while it holds a template it may mine.

    ``ask`` (the loop, near a job's end) wants a refresh for the next
    job; the thread tries until one arrives, ``FETCH_RETRY_S`` apart,
    each try the span ``mine.fetch`` in the tree of the job in hand
    (``root``).  ``take`` (the loop, at a job's end) chooses what the
    next job is built from.  ``forget`` (after a push) drops what was
    fetched so far.  Whatever ends the thread but a failed fetch is
    raised in the loop by the next ``take``: a miner whose feed is dead
    must not mine on in silence."""

    def __init__(self, node: str):
        self.node = node
        self.root = None     # the job in hand's root span
        self.took = 0.0      # seconds the last good fetch took
        self.jobs = 0        # jobs begun (``take`` returned)
        self._cond = threading.Condition()
        self._wanted = False
        self._asked_for = 0  # the job the last ``ask`` wanted a refresh for
        self._newest: Optional[Template] = None
        self._not_before = 0.0
        self._stopped = False
        self._died: Optional[BaseException] = None

    def start(self) -> None:
        # in the caller's context: its telemetry scope counts the tries
        threading.Thread(target=contextvars.copy_context().run,
                         args=(self._run,), daemon=True,
                         name="miner-feed").start()

    def stop(self) -> None:
        """The loop has ended: the thread makes no further try."""
        with self._cond:
            self._stopped = True
            self._cond.notify_all()

    def fetch(self) -> Optional[Template]:
        """One try, on the caller's thread: the template, or None after
        a failure that is counted, said and left in the span."""
        t0 = time.monotonic()
        with telemetry.attached(self.root), \
                telemetry.span("mine.fetch") as span:
            try:
                info, error = fetch_mining_info(self.node), None
            except FETCH_ERRORS as e:
                info, error = None, e
            if span is not None:
                span.fields["ok"] = error is None
                if error is not None:
                    span.error = type(error).__name__
                    span.fields["error"] = f"{error!s:.120}"
        if error is None:
            return Template(info, t0, time.monotonic() - t0)
        telemetry.inc("mine.fetch_errors")
        _warn(f"node unreachable: {error}; retrying")
        return None

    def fetch_until_good(self, beat) -> Template:
        """``--once``: the one template, fetched here and now."""
        while True:
            beat()
            got = self.fetch()
            if got is not None:
                return got
            time.sleep(1)

    def _run(self) -> None:
        try:
            while True:
                with self._cond:
                    while not self._wanted and not self._stopped:
                        self._cond.wait()
                    if self._stopped:
                        return
                    for_job = self.jobs + 1
                t0 = time.monotonic()
                got = self.fetch()
                if got is None:
                    time.sleep(max(0.0, FETCH_RETRY_S
                                   - (time.monotonic() - t0)))
                    continue
                with self._cond:
                    self._arrived(got, for_job)
        except BaseException as e:   # handed to the loop, which raises it
            with self._cond:
                self._died = e
                self._cond.notify_all()

    def _arrived(self, got: Template, for_job: int) -> None:
        """An answer (under the lock).  It is late where the job it was
        fetched for has begun without it, or a newer answer is here
        already; the newest stays the newest either way."""
        newest = self._newest
        newer = newest is None or got.fetched > newest.fetched
        if self.jobs >= for_job or not newer:
            telemetry.inc("mine.templates_late")
        if got.fetched < self._not_before or not newer:
            return
        self._newest, self.took = got, got.took
        self._wanted = False
        self._cond.notify_all()

    def _want(self) -> None:
        if not self._wanted:
            self._wanted = True
            self._cond.notify_all()

    def ask(self) -> None:
        """A refresh for the next job, once a job."""
        with self._cond:
            if self._asked_for <= self.jobs:
                self._asked_for = self.jobs + 1
                self._want()

    def forget(self) -> None:
        """A block was pushed: the tip may have moved under every
        template fetched up to now, one on its way included."""
        with self._cond:
            self._newest, self._not_before = None, time.monotonic()
            self._want()

    def take(self, have: Optional[Template], ttl: float, beat) -> Template:
        """The template the next job is built from: the newest that has
        arrived if it is newer than ``have``; else ``have`` again (a held
        job) while it is younger than ``ttl``, with a refresh wanted;
        else the next to arrive, waited for (``beat`` once a second: a
        node that is away is no device hang)."""
        with self._cond:
            while True:
                if self._died is not None:
                    raise self._died
                if self._newest is not None and self._newest is not have:
                    chosen = self._newest
                    break
                self._want()
                if have is not None and have.age() < ttl:
                    chosen = have
                    break
                beat()
                self._cond.wait(1.0)
            self.jobs += 1
            return chosen

    def poll(self, have: Optional[Template]) -> Optional[Template]:
        """``take`` for a seam, while the rounds of the job in hand are
        in flight: no wait and no held job.  The newest template if it
        is newer than ``have`` and none fresher is on its way, which
        begins the next job; else None, and the seam is ``take``'s once
        the job in hand has ended."""
        with self._cond:
            if self._died is not None:
                raise self._died
            if self._wanted or self._newest is None \
                    or self._newest is have:
                return None
            self.jobs += 1
            return self._newest


def push_until_verdict(node: str, content: str, txs: list, block_no: int,
                       template: Template, ttl: float, beat) -> tuple:
    """(the node's answer, tries).  An answer with ``ok`` in it is the
    node's verdict, true or false, and ends the push, as does an HTTP
    status that blames the request; a transport error (refused, reset,
    timed out, 5xx, 429) is tried again, ``PUSH_RETRY_S`` apart and the
    same bytes each time, while the block's template is younger than
    ``ttl``."""
    tries = 0
    while True:
        tries += 1
        beat()
        t0 = time.monotonic()
        try:
            reply = push_block(node, content, txs, block_no)
            if isinstance(reply, dict) and "ok" in reply:
                return reply, tries
            error = f"no verdict in {reply!s:.100}"
        except urllib.error.HTTPError as e:
            if e.code < 500 and e.code != 429:
                return {"ok": False, "error": f"HTTP {e.code} {e.reason}"}, \
                    tries
            error = e
        except TRANSPORT_ERRORS as e:
            error = e
        telemetry.inc("mine.push_errors")
        if template.age() >= ttl:
            _warn(f"push_block failed: {error}; its template is past the "
                  "ttl: dropped")
            return {"ok": False}, tries
        _warn(f"push_block failed: {error}; retrying")
        telemetry.inc("mine.push_retries")
        time.sleep(max(0.0, PUSH_RETRY_S - (time.monotonic() - t0)))


#: one of the three a job, so their sum is ``mine.jobs``
ROLL_COUNTERS = FRESH, ROLLED, REPEATED = (
    "mine.headers_fresh", "mine.headers_rolled", "mine.headers_repeated")


class Stamp(NamedTuple):
    """The timestamp a job's header carries, and how it was come by."""

    timestamp: int
    behind_s: int   # now - timestamp: 0 unless rolled back
    window_s: int   # now - the previous block's timestamp
    repeat: int     # 1: the window held no fresh second, so ``now`` again


def choose_timestamp(prev_ts: int, now: int, swept) -> tuple:
    """(second, repeat): the newest second in ``(prev_ts, now]`` that is
    not in ``swept``, or, where none is left, ``now`` once more.  ``now``
    itself is always a candidate: where the window is empty (a node whose
    clock runs ahead of this one) the miner stamps ``now`` as it always
    has."""
    for second in range(now, min(prev_ts, now - 1), -1):
        if second not in swept:
            return second, False
    return now, True


class HeaderRoll:
    """The seconds this process has stamped a header with, and so swept
    its nonce range under, for one ``key`` = (previous hash, merkle root,
    difficulty, address).  Dropped when the key changes; every member
    lies in the key's window ``(prev_ts, now]``, whose length bounds it."""

    def __init__(self):
        self.key = None
        self.swept: set = set()

    def stamp(self, key: tuple, prev_ts: int, now: int) -> Stamp:
        if key != self.key:
            self.key, self.swept = key, set()
        second, repeat = choose_timestamp(prev_ts, now, self.swept)
        # swept from here on, whatever ends the job: a job the TTL cuts
        # short began at the range's start, and so would its twin
        self.swept.add(second)
        telemetry.inc(REPEATED if repeat else
                      ROLLED if second < now else FRESH)
        return Stamp(second, now - second, now - prev_ts, int(repeat))


def build_job(info: dict, address: str,
              roll: Optional[HeaderRoll] = None) -> tuple:
    """(job, pending hashes, block number, Stamp).  ``roll`` is what the
    caller has swept so far; without one the job is stamped ``now``."""
    last_block = dict(info["last_block"])
    last_block.setdefault("hash", GENESIS_PREV_HASH)
    last_block.setdefault("id", 0)
    pending_hashes = info["pending_transactions_hashes"]
    merkle_root = miner_merkle_root(pending_hashes)
    now = timestamp()
    with telemetry.span("mine.roll", light=True):
        # a node that names no previous timestamp leaves one second
        stamp = (roll or HeaderRoll()).stamp(
            (last_block["hash"], merkle_root, str(info["difficulty"]),
             address),
            int(last_block.get("timestamp", now - 1)), now)
    job = MiningJob.from_header_fields(
        previous_hash=last_block["hash"],
        address=address,
        merkle_root=merkle_root,
        timestamp=stamp.timestamp,
        difficulty=info["difficulty"],
    )
    return job, pending_hashes, last_block["id"] + 1, stamp


def push_block(node: str, block_content: str, txs: list, block_no: int) -> dict:
    """One try; the timeout is per try."""
    return _http_json(
        node + "push_block",
        {"block_content": block_content, "txs": txs, "block_no": block_no},
        timeout=20 + len(txs) // 3,
    )


def select_backend(device: str, mesh_devices: int = 0) -> tuple:
    """(backend, mesh size) of ``--device``.  ``tpu`` is the resident
    program (mesh_engine.py: target, template and range are data, one
    compile a process) on a mesh of one device, the chip the static
    engine used, whatever ``device.mesh_devices`` says; ``mesh`` takes
    ``mesh_devices`` (0 = every visible device); ``pallas`` is the
    static-target engine, one trace and compile a tip.  The mesh size
    means nothing to the other backends and rides along unchanged."""
    if device == "tpu":
        return "mesh", 1
    if device == "cpu":
        from .. import native

        device = "native" if native.load() is not None else "jnp"
    elif device not in ("pallas", "jnp", "native", "python", "mesh"):
        raise SystemExit(f"unknown device {device!r}")
    return device, mesh_devices


def _runtime_arm_reason() -> Optional[str]:
    """The device runtime's structured ``arm_failure_reason``, read
    WITHOUT blocking — the watchdog fires precisely when dispatches are
    stuck, so it must not wait on the armed-platform event.  None when
    the runtime never started, armed cleanly, or can't be inspected."""
    try:
        from ..device import runtime as _dr

        rt = _dr._RUNTIME
        if rt is None:
            return None
        return rt._arm_info.get("arm_failure_reason")
    except (ImportError, AttributeError, TypeError):
        return None


#: watchdog exit codes the supervisor decodes in its respawn log
RC_HANG = 3        # stale heartbeat, backend had armed (device hang)
RC_ARM_FAILED = 4  # stale heartbeat AND the runtime recorded an arm failure
RC_NO_DEVICE = 5   # device=tpu and no TPU at start-up: not respawned


def _start_hang_watchdog(heartbeat: dict, limit: float, _exit=None):
    """A device dispatch on a lost device can HANG (never raise), so
    the in-loop TTL check can never fire.  This thread hard-exits the
    process when the heartbeat goes stale; the supervisor (reference
    miner.py:149-156's outer watchdog) respawns a fresh process — the
    only reliable recovery once a thread is stuck inside the PJRT client.

    When the device runtime recorded a structured arm failure, the exit
    message carries that actual reason (and the exit status becomes
    ``RC_ARM_FAILED``) instead of the generic device-hang guess.

    ``heartbeat['limit']`` (optional) overrides ``limit`` — the caller
    raises it for the first round (cold compile can exceed the steady-
    state budget) and drops it once progress ticks.
    """
    import os

    _exit = _exit or os._exit

    def watch():
        while True:
            time.sleep(min(5.0, limit / 4))
            lim = heartbeat.get("limit", limit)
            if time.monotonic() - heartbeat["t"] > lim:
                reason = _runtime_arm_reason()
                if reason:
                    print(f"no mining progress for {lim:.0f}s — backend "
                          f"arm failure: {reason}; exiting for respawn",
                          file=sys.stderr, flush=True)
                    _exit(RC_ARM_FAILED)
                else:
                    print(f"no mining progress for {lim:.0f}s — device "
                          "hang? exiting for respawn",
                          file=sys.stderr, flush=True)
                    _exit(RC_HANG)
                # os._exit never returns; a test's substitute does — stop
                # so the thread doesn't keep printing for the rest of the
                # process lifetime
                return

    t = threading.Thread(target=watch, daemon=True, name="miner-watchdog")
    t.start()
    return t


@dataclass
class _Job:
    """One job from its build to its end."""

    root: object            # its ``mine.job`` root span
    template: Template
    held: bool
    work: MiningJob
    pending_hashes: list
    block_no: int
    stamp: Stamp
    age: float                      # the template's, when the job was built
    sweep: Optional[Sweep] = None   # issued ahead, at the seam before it


@contextlib.contextmanager
def _under(root, ends: bool):
    """``root`` ambient for the block, and closed with it where the block
    ``ends`` the job or raises."""
    try:
        with telemetry.attached(root):
            yield
    except BaseException as e:
        telemetry.close_trace(root, e)
        raise
    if ends:
        telemetry.close_trace(root)


def run(address: str, node: str, device: str, batch: int, ttl: float,
        shard: tuple = (0, 1), once: bool = False,
        mesh_devices: int = 0, hang_grace: float = 90.0,
        first_round_grace: float = 240.0) -> int:
    backend, mesh_devices = select_backend(device, mesh_devices)
    i, k = shard
    from ..parallel.multihost import plan_nonce_ranges

    lo, hi = plan_nonce_ranges(k)[i]
    print(f"upow_tpu miner: backend={backend} shard={i}/{k} "
          f"nonces=[{lo}, {hi}) node={node}")
    # first round gets extra headroom: a cold-cache pallas compile can
    # legitimately exceed the steady-state ttl+grace budget
    heartbeat = {"t": time.monotonic(),
                 "limit": ttl + hang_grace + first_round_grace}
    if backend in ("pallas", "jnp", "mesh") and not once:
        _start_hang_watchdog(heartbeat, ttl + hang_grace)
    roll = HeaderRoll()
    for name in (ROLL_COUNTERS + FEED_COUNTERS + SEAM_COUNTERS
                 + QUEUE_COUNTERS):
        telemetry.ensure_counter(name)   # at zero from scrape one
    total = min(hi, MAX_SEARCH_END) - lo
    feed = TemplateFeed(node)
    if not once:
        feed.start()
    job = ahead = None   # the job in hand; the one issued behind it
    seam_s = 0.0         # what the last seam's build and prepare took
    begun = 0            # jobs that became the job in hand

    def beat():
        heartbeat["t"] = time.monotonic()

    def progress(tried, elapsed):
        beat()
        heartbeat["limit"] = ttl + hang_grace  # compiled: steady budget
        _say(f"{tried / elapsed / 1e6:.2f} MH/s ({tried} hashes)")
        # the next template is asked for so that it is in hand when the
        # seam begins, at this job's last issue: the rounds then still in
        # flight (the engine's depth in force), one more because this
        # look comes once a round, the fetch's seconds, and the last
        # seam's build and prepare for room (a seam that finds no
        # template looks again a round later); a sweep the ttl will cut
        # ends there.  Once the seam has its template the next ask is the
        # next job's
        round_s = elapsed * batch / tried
        left_s = min((total - tried) / batch * round_s, ttl - elapsed)
        if not once and ahead is None and left_s <= (
                (rounds_in_flight() + 1) * round_s + feed.took + seam_s):
            feed.ask()

    def open_root():
        return telemetry.open_trace("mine.job", backend=backend,
                                    shard=f"{i}/{k}", nonces=total)

    def build(root, template: Template, held: bool) -> _Job:
        info, age = template.info, template.age()
        said = {"held": int(held), "template_age_s": round(age, 3)}
        with telemetry.span("mine.build_job") as built:
            mining_job, pending_hashes, block_no, stamp = build_job(
                info, address, roll)
            if built is not None:
                built.fields.update(stamp._asdict(), **said,
                                    pending=len(pending_hashes))
        root.fields.update(
            stamp._asdict(), **said,
            block=block_no, difficulty=str(info["difficulty"]),
            tip=str(getattr(mining_job, "previous_hash", ""))[-12:])
        return _Job(root, template, held, mining_job, pending_hashes,
                    block_no, stamp, age)

    def next_job() -> Optional[Sweep]:
        """``engine.mine`` at a seam, rounds of the job in hand still in
        flight: the next job built and prepared under its own root, if
        its template is here; its first rounds are the engine's to
        issue."""
        nonlocal ahead, seam_s
        with telemetry.span("mine.take_template", light=True):
            template = feed.poll(job.template)
        if template is None:
            return None
        t0 = time.perf_counter()
        root = open_root()
        with _under(root, ends=False):
            ahead = build(root, template, held=False)
            ahead.sweep = Sweep(ahead.work, backend, start=lo, stride_end=hi,
                                batch=batch, mesh_devices=mesh_devices)
        seam_s = time.perf_counter() - t0
        return ahead.sweep

    def search_and_end() -> tuple:
        """The job in hand from its lines to its end.  Returns (what
        ``--once`` exits with, whether a block was pushed)."""
        nonlocal ahead, begun
        root, info = job.root, job.template.info
        telemetry.inc("mine.jobs")
        if begun:
            telemetry.inc(DRAINED if job.sweep is None else OVERLAPPED)
        begun += 1
        if job.held:
            telemetry.inc("mine.jobs_held")
        stamp = job.stamp
        _say(f"difficulty: {info['difficulty']}  block: {job.block_no}  "
             f"confirming {len(job.pending_hashes)} transactions")
        _say(f"header: timestamp={stamp.timestamp} behind={stamp.behind_s} "
             f"window={stamp.window_s} repeat={stamp.repeat} "
             f"held={int(job.held)} age={job.age:.1f}")
        result = mine(job.work, backend, start=lo, stride_end=hi, batch=batch,
                      ttl=ttl, progress=progress, mesh_devices=mesh_devices,
                      ahead=job.sweep, next_job=None if once else next_job)
        if result.nonce is None:
            telemetry.inc("mine.jobs_expired")
            root.fields["end"] = "expired"
            _say(f"template expired after {result.hashes_tried} hashes; "
                 "refreshing")
            return 1, False
        telemetry.inc("mine.jobs_found")
        root.fields["end"] = "found"
        if ahead is not None:
            # found in the last rounds, the next job's first behind them:
            # those are left where they are, and the job was none
            telemetry.inc(DROPPED)
            ahead.root.fields["end"] = "dropped"
            telemetry.close_trace(ahead.root)
            ahead = None
        content = job.work.block_content(result.nonce)
        _say(f"found nonce {result.nonce} at {result.hashrate / 1e6:.2f} MH/s"
             f" ({result.hashes_tried} hashes in {result.elapsed:.2f}s, first"
             f" dispatch {result.first_dispatch:.2f}s)")
        if backend == "mesh":
            _print_mesh_accounting(mesh_devices)
        with telemetry.span("mine.push") as pushed:
            reply, tries = push_until_verdict(
                node, content, job.pending_hashes, job.block_no,
                job.template, ttl, beat)
            if pushed is not None:
                pushed.fields.update(ok=bool(reply.get("ok")),
                                     attempts=tries)
        _say(str(reply))
        if reply.get("ok"):
            _say("BLOCK MINED\n")
        return (0 if reply.get("ok") else 1), True

    def take_and_build(have: Optional[Template]) -> _Job:
        """The seam with nothing in flight, and the first job."""
        root = feed.root = open_root()   # a fetch waited for is this job's
        with _under(root, ends=False):
            # from the end of a job to the template of the next: any
            # wait for the node is in here, and nowhere else
            with telemetry.span("mine.take_template", light=True):
                template = feed.fetch_until_good(beat) if once \
                    else feed.take(have, ttl, beat)
            return build(root, template, held=template is have)

    have = None
    try:
        while True:
            beat()
            if ahead is not None:
                job, ahead = ahead, None
                feed.root = job.root
            else:
                job = take_and_build(have)
            with _under(job.root, ends=True):
                rc, pushed = search_and_end()
            if once:
                return rc
            have = job.template
            if pushed:
                have = None
                feed.forget()
    finally:
        feed.stop()
        if ahead is not None:
            telemetry.close_trace(ahead.root)


def _print_mesh_accounting(mesh_devices: int) -> None:
    """One line of the resident mesh engine's per-shard accounting: the
    devices of the mesh and the disjoint [lo, hi) each shard searched
    in the last round."""
    from .mesh_engine import get_mesh_engine

    eng = get_mesh_engine(mesh_devices=mesh_devices)  # the resident one
    stats = eng.stats()
    last = stats["rounds"][-1] if stats["rounds"] else {}
    print("mesh: " + json.dumps({
        "devices": [f"{d.platform}:{d.id}" for d in eng.mesh_devices()],
        "body": stats["body"],
        "batch_per_device": stats["batch_per_device"],
        "dispatches": stats["dispatches"],
        "job_layouts": stats["job_layouts"],
        "jit_entries": stats["jit_entries"],
        "rounds_in_flight": rounds_in_flight(),
        "exact_steps": telemetry.counters().get(
            "kernel.mine_mesh.exact_steps", 0),
        "last_round_shards": last.get("shards", [])}), flush=True)


def _start_device(device: str) -> int:
    """Apply ``device`` (tpu|cpu|auto) through the device runtime and
    print which device serves; non-zero (with the arm's own reason on
    stderr) when ``tpu`` was asked for and is not there."""
    from ..device import runtime as _dr

    try:
        info = _dr.start(device)
    except (_dr.DeviceUnavailable, ValueError) as e:
        print(f"upow_tpu miner: {e}", file=sys.stderr, flush=True)
        return RC_NO_DEVICE
    if info:
        print(_dr.device_line(info), flush=True)
    return 0


def _install_profile_hooks(profile) -> None:
    """The searching process's profiler hook (``ProfilingConfig.enabled``
    only): SIGUSR1 starts a ``jax.profiler`` capture into
    ``profile.trace_dir``, SIGUSR2 stops it, each answered by one
    ``profile:`` line on stdout; SIGTERM exits cleanly, through the exit
    lines.  A signal whose handler somebody installed before
    (``benchmarks/launch/miner_child.py``) is left alone.  The capture
    starts and stops on a thread of its own: a handler runs on the
    miner's thread, between two rounds."""
    import signal

    from .. import profiling

    def answer(what, call):
        threading.Thread(
            target=lambda: _say(f"profile: {what} {json.dumps(call())}"),
            daemon=True, name="miner-profile").start()

    def on_term(*_a):
        raise SystemExit(0)

    hooks = {
        signal.SIGUSR1: lambda *_a: answer("start", lambda: profiling.start(
            profile.trace_dir, profile.max_capture_seconds)),
        signal.SIGUSR2: lambda *_a: answer("stop", profiling.stop),
        signal.SIGTERM: on_term,
    }
    for sig, handler in hooks.items():
        if signal.getsignal(sig) is signal.SIG_DFL:
            signal.signal(sig, handler)


def _print_exit_lines() -> None:
    """What the searching process leaves on stdout under
    ``ProfilingConfig.enabled``: the device's peak memory, the flat
    span and counter aggregates and the round loop's depth in force.  An
    open capture is closed first."""
    from .. import profiling

    if profiling.status().get("active"):
        _say(f"profile: stop {json.dumps(profiling.stop())}")
    peaks = [int(mem["peak_bytes_in_use"])
             for mem in telemetry.device.device_memory().values()
             if "peak_bytes_in_use" in mem]
    _say(f"memory: peak_bytes={max(peaks) if peaks else 'null'}")
    _say("telemetry: " + json.dumps(
        {"spans": telemetry.stats(), "counters": telemetry.counters(),
         "rounds_in_flight": rounds_in_flight()}, sort_keys=True))


def _reap(procs, timeout: float = 5.0) -> None:
    import subprocess

    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()


def _child_cmd(args) -> list:
    """Base child command shared by the supervisor and the worker fan-out
    (one home so new flags cannot silently diverge)."""
    return [sys.executable, "-m", "upow_tpu.mine.miner", args.address,
            "--node", args.node, "--device", args.device,
            "--batch", str(args.batch), "--ttl", str(args.ttl)]


def _supervise(args) -> int:
    """Respawn loop for device-backend miners (reference miner.py:149-156):
    restart the mining child whenever it exits — watchdog hang-exit (rc 3),
    crash, or backend failure — with a short backoff."""
    import os
    import subprocess

    env = dict(os.environ, UPOW_MINER_CHILD="1")
    cmd = _child_cmd(args) + ["--shard", args.shard]
    child = None
    rc_meaning = {
        RC_HANG: "watchdog: device hang (backend had armed)",
        RC_ARM_FAILED: "watchdog: backend arm failure — the child's "
                       "stderr above has the structured reason",
    }
    try:
        while True:
            child = subprocess.Popen(cmd, env=env)
            rc = child.wait()
            if rc in (0, RC_NO_DEVICE):
                return rc  # done, or device=tpu without a TPU: final
            detail = rc_meaning.get(rc, "crash or backend failure")
            print(f"miner child exited rc={rc} ({detail}); "
                  "respawning in 5s", file=sys.stderr, flush=True)
            child = None
            time.sleep(5)
    except KeyboardInterrupt:
        if child is not None:
            child.terminate()
            try:
                child.wait(timeout=5)
            except subprocess.TimeoutExpired:
                child.kill()
        return 130


def _run_workers(args) -> int:
    """Reference-style multi-process fan-out (miner.py:126-156): worker i
    takes contiguous shard i/N.  CPU-parity path — one process drives a
    whole TPU, so fanning out there would just contend for the chip."""
    import subprocess

    if args.device in ("tpu", "pallas", "mesh"):
        print("workers>1 with --device tpu would have every process fight "
              "over the one chip (libtpu is single-client); use --device "
              "cpu, or shard across hosts with --shard/UPOW_COORDINATOR_"
              "ADDRESS", file=sys.stderr)
        return 2
    import os

    procs = []
    base = _child_cmd(args)
    if args.once:
        base.append("--once")
    # workers are leaf miners: the child marker stops each one becoming a
    # nested supervisor (which would mask failures and orphan grandchildren)
    env = dict(os.environ, UPOW_MINER_CHILD="1")
    for i in range(args.workers):
        procs.append(subprocess.Popen(
            base + ["--shard", f"{i}/{args.workers}"], env=env))
    try:
        while True:
            codes = [p.poll() for p in procs]
            if any(c == 0 for c in codes):
                _reap(procs)  # first finder wins; stop the losers
                return 0
            if all(c is not None for c in codes):
                return max(codes)
            time.sleep(0.2)
    except KeyboardInterrupt:
        _reap(procs)
        return 130


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="uPow TPU miner")
    ap.add_argument("address")
    ap.add_argument("workers", nargs="?", type=int, default=0,
                    help="reference-compatible positional: spawn N host "
                         "processes on disjoint nonce shards "
                         "(miner.py:126-156); 0 = single process")
    ap.add_argument("node_pos", nargs="?", default=None,
                    help="reference-compatible positional node URL")
    ap.add_argument("--node", default="http://localhost:3006/")
    ap.add_argument("--device", default="tpu",
                    help="tpu: the resident search program on one chip, "
                         "target as data, no compile a tip; mesh: the same "
                         "program over device.mesh_devices chips (0 = all); "
                         "cpu; or an explicit backend pallas (the "
                         "static-target engine, one compile a tip; kept "
                         "until ROADMAP D2)|jnp|native|python")
    ap.add_argument("--batch", type=int, default=0,
                    help="nonces per dispatch (0 = config device.search_batch)")
    ap.add_argument("--ttl", type=float, default=90.0)
    ap.add_argument("--shard", default="0/1", help="i/k disjoint nonce-range shard")
    ap.add_argument("--once", action="store_true", help="mine a single template and exit")
    args = ap.parse_args(argv)
    from ..config import Config

    cfg = Config.load()
    if args.batch <= 0:
        args.batch = cfg.device.search_batch
    if args.node_pos:
        args.node = args.node_pos
    if args.workers > 1:
        return _run_workers(args)
    import os

    if (not args.once
            and select_backend(args.device)[0] in ("pallas", "jnp", "mesh")
            and not os.environ.get("UPOW_MINER_CHILD")):
        # device backends run supervised: the hang watchdog hard-exits a
        # child stuck in a dispatch that never returns, and this loop
        # respawns it (the reference's outer watchdog, miner.py:149-156).
        # The supervisor never touches JAX — the chip is the child's.
        return _supervise(args)
    # this process searches: give ``device`` its one meaning before the
    # first job is fetched (--device tpu|cpu IS the switch; an explicit
    # backend name defers to config device.device)
    i, k = (int(x) for x in args.shard.split("/"))
    assert 0 <= i < k, "--shard must be i/k with 0 <= i < k"
    if (i, k) == (0, 1):
        # multi-host run (UPOW_COORDINATOR_ADDRESS set): each process
        # takes its slot in the deterministic nonce plan automatically
        from ..parallel import multihost

        if multihost.initialize():
            import jax

            i, k = jax.process_index(), jax.process_count()
            print(f"distributed mining: process {i}/{k}")
    rc = _start_device(args.device if args.device in ("tpu", "cpu")
                       else cfg.device.device)
    if rc:
        return rc
    node = args.node.rstrip("/") + "/"
    if cfg.profile.enabled:
        _install_profile_hooks(cfg.profile)
    try:
        return run(args.address, node, args.device, args.batch, args.ttl,
                   shard=(i, k), once=args.once,
                   mesh_devices=cfg.device.mesh_devices)
    finally:
        if cfg.profile.enabled:
            _print_exit_lines()


if __name__ == "__main__":
    raise SystemExit(main())
