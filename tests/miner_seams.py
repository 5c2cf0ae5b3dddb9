"""What the tests of the miner's seams share (ISSUES 44 and 47; no test
lives here): a device that answers at once, logs every issue and every
answer read and says which rounds it has finished, the engine's depth
pinned, a feed without its thread, and ``miner.run`` over a scripted node
for a given number of jobs."""

import importlib.util
import os
import time

from upow_tpu import telemetry
from upow_tpu.crypto.sha256 import SENTINEL
from upow_tpu.mine import engine, miner

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEAMS = ("mine.jobs",) + miner.SEAM_COUNTERS
SHARD = (0, 1 << 18)      # shard 0 of 2^18: a range of RANGE nonces
RANGE = 1 << 14
ROUNDS = ("mine.rounds", "mine.nonces")
QUEUE = engine.QUEUE_COUNTERS


def minerlog():
    """``benchmarks/harness/minerlog.py``, as the benchmark reads the
    miner's lines."""
    spec = importlib.util.spec_from_file_location(
        "benchmarks_minerlog",
        os.path.join(REPO, "benchmarks", "harness", "minerlog.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def counters(*names) -> dict:
    have = telemetry.counters()
    return {n: have.get(n, 0) for n in names}


def grew(before: dict) -> dict:
    return {n: v - before[n] for n, v in counters(*before).items()}


class FakeDevice:
    """In place of ``engine._make_dispatcher``: ``log`` holds ("issue" |
    "wait", the job's number by the order of ``mine.prepare``, the
    round's start) in the order the loop made them; ``hits`` maps (job
    number, start) to the nonce that round answers, which every job's
    ``check`` is made to pass; a round takes ``round_s`` to answer, and
    ``on_wait(job number, start)`` runs before each answer is read.  The
    engine keeps ``depth`` rounds in flight whatever their period (None:
    by its own rule, from two).  A handle says it is ready where the
    device has finished its round: the rounds answered so far and the
    ``ahead`` next (in the order they were issued)."""

    def __init__(self, monkeypatch, hits=(), round_s=0.0, depth=2):
        self.log, self.jobs = [], []
        self.hits, self.round_s = dict(hits), round_s
        self.on_wait = lambda number, start: None
        self.issued = self.answered = self.ahead = 0
        monkeypatch.setattr(engine, "_make_dispatcher", self.make)
        monkeypatch.setattr(engine.MiningJob, "check", lambda job, n: True)
        monkeypatch.setattr(engine, "_depth",
                            depth or engine.MIN_ROUNDS_IN_FLIGHT)
        if depth:
            monkeypatch.setattr(engine, "depth_for", lambda round_s: depth)

    def make(self, job, backend, mesh_devices=0, batch=None):
        number, device = len(self.jobs), self
        self.jobs.append(job)

        class Handle:
            def __init__(self, start):
                self.start = start
                self.nth, device.issued = device.issued, device.issued + 1

            def ready(self):
                return self.nth < device.answered + device.ahead

            def __int__(self):
                time.sleep(device.round_s)
                device.on_wait(number, self.start)
                device.log.append(("wait", number, self.start))
                device.answered = self.nth + 1
                return device.hits.get((number, self.start), int(SENTINEL))

        def dispatch(start, count):
            device.log.append(("issue", number, start))
            return Handle(start)

        return dispatch

    def rounds(self, what: str, number: int) -> list:
        return [start for w, n, start in self.log
                if w == what and n == number]

    def most_in_flight(self, upto=None) -> int:
        """The most rounds that were out at once, whichever job's (rounds
        dropped after a hit stay out: ``upto`` ends the count there)."""
        out = most = 0
        for what, _number, _start in self.log[:upto]:
            out += 1 if what == "issue" else -1
            most = max(most, out)
        return most


class InlineFeed(miner.TemplateFeed):
    """The feed without its thread: a template is fetched where it is
    wanted, by the thread that wants it, so a seam finds it or not by
    the loop's own rules and not by the scheduler's."""

    def start(self):
        pass

    def _want(self):
        got = self.fetch()
        if got is not None:
            self._arrived(got, self.jobs + 1)


class Done(BaseException):
    """Ends ``miner.run`` from inside its loop."""


def info(difficulty=9.0, tip=0xFEED, prev_ts=None) -> dict:
    last = {"hash": "%064x" % tip, "id": 41}
    if prev_ts is not None:
        last["timestamp"] = prev_ts
    return {"difficulty": difficulty, "last_block": last,
            "pending_transactions_hashes": ["%064x" % 5, "%064x" % 6]}


def address() -> str:
    from upow_tpu.core import curve, point_to_string

    return point_to_string(curve.keygen(rng=44)[1])


def run_jobs(monkeypatch, capsys, n_jobs, *, serve=lambda k: info(),
             backend="jnp", rounds=4, ttl=90.0, once=False,
             push=lambda *a: {"ok": True}, feed=InlineFeed):
    """``miner.run`` until the ``n_jobs``-th job it built has ended (one
    built ahead and dropped is one of them): jobs of ``RANGE`` nonces in
    ``rounds`` rounds, ``serve(k)`` the k-th ``get_mining_info``.
    Returns (stdout's lines, what ``run`` returned or "done")."""
    fetched = []

    def fetch(_node):
        fetched.append(1)
        return serve(len(fetched) - 1)

    class Feed(feed):
        """No job after the ``n_jobs``-th, and none ahead of it."""

        def take(self, have, ttl, beat):
            if self.jobs == n_jobs:
                raise Done
            return super().take(have, ttl, beat)

        def poll(self, have):
            return None if self.jobs == n_jobs else super().poll(have)

    monkeypatch.setattr(miner, "fetch_mining_info", fetch)
    monkeypatch.setattr(miner, "push_block", push)
    monkeypatch.setattr(miner, "TemplateFeed", Feed)
    monkeypatch.setattr(miner, "_start_hang_watchdog", lambda *a, **k: None)
    try:
        ended = miner.run(address(), "http://x/", backend, RANGE // rounds,
                          ttl, shard=SHARD, once=once)
    except Done:
        ended = "done"
    return capsys.readouterr().out.splitlines(), ended
