"""The header's timestamp as the miner's extra nonce (``mine/miner.py``
``choose_timestamp``, ``HeaderRoll``, ``build_job``), held against the
benchmark's plain reference ``benchmarks/harness/rollref.py``: the
node's rule ``prev_ts < timestamp <= now`` and what counts as hashing a
header twice.  CPU, seconds."""

import importlib.util
import json
import os
import random
import re
import sys

import pytest

from upow_tpu import telemetry
from upow_tpu.core import AddressFormat, clock, curve, point_to_string
from upow_tpu.mine import miner
from upow_tpu.mine.engine import MiningJob
from upow_tpu.telemetry import scope

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEADER = re.compile(r"header: timestamp=(\d+) behind=(-?\d+) "
                    r"window=(-?\d+) repeat=([01])"
                    r" held=[01] age=\d+\.\d")   # the feed's two, last


@pytest.fixture(scope="module")
def rollref():
    bench = os.path.join(REPO, "benchmarks")
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location(
            "harness.rollref", os.path.join(bench, "harness", "rollref.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module
    finally:
        sys.path.remove(bench)


@pytest.fixture
def frozen_clock():
    clock.freeze(1_790_000_000)
    yield clock
    clock.reset()


def _address(v1: bool = False) -> str:
    _, pub = curve.keygen(rng=4242)
    return point_to_string(pub, AddressFormat.FULL_HEX) if v1 \
        else point_to_string(pub)


def _info(prev_ts, tip=0xFEED, pending=(5, 6), difficulty=9.0):
    last = {"hash": "%064x" % tip, "id": 41}
    if prev_ts is not None:
        last["timestamp"] = prev_ts
    return {"difficulty": difficulty, "last_block": last,
            "pending_transactions_hashes": ["%064x" % h for h in pending]}


def _counters():
    have = telemetry.counters()
    return [have.get(name, 0) for name in miner.ROLL_COUNTERS]


# ---- the choice, against the reference ----

@pytest.mark.parametrize("seed", range(12))
def test_the_choice_is_the_references_newest_fresh_second(rollref, seed):
    rng = random.Random(seed)
    for _ in range(200):
        prev_ts = rng.randrange(1 << 31)
        now = prev_ts + rng.randrange(-2, 40)
        window = range(prev_ts - 3, now + 3)
        swept = set(rng.sample(window, rng.randrange(len(window) + 1)))
        if rng.random() < 0.3:      # a window with nothing left
            swept |= set(range(prev_ts + 1, now + 1))
        want = rollref.newest_fresh(prev_ts, now, swept)
        second, repeat = miner.choose_timestamp(prev_ts, now, swept)
        if want is not None:
            assert (second, repeat) == (want, False)
            assert rollref.valid(prev_ts, second, now)
            assert second == now or now in swept       # now first
        else:
            # none left, or no window at all: the clock's second, as ever
            assert (second, repeat) == (now, now in swept)


@pytest.mark.parametrize("v1", [False, True], ids=["v2", "v1"])
def test_a_job_rolls_back_through_the_window_then_repeats(
        rollref, frozen_clock, v1):
    """Four jobs inside one second of a three-second window: now, the
    two seconds behind it, then now again, said and counted; the fifth,
    a second later, is fresh.  A v1 (64-byte address) header rolls the
    same way."""
    address = _address(v1)
    now = clock.timestamp()
    roll, before, got = miner.HeaderRoll(), _counters(), []
    for step in range(5):
        if step == 4:
            clock.advance(1)
        job, _hashes, _no, stamp = miner.build_job(
            _info(now - 3), address, roll)
        assert len(job.prefix) == (134 if v1 else 104)
        assert job.prefix[-6:-2] == stamp.timestamp.to_bytes(4, "little")
        got.append(tuple(stamp))
    assert got == [(now, 0, 3, 0), (now - 1, 1, 3, 0), (now - 2, 2, 3, 0),
                   (now, 0, 3, 1), (now + 1, 0, 4, 0)]
    for ts, behind, window, repeat in got:
        assert rollref.valid(ts + behind - window, ts, ts + behind)
    jobs = [{"previous_hash": "tip", "merkle_root": "m", "address": address,
             "difficulty": 9.0, "timestamp": s[0], "range": (0, 1 << 32)}
            for s in got]
    assert rollref.repeats(jobs) == [3]
    assert [a - b for a, b in zip(_counters(), before)] == [2, 2, 1]


def test_a_miner_slower_than_the_clock_stamps_now_byte_for_byte(
        frozen_clock):
    """Sweeps of over a second: every job carries the clock's second,
    and its header is the one ``from_header_fields`` builds from it."""
    roll, address = miner.HeaderRoll(), _address()
    for _ in range(6):
        clock.advance(2)
        job, hashes, _no, stamp = miner.build_job(
            _info(clock.timestamp() - 60), address, roll)
        assert (stamp.behind_s, stamp.window_s, stamp.repeat) == (0, 60, 0)
        assert job.prefix == MiningJob.from_header_fields(
            "%064x" % 0xFEED, address, miner.miner_merkle_root(hashes),
            clock.timestamp(), 9.0).prefix
    assert len(roll.swept) == 6


@pytest.mark.parametrize("change", [
    {"tip": 0xBEEF}, {"pending": (5, 6, 7)}, {"difficulty": 9.1},
    {"address": True}], ids=lambda c: next(iter(c)))
def test_the_swept_seconds_are_dropped_with_the_key(frozen_clock, change):
    """A new tip, merkle root, difficulty or address is a new header
    whatever the second: ``now`` is fresh again."""
    now, roll = clock.timestamp(), miner.HeaderRoll()
    for _ in range(3):
        miner.build_job(_info(now - 1), _address(), roll)
    assert roll.swept == {now}
    address = _address(v1=change.pop("address", False))
    *_job, stamp = miner.build_job(_info(now - 1, **change), address, roll)
    assert tuple(stamp) == (now, 0, 1, 0)
    assert roll.swept == {now} and roll.key[3] == address
    # and the old key, met again, starts over too
    *_job, stamp = miner.build_job(_info(now - 1), _address(), roll)
    assert stamp.repeat == 0


@pytest.mark.parametrize("jobs_a_second", [1, 2, 5])
def test_the_swept_set_is_bounded_by_the_window(rollref, frozen_clock,
                                                jobs_a_second):
    """300 s of one tip that was 20 s old at the start: every stamp is
    inside the node's rule, none repeats while a fresh second exists,
    and the set never outgrows the window."""
    roll, address = miner.HeaderRoll(), _address()
    prev_ts, stamps = clock.timestamp() - 20, []
    for _second in range(300):
        for _ in range(jobs_a_second):
            *_job, stamp = miner.build_job(_info(prev_ts), address, roll)
            now = clock.timestamp()
            assert rollref.valid(prev_ts, stamp.timestamp, now)
            assert len(roll.swept) <= stamp.window_s == now - prev_ts
            stamps.append(stamp)
        clock.advance(1)
    fresh = [s.timestamp for s in stamps if not s.repeat]
    assert len(fresh) == len(set(fresh))
    # repeats begin only once every second of the window is spent
    spent = next((i for i, s in enumerate(stamps) if s.repeat), None)
    if jobs_a_second == 1:
        assert spent is None
    else:
        first = stamps[spent]
        assert len(set(fresh[:spent])) == first.window_s
        assert spent == pytest.approx(20 * jobs_a_second
                                      / (jobs_a_second - 1), abs=jobs_a_second)


def test_a_node_whose_clock_runs_ahead_gets_now_as_ever(frozen_clock):
    """No second satisfies the rule: the miner stamps ``now``, which is
    what it did before it could roll, and says the window it saw."""
    now, roll = clock.timestamp(), miner.HeaderRoll()
    got = [tuple(miner.build_job(_info(now + 5), _address(), roll)[3])
           for _ in range(2)]
    assert got == [(now, 0, -5, 0), (now, 0, -5, 1)]


# ---- the loop ----

def _run_jobs(monkeypatch, capsys, serve, n_jobs, address=None):
    """``miner.run`` on the jnp engine, one 4,096-nonce round a job (the
    TTL ends it), against a scripted node; ``serve(k)`` is the k-th
    ``get_mining_info``.  Returns (header lines, the jobs' prefixes)."""
    calls, prefixes = [], []

    def fetch(_node):
        if len(calls) == n_jobs:
            raise KeyboardInterrupt
        calls.append(1)
        return serve(len(calls) - 1)

    real_mine = miner.mine

    def mine(job, backend, **kw):
        prefixes.append(job.prefix)
        return real_mine(job, backend, **kw)

    monkeypatch.setattr(miner, "fetch_mining_info", fetch)
    monkeypatch.setattr(miner, "mine", mine)
    # its thread would outlive the loop and take the test process down
    monkeypatch.setattr(miner, "_start_hang_watchdog", lambda *a, **k: None)
    with pytest.raises(KeyboardInterrupt):
        miner.run(address or _address(), "http://x/", "jnp", 4096, 0.0)
    out = capsys.readouterr().out.splitlines()
    lines = [HEADER.fullmatch(text) for text in out
             if text.startswith("header: ")]
    assert all(lines) and len(lines) == n_jobs
    # each right after its job's difficulty: line
    assert all(out[i - 1].startswith("difficulty: ") for i, text in
               enumerate(out) if text.startswith("header: "))
    return [tuple(int(g) for g in m.groups()) for m in lines], prefixes


def test_a_fast_miner_hashes_no_header_twice_while_the_window_has_room(
        rollref, frozen_clock, monkeypatch, capsys):
    """Twelve sweeps inside two seconds of the header's clock, a tip
    that was 8 s old: twelve distinct headers, each inside the rule;
    then the window is spent and the miner says so and counts it."""
    now = clock.timestamp()

    def serve(k):
        if k == 6:
            clock.advance(1)
        return _info(now - 8)

    before = _counters()
    jobs_before = telemetry.counters().get("mine.jobs", 0)
    lines, prefixes = _run_jobs(monkeypatch, capsys, serve, 12)
    assert len(set(prefixes)) == 9 and len(set(prefixes[:9])) == 9
    assert [ln[3] for ln in lines] == [0] * 9 + [1] * 3
    assert [ln[0] for ln in lines[:9]] == \
        [now - k for k in range(6)] + [now + 1, now - 6, now - 7]
    for (ts, behind, window, _r), prefix in zip(lines, prefixes):
        assert prefix[-6:-2] == ts.to_bytes(4, "little")
        assert rollref.valid(now - 8, ts, ts + behind)
        assert ts + behind - window == now - 8
    identity = [{"previous_hash": p[1:33], "address": p[33:66],
                 "merkle_root": p[66:98], "timestamp": p[98:102],
                 "difficulty": p[102:104], "range": (0, 1 << 32)}
                for p in prefixes]
    assert rollref.repeats(identity) == [9, 10, 11]
    grew = [a - b for a, b in zip(_counters(), before)]
    assert grew == [2, 7, 3]
    assert sum(grew) == telemetry.counters()["mine.jobs"] - jobs_before == 12
    # the spans: the choice inside the build, its fields on build and root
    job = telemetry.traces()["recent"][-2]
    built = next(c for c in job["spans"] if c["name"] == "mine.build_job")
    want = dict(zip(("timestamp", "behind_s", "window_s", "repeat"),
                    lines[-1]))
    assert {k: built["fields"][k] for k in want} == want
    assert {k: job["fields"][k] for k in want} == want
    assert telemetry.stats()["mine.roll"]["count"] >= 12


def test_a_dropped_jobs_second_is_spent_and_costs_nothing_else(
        rollref, frozen_clock, monkeypatch, capsys):
    """ISSUE 44: the second job finds a block in its last round, the
    third, stamped a second further back, already issued behind it: that
    one is dropped.  The node refuses the block, so the tip stands and
    the job after the push is built on the same key: it takes the next
    fresh second, not the dropped job's, which stays swept.  Nothing
    repeats, every timestamp is inside the node's rule, and the three
    counters add up to the jobs and the dropped one."""
    import miner_seams

    now = clock.timestamp()
    device = miner_seams.FakeDevice(monkeypatch, hits={(1, 12288): 99})
    names = ("mine.jobs", "mine.jobs_dropped")
    before, rolled = miner_seams.counters(*names), _counters()
    out, _ = miner_seams.run_jobs(
        monkeypatch, capsys, 5, push=lambda *a: {"ok": False},
        serve=lambda k: miner_seams.info(prev_ts=now - 8))
    lines = [tuple(int(g) for g in m.groups())
             for m in map(HEADER.fullmatch, out) if m]
    # the jobs that became the job in hand: the third built is not one
    assert [ts for ts, *_rest in lines] == [now, now - 1, now - 3, now - 4]
    assert all(repeat == 0 for *_rest, repeat in lines)
    stamped = [int.from_bytes(job.prefix[-6:-2], "little")
               for job in device.jobs]
    assert stamped == [now, now - 1, now - 2, now - 3, now - 4]
    assert all(rollref.valid(now - 8, ts, now) for ts in stamped)
    assert len({job.prefix for job in device.jobs}) == 5
    assert device.rounds("wait", 2) == []           # the dropped one
    assert miner_seams.grew(before) == {"mine.jobs": 4,
                                        "mine.jobs_dropped": 1}
    assert sum(_counters()) - sum(rolled) == 5


def test_a_one_second_window_is_todays_miner(frozen_clock, monkeypatch,
                                              capsys):
    """What the benchmark's older stub serves, ``int(now) - 1``: every
    job is stamped ``now``; the second and third inside one second
    repeat, and are counted."""
    def serve(k):
        if k == 3:
            clock.advance(1)
        return _info(clock.timestamp() - 1)

    now = clock.timestamp()
    lines, prefixes = _run_jobs(monkeypatch, capsys, serve, 5)
    assert lines == [(now, 0, 1, 0), (now, 0, 1, 1), (now, 0, 1, 1),
                     (now + 1, 0, 1, 0), (now + 1, 0, 1, 1)]
    assert len(set(prefixes)) == 2


def test_the_three_counters_are_exported_at_zero_from_the_first_scrape(
        monkeypatch, capsys):
    def fetch(_node):
        raise KeyboardInterrupt     # before any job is built

    monkeypatch.setattr(miner, "fetch_mining_info", fetch)
    with scope.activate(scope.TelemetryScope("roll")):
        assert not set(miner.ROLL_COUNTERS) & set(telemetry.counters())
        with pytest.raises(KeyboardInterrupt):
            miner.run("addr", "http://x/", "python", 64, 1.0, once=True)
        have = telemetry.counters()
        assert [have[name] for name in miner.ROLL_COUNTERS] == [0, 0, 0]
        # and the exit line says the three counts
        miner._print_exit_lines()
        said = json.loads(capsys.readouterr().out.splitlines()[-1]
                          [len("telemetry: "):])["counters"]
        assert [said[name] for name in miner.ROLL_COUNTERS] == [0, 0, 0]
