"""Mining engine tests: every backend finds the same nonce; TTL/sharding."""

import hashlib
import random

import pytest

from upow_tpu import native
from upow_tpu.mine.engine import MiningJob, mine
from upow_tpu.mine.miner import build_job

rng = random.Random(4242)


def _job(difficulty="1") -> MiningJob:
    from upow_tpu.core import curve, point_to_string

    prev = bytes(rng.randrange(256) for _ in range(32)).hex()
    _, pub = curve.keygen(rng=rng.randrange(1, 1 << 200))
    addr = point_to_string(pub)
    return MiningJob.from_header_fields(
        previous_hash=prev,
        address=addr,
        merkle_root=hashlib.sha256(b"").hexdigest(),
        timestamp=1_753_791_000,
        difficulty=difficulty,
    )


backends = ["jnp", "python"] + (["native"] if native.load() is not None else [])


@pytest.mark.parametrize("backend", backends)
def test_backends_agree_on_first_hit(backend):
    job = _job("1")
    result = mine(job, backend, batch=4096, stride_end=1 << 16)
    ref = mine(job, "python", batch=4096, stride_end=1 << 16)
    assert result.nonce == ref.nonce
    assert job.check(result.nonce)


def test_mine_respects_ttl_and_range():
    job = _job("9")  # unhittable in a tiny window
    result = mine(job, "python", batch=256, stride_end=512, ttl=30)
    assert result.nonce is None
    assert result.hashes_tried == 512


def _counters(*names):
    from upow_tpu import telemetry

    seen = telemetry.counters()
    return {n: seen.get(n, 0) for n in names}


_RAGGED = ("mine.rounds", "mine.nonces", "mine.rounds_masked",
           "mine.lanes_masked", "kernel.sha256_search.compile_cache_misses",
           "kernel.sha256_search.lanes_real",
           "kernel.sha256_search.lanes_padded")


def test_a_range_of_no_whole_rounds_is_one_program_and_one_masked_round():
    """The CLI's default range in small: three rounds of ``batch`` and
    one of 100.  The short round runs the job's one program, its surplus
    lanes masked; what is counted as tried is the live lanes."""
    from upow_tpu.crypto import sha256

    batch, length = 512, 3 * 512 + 100
    before = _counters(*_RAGGED)
    programs = sha256._pow_search_jnp._cache_size()
    result = mine(_job("9"), "jnp", batch=batch, start=7 * batch,
                  stride_end=7 * batch + length)
    grew = {n: v - before[n] for n, v in _counters(*_RAGGED).items()}
    assert result.nonce is None
    assert result.hashes_tried == length
    assert grew["mine.rounds"] == 4                  # ceil(length / batch)
    assert grew["mine.nonces"] == length
    assert grew["mine.rounds_masked"] == 1
    assert grew["mine.lanes_masked"] == batch - 100
    # one compile key a job, and one traced program for all four rounds
    assert grew["kernel.sha256_search.compile_cache_misses"] == 1
    assert sha256._pow_search_jnp._cache_size() - programs == 1
    # the padded lanes are told as the verify path tells them: the job's
    # key (batch of batch) and the masked round (100 of batch)
    assert grew["kernel.sha256_search.lanes_real"] == batch + 100
    assert grew["kernel.sha256_search.lanes_padded"] == 2 * batch


@pytest.mark.parametrize("prefix_len,rounds,words", [
    (104, 10, 4),   # v2: the nonce is w10
    (134, 1, 0),    # v1: the nonce starts in w1
])
def test_hoist_counters_say_what_the_host_finished_for_the_job(
        prefix_len, rounds, words):
    """``kernel.sha256_search.rounds_hoisted`` / ``.words_hoisted`` grow
    once a job by what the header's nonce placement let the host finish
    of the 64 rounds and 48 schedule words, however many rounds the job
    runs; the job's answer is the plain loop's."""
    from decimal import Decimal

    names = ("kernel.sha256_search.rounds_hoisted",
             "kernel.sha256_search.words_hoisted", "mine.rounds")
    job = MiningJob(bytes(rng.randrange(256) for _ in range(prefix_len)),
                    bytes(rng.randrange(256) for _ in range(32)).hex(),
                    Decimal("2"))
    first = next(n for n in range(1 << 14) if job.check(n))
    before = _counters(*names)
    result = mine(job, "jnp", batch=64, stride_end=1 << 14)
    grew = {n: v - before[n] for n, v in _counters(*names).items()}
    assert result.nonce == first
    assert grew["mine.rounds"] >= first // 64 + 1
    assert grew["kernel.sha256_search.rounds_hoisted"] == rounds
    assert grew["kernel.sha256_search.words_hoisted"] == words


def test_a_hit_in_the_short_last_round_is_found():
    """A job whose only hit lies in its masked round, and the same range
    cut just below that hit, which then ends with none."""
    job, batch = _job("2"), 64
    hits = [n for n in range(1 << 14) if job.check(n)]
    # a hit with three whole rounds and more of misses below it
    hit = next(h for below, h in zip(hits, hits[1:])
               if h - below > 3 * batch + 50)
    start = hit - (3 * batch + 50)
    before = _counters("mine.rounds_masked")
    found = mine(job, "jnp", batch=batch, start=start, stride_end=hit + 10)
    assert found.nonce == hit
    assert found.hashes_tried == 3 * batch + 60      # the whole short round
    missed = mine(job, "jnp", batch=batch, start=start, stride_end=hit)
    assert missed.nonce is None
    assert missed.hashes_tried == 3 * batch + 50
    assert _counters("mine.rounds_masked")["mine.rounds_masked"] \
        - before["mine.rounds_masked"] == 2


def test_the_top_of_the_nonce_space_leaves_the_sentinel_out():
    """[2^32 - 3*batch - 1, 2^32): the cap at MAX_SEARCH_END takes the
    sentinel off the end, so the job is three whole rounds, none masked,
    with bases above 2^31 and lanes up to 2^32 - 2."""
    from upow_tpu.mine.engine import NONCE_SPACE

    batch = 512
    before = _counters("mine.rounds", "mine.rounds_masked")
    result = mine(_job("9"), "jnp", batch=batch,
                  start=NONCE_SPACE - 3 * batch - 1, stride_end=NONCE_SPACE)
    grew = {n: v - before[n] for n, v in
            _counters("mine.rounds", "mine.rounds_masked").items()}
    assert result.nonce is None
    assert result.hashes_tried == 3 * batch
    assert grew == {"mine.rounds": 3, "mine.rounds_masked": 0}


def test_shard_ranges_partition_nonce_space():
    from upow_tpu.mine.engine import NONCE_SPACE

    k = 8
    bounds = [(NONCE_SPACE * i // k, NONCE_SPACE * (i + 1) // k) for i in range(k)]
    assert bounds[0][0] == 0 and bounds[-1][1] == NONCE_SPACE
    for (a, b), (c, d) in zip(bounds, bounds[1:]):
        assert b == c


def test_build_job_defaults_genesis():
    from upow_tpu.core import curve, point_to_string

    _, pub = curve.keygen(rng=12345)
    info = {
        "difficulty": 6.0,
        "last_block": {},
        "pending_transactions": [],
        "pending_transactions_hashes": [],
        "merkle_root": hashlib.sha256(b"").hexdigest(),
    }
    job, hashes, block_no, stamp = build_job(info, point_to_string(pub))
    assert block_no == 1
    assert hashes == []
    assert job.previous_hash == (18_884_643).to_bytes(32, "little").hex()
    # no previous timestamp served: one second, the clock's
    assert (stamp.behind_s, stamp.window_s, stamp.repeat) == (0, 1, 0)
    assert job.prefix[-6:-2] == stamp.timestamp.to_bytes(4, "little")


def test_fetch_mining_info_unwraps_node_errors(monkeypatch):
    """A node error envelope (syncing, rate-limited) surfaces readably,
    not as KeyError('result')."""
    from upow_tpu.mine import miner as miner_mod

    monkeypatch.setattr(miner_mod, "_http_json",
                        lambda url, **kw: {"ok": False,
                                           "error": "Node is already syncing"})
    with pytest.raises(RuntimeError, match="syncing"):
        miner_mod.fetch_mining_info("http://x/")
    monkeypatch.setattr(miner_mod, "_http_json",
                        lambda url, **kw: {"ok": True, "result": {"a": 1}})
    assert miner_mod.fetch_mining_info("http://x/") == {"a": 1}


def test_hang_watchdog_trips_on_stale_heartbeat():
    """A dispatch on a lost device hangs forever; the watchdog must fire once
    the heartbeat goes stale, and not before while it is refreshed."""
    import threading
    import time as _time

    from upow_tpu.mine import miner

    fired = threading.Event()
    hb = {"t": _time.monotonic()}
    miner._start_hang_watchdog(hb, limit=1.2, _exit=lambda code: fired.set())
    # keep the heartbeat fresh: no trip
    for _ in range(4):
        _time.sleep(0.4)
        hb["t"] = _time.monotonic()
    assert not fired.is_set()
    # go stale: trips within ~limit + poll interval
    assert fired.wait(timeout=4.0)


def test_supervisor_respawns_hung_child(tmp_path):
    """End-to-end: a miner child whose backend hangs must be killed by the
    watchdog with the respawn exit code (3), promptly."""
    import os
    import subprocess
    import sys as _sys
    import textwrap

    import upow_tpu

    repo = os.path.dirname(os.path.dirname(upow_tpu.__file__))
    stub = tmp_path / "hang_miner.py"
    stub.write_text(textwrap.dedent(f"""
        import sys, time
        sys.path.insert(0, {repo!r})
        import upow_tpu.mine.miner as miner

        def fake_fetch(node):
            return {{"difficulty": "1.0"}}

        def fake_build(info, address, roll):
            return object(), [], 1, miner.Stamp(0, 0, 1, 0)

        def hang(job, backend, **kw):
            time.sleep(600)

        miner.fetch_mining_info = fake_fetch
        miner.build_job = fake_build
        miner.mine = hang
        miner.run("addr", "http://x/", "jnp", 0, ttl=0.5, hang_grace=1.0,
                  first_round_grace=0.0)
    """))
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    env["JAX_PLATFORMS"] = "cpu"
    t0 = __import__("time").monotonic()
    proc = subprocess.run([_sys.executable, str(stub)], env=env, timeout=60,
                          capture_output=True, text=True)
    assert proc.returncode == 3
    assert "no mining progress" in proc.stderr
    assert __import__("time").monotonic() - t0 < 30


# ---- the seam: the round pipeline runs across jobs (ISSUE 44), as deep
# as the queue's lead in seconds asks (ISSUE 47) ----

def _seam_jobs(n=2):
    return [_job("9") for _ in range(n)]


def _issues(number, *starts):
    return [("issue", number, start) for start in starts]


def _waits(number, *starts):
    return [("wait", number, start) for start in starts]


@pytest.mark.parametrize("depth", [2, 4])
@pytest.mark.parametrize("not_yet", [0, 1, 2])
def test_the_next_jobs_first_rounds_go_behind_the_last_of_the_job_in_hand(
        monkeypatch, not_yet, depth):
    """Four rounds a job, ``depth`` in flight.  Once the job in hand has
    issued its last and while rounds are in flight, ``next_job`` is asked
    before each wait (``not_yet``: how often it says None first; an ask
    with nothing in flight is never made); from where it answers, every
    answer of the job in hand read makes room for one round of the next
    job, issued before the answers still out are read.  The bound is one
    over both jobs.  The sweep handed back as ``ahead`` goes on where it
    was."""
    import miner_seams
    from upow_tpu.mine import engine
    from upow_tpu.mine.engine import Sweep

    device = miner_seams.FakeDevice(monkeypatch, depth=depth)
    jobs, asked, made = _seam_jobs(), [], []
    before = miner_seams.counters(*miner_seams.ROUNDS)

    def next_job():
        asked.append(len(device.log))
        if len(asked) <= not_yet:
            return None
        made.append(Sweep(jobs[1], "jnp", batch=64, stride_end=256))
        return made[-1]

    first = mine(jobs[0], "jnp", batch=64, stride_end=256, next_job=next_job)
    assert first.nonce is None and first.hashes_tried == 256
    assert engine.rounds_in_flight() == depth >= engine.MIN_ROUNDS_IN_FLIGHT
    in_hand, last = {
        2: (_issues(0, 0, 64) + _waits(0, 0) + _issues(0, 128)
            + _waits(0, 64) + _issues(0, 192), _waits(0, 128, 192)),
        4: (_issues(0, 0, 64, 128, 192), _waits(0, 0, 64, 128, 192)),
    }[depth]
    # the k-th of the last answers read leaves room for k rounds of the
    # next job in all, once that job is there (after ``not_yet`` of them)
    expected, ahead = list(in_hand), 0
    for k, wait in enumerate(last, 1):
        expected.append(wait)
        if depth > not_yet <= k:
            expected += _issues(1, *range(64 * ahead, 64 * k, 64))
            ahead = k
    assert device.log == expected
    assert device.log[len(in_hand):] == {
        (2, 0): _waits(0, 128) + _issues(1, 0) + _waits(0, 192)
        + _issues(1, 64),
        (2, 2): _waits(0, 128, 192),
        (4, 2): _waits(0, 0, 64) + _issues(1, 0, 64) + _waits(0, 128)
        + _issues(1, 128) + _waits(0, 192) + _issues(1, 192),
    }.get((depth, not_yet), expected[len(in_hand):])
    assert len(asked) == min(not_yet + 1, depth)
    assert len(made) == (not_yet < depth)
    # issues - waits <= depth at every step, across the seam too (the
    # benchmark's traced runs allow the lines and the device four rounds
    # of edge)
    assert device.most_in_flight() == depth
    # only what was answered is counted: the rounds ahead are in flight
    assert miner_seams.grew(before) == {"mine.rounds": 4, "mine.nonces": 256}
    if made:
        assert [c for _h, c in made[0].inflight] == [64] * depth
        second = mine(jobs[1], "jnp", ahead=made[0])
        assert second.nonce is None and second.hashes_tried == 256
        assert device.rounds("issue", 1) == device.rounds("wait", 1) \
            == [0, 64, 128, 192]
        assert len(device.jobs) == 2      # prepared once, by its Sweep
        assert device.most_in_flight() == depth


@pytest.mark.parametrize("depth", [2, 4])
def test_the_queue_is_filled_before_a_round_is_said(monkeypatch, depth):
    """An answer read, the next round is issued before ``progress``
    hears of it: what the loop says about a round lies behind the
    device's queue, not before it.  The sweep a ``ttl`` cuts issues no
    round after the cut."""
    import miner_seams

    device = miner_seams.FakeDevice(monkeypatch, depth=depth)

    def progress(tried, _elapsed):
        device.log.append(("said", 0, tried))

    mine(_job("9"), "jnp", batch=64, stride_end=384, progress=progress)
    assert device.log == {
        2: [("issue", 0, 0), ("issue", 0, 64),
            ("wait", 0, 0), ("issue", 0, 128), ("said", 0, 64),
            ("wait", 0, 64), ("issue", 0, 192), ("said", 0, 128),
            ("wait", 0, 128), ("issue", 0, 256), ("said", 0, 192),
            ("wait", 0, 192), ("issue", 0, 320), ("said", 0, 256),
            ("wait", 0, 256), ("said", 0, 320),
            ("wait", 0, 320), ("said", 0, 384)],
        4: [("issue", 0, 0), ("issue", 0, 64), ("issue", 0, 128),
            ("issue", 0, 192),
            ("wait", 0, 0), ("issue", 0, 256), ("said", 0, 64),
            ("wait", 0, 64), ("issue", 0, 320), ("said", 0, 128),
            ("wait", 0, 128), ("said", 0, 192),
            ("wait", 0, 192), ("said", 0, 256),
            ("wait", 0, 256), ("said", 0, 320),
            ("wait", 0, 320), ("said", 0, 384)]}[depth]
    del device.log[:]
    mine(_job("9"), "jnp", batch=64, stride_end=384, ttl=0.0,
         progress=progress)
    assert device.log == _issues(1, *range(0, 64 * depth, 64)) \
        + [("wait", 1, 0), ("said", 0, 64)]


@pytest.mark.parametrize("round_ms, depth", [
    (1.86, 4),      # the pod: 5.6 ms queued behind the round that runs
    (7.2, 2),       # one chip: a round outlasts the lead by itself
    (0.9, 4),       # the cap: nothing deeper was measured
    (3.0, 3), (2.5, 3), (2.49, 4), (5.0, 2), (4.99, 3)])
def test_the_depth_is_the_fewest_rounds_that_queue_the_lead(round_ms, depth):
    from upow_tpu.mine import engine

    round_s = round_ms / 1e3
    assert engine.depth_for(round_s) == depth
    assert engine.MIN_ROUNDS_IN_FLIGHT <= depth <= engine.MAX_ROUNDS_IN_FLIGHT
    if depth > engine.MIN_ROUNDS_IN_FLIGHT:
        assert (depth - 2) * round_s < engine.QUEUE_LEAD_S
    if depth < engine.MAX_ROUNDS_IN_FLIGHT:
        assert (depth - 1) * round_s >= engine.QUEUE_LEAD_S


def test_nothing_timed_yet_the_engine_keeps_the_fewest_rounds_in_flight():
    """A fresh process (the module as imported): no answer has been
    timed, so the first job begins at the range's floor."""
    import importlib.util

    from upow_tpu.mine import engine

    spec = importlib.util.find_spec("upow_tpu.mine.engine")
    fresh = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fresh)
    assert fresh.rounds_in_flight() == fresh.MIN_ROUNDS_IN_FLIGHT == 2
    assert engine.MIN_ROUNDS_IN_FLIGHT == 2 <= engine.MAX_ROUNDS_IN_FLIGHT


@pytest.mark.parametrize("periods_ms, depths", [
    # the pod: the second answer gives the first period
    ([1.86] * 8, [2] + [4] * 8),
    # one chip
    ([7.2] * 4, [2] * 5),
    # a stall of 50 ms is in the job's mean for as long as it outweighs
    # the lead (the shallower queue for as long), then falls out of it
    ([1.86, 1.86, 50.0] + [1.86] * 80,
     [2, 4, 4] + [2] * 13 + [3] * 60 + [4] * 8),
])
def test_the_depth_follows_the_mean_period_of_the_jobs_own_answers(
        monkeypatch, periods_ms, depths):
    """One job's answers at scripted times: the depth after each is
    :func:`depth_for` of the mean period since the job's first answer
    (one answer is no period: the depth stays where it was)."""
    import miner_seams
    from upow_tpu.mine import engine

    device = miner_seams.FakeDevice(monkeypatch, depth=None)
    clock = [100.0]
    monkeypatch.setattr(engine.time, "monotonic", lambda: clock[0])
    steps = iter([0.0] + [ms / 1e3 for ms in periods_ms])
    seen = []

    def on_wait(_number, _start):
        clock[0] += next(steps)

    device.on_wait = on_wait
    mine(_job("9"), "jnp", batch=64, stride_end=64 * len(depths),
         progress=lambda *_a: seen.append(engine.rounds_in_flight()))
    assert seen == depths
    assert device.most_in_flight() == max(depths)


@pytest.mark.parametrize("round_s, depth", [(0.0, 4), (0.008, 2)])
def test_a_jobs_first_rounds_go_out_at_the_depth_the_last_job_ended_on(
        monkeypatch, round_s, depth):
    """The engine's own rule on a device whose rounds take ``round_s``:
    the process's first job begins two deep; short rounds deepen the
    queue inside it, and the next job begins where it ended."""
    import miner_seams
    from upow_tpu.mine import engine

    device = miner_seams.FakeDevice(monkeypatch, round_s=round_s, depth=None)
    jobs = _seam_jobs()
    mine(jobs[0], "jnp", batch=64, stride_end=512)
    assert device.log[:3] == _issues(0, 0, 64) + _waits(0, 0)
    assert engine.rounds_in_flight() == depth == device.most_in_flight()
    del device.log[:]
    mine(jobs[1], "jnp", batch=64, stride_end=512)
    assert device.log[:depth + 1] == \
        _issues(1, *range(0, 64 * depth, 64)) + _waits(1, 0)
    assert device.most_in_flight() == depth


def test_a_hit_in_the_oldest_of_four_rounds_returns_at_once(monkeypatch,
                                                            capsys):
    """Four in flight, the oldest two the job in hand's last and the
    newest two the next job's first (the feed's template came a round
    into the job, so the seam began at its second answer): the hit
    returns without another answer read or round issued, only what was
    answered is counted, the job issued behind it is dropped, and the job
    after the push starts with nothing in flight."""
    import miner_seams

    batch = miner_seams.RANGE // 4
    device = miner_seams.FakeDevice(monkeypatch, depth=4,
                                    hits={(1, 2 * batch): 12345})
    pushes = []

    def push(_node, content, txs, block_no):
        pushes.append((content, list(device.log)))
        return {"ok": True}

    names = miner_seams.SEAMS + miner_seams.ROUNDS + ("mine.jobs_found",)
    before = miner_seams.counters(*names)
    miner_seams.run_jobs(monkeypatch, capsys, 4, push=push)
    at = device.log.index(("wait", 1, 2 * batch))
    assert device.log[at - 3:at + 2] == _waits(1, batch) \
        + _issues(2, 0, batch) + _waits(1, 2 * batch) + _issues(3, 0)
    assert device.rounds("wait", 1) == [0, batch, 2 * batch]
    assert device.rounds("issue", 1) == [0, batch, 2 * batch, 3 * batch]
    assert device.rounds("issue", 2) == [0, batch]
    assert device.rounds("wait", 2) == []
    assert device.rounds("wait", 3) == [0, batch, 2 * batch, 3 * batch]
    assert len(pushes) == 1
    assert pushes[0][0] == device.jobs[1].block_content(12345)
    assert pushes[0][1] == device.log[:at + 1]      # pushed at once
    assert miner_seams.grew(before) == {
        "mine.jobs": 3, "mine.jobs_overlapped": 1, "mine.jobs_drained": 1,
        "mine.jobs_dropped": 1, "mine.jobs_found": 1,
        "mine.rounds": 4 + 3 + 4, "mine.nonces": 11 * batch}
    assert device.most_in_flight(upto=at + 1) == 4


def test_the_queues_counters_say_how_often_it_ran_low(monkeypatch):
    """``ahead``: the rounds the device has finished beyond the one whose
    answer was read, by the end of the fill.  Four in flight: three were
    out at the fill; with one of them unfinished at most the queue was
    low, with none the device had gone idle.  With one round out the
    queue is low by construction, and only ``mine.queue_empty`` counts."""
    import miner_seams

    device = miner_seams.FakeDevice(monkeypatch, depth=4)
    finished_beyond = {0: 0, 64: 1, 128: 2, 192: 3, 256: 4, 320: 1}

    def on_wait(_number, start):
        device.ahead = finished_beyond.get(start, 0)

    device.on_wait = on_wait
    names = miner_seams.QUEUE + miner_seams.ROUNDS
    before = miner_seams.counters(*names)
    mine(_job("9"), "jnp", batch=64, stride_end=64 * 9)
    # out at each fill: 3, 3, 3, 3, 3, 3 (the range's last issue), then
    # 2, 1 and 0 as the job drains with no job behind it
    assert miner_seams.grew(before) == {
        "mine.queue_low": 3, "mine.queue_empty": 2 + 1,
        "mine.rounds": 9, "mine.nonces": 64 * 9}
    # two in flight: one round out at every fill, the brink by
    # construction: only the idle device is counted
    device = miner_seams.FakeDevice(monkeypatch, depth=2)
    device.on_wait = on_wait
    before = miner_seams.counters(*names)
    mine(_job("9"), "jnp", batch=64, stride_end=64 * 9)
    assert miner_seams.grew(before) == {
        "mine.queue_low": 0, "mine.queue_empty": 5 + 1,
        "mine.rounds": 9, "mine.nonces": 64 * 9}


def test_the_queues_counters_are_exported_at_zero_from_the_first_scrape(
        monkeypatch, capsys):
    import json

    from upow_tpu import telemetry
    from upow_tpu.mine import engine, miner
    from upow_tpu.telemetry import scope

    def fetch(_node):
        raise KeyboardInterrupt     # before any job is built

    monkeypatch.setattr(miner, "fetch_mining_info", fetch)
    with scope.activate(scope.TelemetryScope("queue")):
        assert not set(engine.QUEUE_COUNTERS) & set(telemetry.counters())
        with pytest.raises(KeyboardInterrupt):
            miner.run("addr", "http://x/", "python", 64, 1.0, once=True)
        have = telemetry.counters()
        assert [have[name] for name in engine.QUEUE_COUNTERS] == [0, 0]
        miner._print_exit_lines()
        said = json.loads(capsys.readouterr().out.splitlines()[-1]
                          [len("telemetry: "):])["counters"]
        assert [said[name] for name in engine.QUEUE_COUNTERS] == [0, 0]


def test_a_job_the_ttl_cuts_and_a_host_backend_never_ask_for_the_next(
        monkeypatch):
    def next_job():
        raise AssertionError("asked with rounds still to issue, or none "
                             "in flight")

    cut = mine(_job("9"), "jnp", batch=64, stride_end=1 << 12, ttl=0.0,
               next_job=next_job)
    assert cut.nonce is None and cut.hashes_tried == 64
    host = mine(_job("9"), "python", batch=64, stride_end=256,
                next_job=next_job)
    assert host.nonce is None and host.hashes_tried == 256


def test_a_hit_in_the_last_round_drops_the_job_issued_ahead(monkeypatch,
                                                            capsys):
    """The job in hand finds a block in its last round, the next job's
    first two rounds already behind it: those are never read, counted
    in nothing and followed by none, the block is pushed, and the job
    after it starts from a template fetched after the push, with
    nothing in flight."""
    import miner_seams

    device = miner_seams.FakeDevice(monkeypatch, hits={(1, 12288): 12345})
    pushes = []

    def push(_node, content, txs, block_no):
        pushes.append(content)
        return {"ok": True}

    names = miner_seams.SEAMS + miner_seams.ROUNDS + (
        "mine.jobs_found", "mine.jobs_expired")
    before = miner_seams.counters(*names)
    older = {t["trace_id"] for t in telemetry_traces()}
    out, _ = miner_seams.run_jobs(monkeypatch, capsys, 4, push=push)
    # jobs 0 and 1 are the miner's first two; 2 was issued ahead and
    # dropped; 3 is the job after the push
    assert device.rounds("issue", 2) == [0]     # two in flight, in all
    assert device.rounds("wait", 2) == []
    assert device.rounds("wait", 3) == [0, 4096, 8192, 12288]
    assert device.log.index(("issue", 2, 0)) \
        < device.log.index(("wait", 1, 12288)) \
        < device.log.index(("issue", 3, 0))
    assert miner_seams.grew(before) == {
        "mine.jobs": 3, "mine.jobs_overlapped": 1, "mine.jobs_drained": 1,
        "mine.jobs_dropped": 1, "mine.jobs_found": 1, "mine.jobs_expired": 2,
        "mine.rounds": 12, "mine.nonces": 3 * miner_seams.RANGE}
    assert len(pushes) == 1
    assert pushes[0] == device.jobs[1].block_content(12345)
    jobs = miner_seams.minerlog().jobs(
        miner_seams.minerlog().parse([(0.0, text) for text in out]))
    assert [(j["end"], j["tried"]) for j in jobs] == [
        ("expired", miner_seams.RANGE), ("found", miner_seams.RANGE),
        ("expired", miner_seams.RANGE)]
    dropped = [t for t in telemetry_traces() if t["fields"].get("end")
               == "dropped" and t["trace_id"] not in older]
    assert len(dropped) == 1
    assert [c["name"] for c in dropped[0]["spans"]] == [
        "mine.build_job", "mine.prepare", "mine.first_issue"]


def telemetry_traces():
    from upow_tpu import telemetry

    return [t for t in telemetry.traces()["recent"]
            if t["name"] == "mine.job"]


@pytest.mark.parametrize("how", ["ttl", "found", "once", "host"])
def test_a_seam_with_nothing_in_flight_is_counted_as_drained(
        monkeypatch, capsys, how):
    """A sweep the ``--ttl`` cut, a found block, ``--once`` and a host
    backend begin the next job when the job in hand has ended."""
    import miner_seams

    hits = {(n, 0): 7 for n in range(4)} if how == "found" else {}
    device = None if how == "host" else \
        miner_seams.FakeDevice(monkeypatch, hits=hits)
    before = miner_seams.counters(*miner_seams.SEAMS)
    n_jobs = 1 if how == "once" else 3
    out, ended = miner_seams.run_jobs(
        monkeypatch, capsys, n_jobs, ttl=0.0 if how == "ttl" else 90.0,
        once=how == "once", backend="python" if how == "host" else "jnp",
        rounds=64 if how == "host" else 4)
    assert ended == (1 if how == "once" else "done")
    assert miner_seams.grew(before) == {
        "mine.jobs": n_jobs, "mine.jobs_overlapped": 0,
        "mine.jobs_drained": n_jobs - 1, "mine.jobs_dropped": 0}
    if device is not None:
        # no round of a job is issued before the last read of the one
        # before it
        order = [n for _w, n, _s in device.log]
        assert order == sorted(order) and len(device.jobs) == n_jobs
    assert sum(text.startswith("header: ") for text in out) == n_jobs


@pytest.mark.parametrize("rounds", [4, 3])
def test_the_lines_of_overlapped_jobs_add_up_as_the_sequential_miners_do(
        monkeypatch, capsys, rounds):
    """The jnp engine, real rounds (``rounds`` = 3: three of 5,461 and a
    masked one of 1):
    the benchmark's parser gives every job the rounds and nonces it
    gives the jobs of a miner that never overlaps (``poll`` says None),
    each round but the last of ``batch``, reported = tried = the range,
    though all but the first were issued across a seam."""
    import miner_seams

    log = miner_seams.minerlog()
    shape = {}
    for overlap in (True, False):
        if not overlap:
            monkeypatch.setattr(miner_seams.InlineFeed, "poll",
                                lambda feed, have: None)
        before = miner_seams.counters(*miner_seams.SEAMS)
        out, _ = miner_seams.run_jobs(monkeypatch, capsys, 4, rounds=rounds)
        seams = miner_seams.grew(before)
        assert seams["mine.jobs_overlapped" if overlap
                     else "mine.jobs_drained"] == 3, seams
        jobs = log.jobs(log.parse([(0.0, text) for text in out]))
        shape[overlap] = [([n for _t, n in j["rounds"]], j["tried"],
                           j["reported"], j["end"]) for j in jobs]
        # each job's two lines stand together, after the end of the last
        starts = [k for k, text in enumerate(out)
                  if text.startswith("difficulty: ")]
        assert all(out[k + 1].startswith("header: ") for k in starts)
        assert all(out[k - 1].startswith("template expired")
                   for k in starts[1:])
    batch = miner_seams.RANGE // rounds
    whole, short = divmod(miner_seams.RANGE, batch)
    assert shape[True] == shape[False] == [
        ([batch] * whole + [short] * bool(short),
         miner_seams.RANGE, miner_seams.RANGE, "expired")] * 4
