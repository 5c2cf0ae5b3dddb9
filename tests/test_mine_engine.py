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


# ---- the seam: the round pipeline runs across jobs (ISSUE 44) ----

def _seam_jobs(n=2):
    return [_job("9") for _ in range(n)]


@pytest.mark.parametrize("not_yet", [0, 1, 2])
def test_the_next_jobs_first_rounds_go_behind_the_last_of_the_job_in_hand(
        monkeypatch, not_yet):
    """Four rounds a job.  Once the job in hand has issued its last and
    while rounds are in flight, ``next_job`` is asked before each wait;
    where it answers, the next job's first two rounds are issued before
    the answers still out are read (``not_yet``: how often it says None
    first; the third ask would come with nothing in flight and is never
    made).  The sweep handed back as ``ahead`` goes on where it was."""
    import miner_seams
    from upow_tpu.mine.engine import ROUNDS_IN_FLIGHT, Sweep

    device = miner_seams.FakeDevice(monkeypatch)
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
    in_hand = [("issue", 0, 0), ("issue", 0, 64), ("wait", 0, 0),
               ("issue", 0, 128), ("wait", 0, 64), ("issue", 0, 192)]
    ahead = [("issue", 1, 0), ("issue", 1, 64)]
    last = [("wait", 0, 128), ("wait", 0, 192)]
    assert ROUNDS_IN_FLIGHT == 2
    assert device.log == in_hand + last[:not_yet] \
        + (ahead if not_yet < 2 else []) + last[not_yet:]
    assert len(asked) == min(not_yet + 1, 2) and len(made) == (not_yet < 2)
    # never more than the two jobs' two each are out (the benchmark's
    # traced runs allow the lines and the device four rounds of edge)
    out = 0
    for what, _number, _start in device.log:
        out += 1 if what == "issue" else -1
        assert out <= 2 * ROUNDS_IN_FLIGHT
    # only what was answered is counted: the rounds ahead are in flight
    assert miner_seams.grew(before) == {"mine.rounds": 4, "mine.nonces": 256}
    if made:
        assert [c for _h, c in made[0].inflight] == [64, 64]
        second = mine(jobs[1], "jnp", ahead=made[0])
        assert second.nonce is None and second.hashes_tried == 256
        assert device.rounds("issue", 1) == device.rounds("wait", 1) \
            == [0, 64, 128, 192]
        assert len(device.jobs) == 2      # prepared once, by its Sweep


def test_the_queue_is_filled_before_a_round_is_said(monkeypatch):
    """An answer read, the next round is issued before ``progress``
    hears of it: what the loop says about a round lies behind the
    device's queue, not before it.  The sweep a ``ttl`` cuts issues no
    round after the cut."""
    import miner_seams

    device = miner_seams.FakeDevice(monkeypatch)

    def progress(tried, _elapsed):
        device.log.append(("said", 0, tried))

    mine(_job("9"), "jnp", batch=64, stride_end=256, progress=progress)
    assert device.log == [
        ("issue", 0, 0), ("issue", 0, 64),
        ("wait", 0, 0), ("issue", 0, 128), ("said", 0, 64),
        ("wait", 0, 64), ("issue", 0, 192), ("said", 0, 128),
        ("wait", 0, 128), ("said", 0, 192),
        ("wait", 0, 192), ("said", 0, 256)]
    del device.log[:]
    mine(_job("9"), "jnp", batch=64, stride_end=256, ttl=0.0,
         progress=progress)
    assert device.log == [("issue", 1, 0), ("issue", 1, 64),
                          ("wait", 1, 0), ("said", 0, 64)]


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
    assert device.rounds("issue", 2) == [0, 4096]
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
