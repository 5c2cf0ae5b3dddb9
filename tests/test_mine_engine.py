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
