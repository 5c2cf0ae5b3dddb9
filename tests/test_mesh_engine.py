"""Resident mesh-sharded nonce search (ISSUE 12, mine/mesh_engine.py).

Acceptance coverage on the virtual 8-device CPU mesh (conftest.py):
differential bit-identity over >= 3 seeded jobs vs the serial jnp path,
disjoint/exact per-round shard coverage straight from the engine's own
dispatch accounting, the no-recompile job swap (compile-cache counters
plus jax's jit cache size), single-dispatch-owner routing through the
device runtime under source "mine", and the structured arm ladder with
real exception text.  The resident program's TPU body (the Pallas kernel,
here in interpret mode) is held to the jnp body's answers, to the same
no-recompile swap, and to its ``body`` / ``mine.mesh.rounds_pallas``
accounting.  A job's three arrays are laid over the engine's mesh once a
job (ISSUE 31): committed, replicated, the sharding the arm's warm
dispatch compiled for, so the resident program's jit cache holds one
entry from the arm on and ``mine.mesh.job_layouts`` counts jobs, not
rounds.  ``--device tpu`` is this engine on a mesh of one device (ISSUE
39): what ``select_backend`` hands ``mine()``, the one program over jobs
of other tips and difficulties, the ragged last round under
``mine.round.tail``, and the top of the nonce space held to the
benchmark's plain reference ``benchmarks/harness/powref.py``.
"""

import importlib.util
import os
import random
import sys
import time

import jax
import pytest

from upow_tpu import telemetry
from upow_tpu.crypto import SENTINEL, make_template, pow_search_jnp, target_spec
from upow_tpu.mine import mesh_engine
from upow_tpu.mine.engine import MAX_SEARCH_END, NONCE_SPACE, MiningJob, mine
from upow_tpu.mine.mesh_engine import (MeshEngine, get_mesh_engine,
                                       reset_mesh_engine)
from upow_tpu.telemetry import metrics

rng = random.Random(0xA11CE)


def _rand_bytes(n):
    return bytes(rng.randrange(256) for _ in range(n))


def _seeded_job(seed: int, difficulty="1.5") -> MiningJob:
    r = random.Random(seed)
    prefix = bytes(r.randrange(256) for _ in range(104))
    prev_hash = bytes(r.randrange(256) for _ in range(32)).hex()
    from decimal import Decimal

    return MiningJob(prefix, prev_hash, Decimal(difficulty))


@pytest.fixture(autouse=True)
def clean_state():
    telemetry.reset()
    telemetry.configure()
    reset_mesh_engine()
    yield
    reset_mesh_engine()
    telemetry.reset()
    telemetry.configure()


def _armed_engine(batch_per_device=1024) -> MeshEngine:
    eng = get_mesh_engine(batch_per_device=batch_per_device)
    info = eng.arm()
    assert info["armed"], info
    assert eng.n_devices == 8  # the virtual CPU mesh
    return eng


def _jit_entries() -> int:
    """The process's compiled variants of the resident program."""
    from upow_tpu.parallel import mesh as pmesh

    return pmesh._pow_search_mesh_resident._cache_size()


def _arm_through_first_job(eng: MeshEngine) -> tuple:
    """Arm a fresh engine and run its first job's first round; the jit
    entries (before the arm, after it, after that round).  Nothing else
    dispatches in between, so the differences are this engine's."""
    before = _jit_entries()
    info = eng.arm()
    assert info["armed"], info
    armed = _jit_entries()
    eng.set_job(_seeded_job(1))
    int(eng.dispatch(0, eng.capacity))   # waited for: interpret mode is slow
    return before, armed, _jit_entries()


def _assert_laid_over_mesh(eng: MeshEngine) -> None:
    """Each of the job's three arrays is committed, fully replicated and
    on exactly the engine's mesh devices: what the resident program takes
    them as, so a round re-lays nothing."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    assert len(eng._job.arrays) == 3
    for a in eng._job.arrays:
        assert a.committed
        assert a.sharding.is_fully_replicated
        assert a.sharding == NamedSharding(eng._mesh, P())
        assert a.devices() == set(eng.mesh_devices())
        assert len(a.addressable_shards) == eng.n_devices


# ------------------------------------------------- differential identity ----

def test_differential_bit_identity_three_seeded_jobs():
    """>= 3 seeded jobs: every mesh round returns EXACTLY the serial
    path's min-hit for the same window — not merely "a" valid nonce."""
    eng = _armed_engine(batch_per_device=1024)
    total = eng.capacity  # 8 * 1024
    for seed in (101, 202, 303, 404):
        job = _seeded_job(seed)
        eng.set_job(job)
        _assert_laid_over_mesh(eng)
        template = make_template(job.prefix)
        spec = target_spec(job.previous_hash, job.difficulty)
        for start in (0, 1 << 20):
            got = int(eng.dispatch(start, total))
            want = int(pow_search_jnp(template, spec, nonce_base=start,
                                      batch=total))
            assert got == want, (seed, start)
            if got != int(SENTINEL):
                assert job.check(got)


def test_partial_and_tiny_rounds_match_serial():
    """Tail rounds (count < capacity, even count < n_devices) mask the
    idle lanes instead of scanning them — empty shards included."""
    eng = _armed_engine(batch_per_device=512)
    job = _seeded_job(7, difficulty="1")
    eng.set_job(job)
    template = make_template(job.prefix)
    spec = target_spec(job.previous_hash, job.difficulty)
    for start, count in ((0, 3), (1 << 16, 100), (5, eng.capacity - 1)):
        got = int(eng.dispatch(start, count))
        want = int(pow_search_jnp(template, spec, nonce_base=start,
                                  batch=count))
        assert got == want, (start, count)


def test_mine_mesh_backend_matches_jnp_backend():
    """The full mine() loop through backend='mesh' finds the same nonce
    as backend='jnp' with identical round boundaries."""
    job = _seeded_job(55, difficulty="1")
    kw = dict(start=0, stride_end=1 << 14, batch=1 << 12, ttl=60.0)
    want = mine(job, backend="jnp", **kw)
    got = mine(job, backend="mesh", **kw)
    assert got.nonce == want.nonce
    assert got.hashes_tried == want.hashes_tried


# ------------------------------------------------- the Pallas body ----

@pytest.fixture(scope="module")
def pallas_engine():
    """The resident program built with its TPU body — the Pallas kernel,
    here in interpret mode — on the first two devices of the virtual CPU
    mesh.  One engine for the module: interpret mode takes seconds a
    tile, and a shard must span two tiles (parallel/mesh.py).  Not the
    process-wide engine, so ``clean_state`` leaves it armed."""
    eng = MeshEngine(mesh_devices=2, batch_per_device=2048, interpret=True)
    eng.jit_entries_seen = _arm_through_first_job(eng)
    assert eng.n_devices == 2
    return eng


@pytest.mark.parametrize("seed,start,short", [
    (101, 0, 0), (202, 1 << 20, 0), (303, 0, 0),
    (7, 5, 1349),        # a tail round: shard 1 ends inside its second tile
])
def test_pallas_body_matches_jnp_body(pallas_engine, seed, start, short):
    """One program, two bodies: on the seeded jobs and on a tail round
    the Pallas body returns exactly what the jnp body returns (and that
    is the serial path's lowest hit)."""
    job = _seeded_job(seed, difficulty="1" if short else "1.5")
    plain = _armed_engine(batch_per_device=512)   # 8 shards, jnp body
    assert plain.capacity == pallas_engine.capacity
    count = plain.capacity - short
    plain.set_job(job)
    pallas_engine.set_job(job)
    _assert_laid_over_mesh(pallas_engine)
    got = int(pallas_engine.dispatch(start, count))
    assert got == int(plain.dispatch(start, count))
    template = make_template(job.prefix)
    spec = target_spec(job.previous_hash, job.difficulty)
    assert got == int(pow_search_jnp(template, spec, nonce_base=start,
                                     batch=count))
    if got != int(SENTINEL):
        assert job.check(got)


def test_body_is_chosen_by_platform_and_counted(pallas_engine):
    """``stats()["body"]`` and ``mine.mesh.rounds_pallas``: a CPU mesh
    runs the jnp body and counts no Pallas round; the Pallas body (what
    a TPU mesh gets) counts every dispatched round."""
    from upow_tpu.parallel import mesh as pmesh

    plain = _armed_engine(batch_per_device=64)
    assert pmesh.resident_body(plain._mesh) == "jnp"
    assert plain.stats()["body"] == "jnp"
    plain.set_job(_seeded_job(5))
    plain.dispatch(0, plain.capacity)
    assert metrics.counters().get("mine.mesh.rounds_pallas") == 0

    assert pallas_engine.stats()["body"] == "pallas"
    pallas_engine.set_job(_seeded_job(5))
    before = pallas_engine.stats()["dispatches"]
    int(pallas_engine.dispatch(0, pallas_engine.capacity))
    assert metrics.counters().get("mine.mesh.rounds_pallas") == 1
    assert pallas_engine.stats()["dispatches"] == before + 1
    assert MeshEngine().stats()["body"] is None   # not armed: no program


def test_exact_steps_are_exported_from_the_arm_and_counted_a_round():
    """``kernel.mine_mesh.exact_steps``: at zero from the arm on (the jnp
    body leaves it there: it has no steps), and after a round the steps
    of every shard's kernel that took the exact pass, read out of the
    answer's words: both shards' one step at difficulty 1, none where no
    digest meets the target's first word."""
    name = "kernel.mine_mesh.exact_steps"
    plain = _armed_engine(batch_per_device=64)
    assert metrics.counters().get(name) == 0
    plain.set_job(_seeded_job(5, difficulty="1"))
    assert int(plain.dispatch(0, plain.capacity)) != int(SENTINEL)
    assert metrics.counters().get(name) == 0

    eng = MeshEngine(mesh_devices=2, batch_per_device=2048, interpret=True)
    telemetry.reset()
    telemetry.configure()
    assert eng.arm()["armed"]
    assert metrics.counters().get(name) == 0
    eng.set_job(_seeded_job(5, difficulty="1"))
    answer = eng.dispatch(0, eng.capacity)
    assert metrics.counters().get(name) == 0    # not read yet
    assert int(answer) != int(SENTINEL) and int(answer) == int(answer)
    assert metrics.counters().get(name) == 2    # read once, counted once
    eng.set_job(_seeded_job(5, difficulty="9.0"))
    assert int(eng.dispatch(0, eng.capacity)) == int(SENTINEL)
    assert metrics.counters().get(name) == 2


# ------------------------------------------------ disjoint coverage ----

def test_dispatch_accounting_proves_disjoint_exact_coverage():
    """The union of per-shard ranges across rounds equals the scanned
    window exactly — no overlap, no gap, straight from stats()."""
    eng = _armed_engine(batch_per_device=512)
    eng.set_job(_seeded_job(9, difficulty="1"))
    start, total, rounds = 1000, eng.capacity * 3 + 17, 0
    cursor = start
    while cursor < start + total:
        count = min(eng.capacity, start + total - cursor)
        eng.dispatch(cursor, count)
        cursor += count
        rounds += 1

    st = eng.stats()
    assert st["dispatches"] == rounds
    assert st["nonces_planned"] == total
    covered = []
    for rec in st["rounds"]:
        shards = rec["shards"]
        # within a round: adjacent, disjoint, exactly [lo, hi)
        assert shards[0][0] == rec["lo"] and shards[-1][1] == rec["hi"]
        for (a, b), (c, d) in zip(shards, shards[1:]):
            assert b == c
        covered.extend([s for s in shards if s[0] < s[1]])
    covered.sort()
    # across rounds: the non-empty shard ranges tile [start, start+total)
    assert covered[0][0] == start and covered[-1][1] == start + total
    for (a, b), (c, d) in zip(covered, covered[1:]):
        assert b == c
    assert sum(b - a for a, b in covered) == total


def test_dispatch_rejects_oversized_round():
    eng = _armed_engine(batch_per_device=64)
    eng.set_job(_seeded_job(3))
    with pytest.raises(ValueError):
        eng.dispatch(0, eng.capacity + 1)
    with pytest.raises(ValueError):
        eng.dispatch(0, 0)


def test_a_job_needs_the_armed_engines_mesh():
    """``set_job`` lays the job over the engine's mesh, so it needs the
    arm as ``dispatch`` does; an unarmed engine has no program either."""
    eng = MeshEngine(batch_per_device=64)
    with pytest.raises(RuntimeError, match="before arm"):
        eng.set_job(_seeded_job(3))
    with pytest.raises(RuntimeError, match="before arm"):
        eng.dispatch(0, 1)
    st = eng.stats()
    assert st["job_layouts"] == 0 and st["jit_entries"] == 0


# ---------------------------------------------- no-recompile job swap ----

@pytest.mark.parametrize("body", ["jnp", "pallas"])
def test_job_swap_is_pure_dispatch_no_recompile(body, request):
    """A new job / chain-tip change must NOT recompile the resident
    program, whichever body it was built with: the arm compiles one jit
    entry, the first job meets it (its arrays have the warm dispatch's
    sharding), and it stays the one over three more jobs of other
    targets; the mine_mesh compile-cache counters record one miss then
    only hits."""
    if body == "jnp":
        # a per-shard batch no other test compiles: the arm's entry is new
        eng = get_mesh_engine(batch_per_device=320)
        seen = _arm_through_first_job(eng)
    else:
        eng = request.getfixturevalue("pallas_engine")
        seen = eng.jit_entries_seen
    assert eng.stats()["body"] == body
    before, armed, first_job = seen
    assert armed == before + 1     # one program, compiled at the arm
    assert first_job == armed      # no second one at the first job
    jit_entries = _jit_entries()

    for seed in (2, 3, 4):  # three job swaps, different targets too
        eng.set_job(_seeded_job(seed, difficulty=str(1 + seed / 10)))
        int(eng.dispatch(seed * 1000, eng.capacity))

    assert _jit_entries() == jit_entries
    assert eng.stats()["jit_entries"] == jit_entries
    counters = metrics.counters()
    # the first round since clean_state's reset is the key's one miss
    assert counters.get("kernel.mine_mesh.compile_cache_misses", 0) == 1
    assert counters.get("kernel.mine_mesh.compile_cache_hits", 0) >= 2


@pytest.mark.parametrize("body", ["jnp", "pallas"])
def test_job_layouts_count_jobs_not_rounds(body, request):
    """``mine.mesh.job_layouts`` / ``stats()["job_layouts"]``: 0 from
    the arm on, one a job whose arrays were placed, none for the same
    job set again (however often ``dispatcher(job)`` is called), none
    for a round."""
    eng = (_armed_engine(batch_per_device=64) if body == "jnp"
           else request.getfixturevalue("pallas_engine"))
    laid0 = eng.stats()["job_layouts"]
    counted0 = metrics.counters().get("mine.mesh.job_layouts")

    def layouts():
        return (metrics.counters()["mine.mesh.job_layouts"] - (counted0 or 0),
                eng.stats()["job_layouts"] - laid0)

    if body == "jnp":   # a fresh arm exports the counter at zero
        assert counted0 == 0 and laid0 == 0
    job = _seeded_job(11)
    eng.set_job(job)
    _assert_laid_over_mesh(eng)
    laid = eng._job
    assert layouts() == (1, 1)
    assert eng.dispatcher(job).args == (laid,)   # the same job again
    assert eng.set_job(job) is laid
    assert layouts() == (1, 1)
    if body == "jnp":   # a round lays nothing (seconds in interpret mode)
        rounds = eng.stats()["dispatches"]
        int(eng.dispatch(0, eng.capacity))
        assert eng.stats()["dispatches"] == rounds + 1
        assert layouts() == (1, 1)
    eng.set_job(_seeded_job(12, difficulty="2.5"))
    _assert_laid_over_mesh(eng)
    assert layouts() == (2, 2)


@pytest.mark.parametrize("prefix_len,rounds,words", [
    (104, 10, 4),   # v2: the nonce is w10
    (134, 1, 0),    # v1: the nonce starts in w1
])
def test_hoist_counters_say_once_a_job_what_the_host_finished(
        prefix_len, rounds, words):
    """``kernel.mine_mesh.rounds_hoisted`` / ``.words_hoisted``: once a
    job laid over the mesh, what its header's nonce placement let
    ``make_template`` finish; none for the same job again or a round.
    The round's answer is hashlib's, whichever header."""
    from decimal import Decimal

    eng = _armed_engine(batch_per_device=64)

    def hoisted():
        counters = metrics.counters()
        return (counters.get("kernel.mine_mesh.rounds_hoisted", 0),
                counters.get("kernel.mine_mesh.words_hoisted", 0))

    assert hoisted() == (0, 0)
    r = random.Random(f"hoist-{prefix_len}")
    job = MiningJob(bytes(r.randrange(256) for _ in range(prefix_len)),
                    bytes(r.randrange(256) for _ in range(32)).hex(),
                    Decimal("1"))
    eng.set_job(job)
    eng.set_job(job)
    assert hoisted() == (rounds, words)
    got = int(eng.dispatch(0, eng.capacity))
    assert hoisted() == (rounds, words)
    assert got == next(n for n in range(eng.capacity) if job.check(n))
    eng.set_job(_seeded_job(13))
    assert hoisted() == (rounds + 10, words + 4)


def test_engine_reuse_and_replacement_semantics():
    """get_mesh_engine: armed engine is reused while the round fits its
    capacity; a larger round replaces it (one deliberate recompile)."""
    eng = _armed_engine(batch_per_device=128)
    assert get_mesh_engine(round_hint=eng.capacity) is eng
    assert get_mesh_engine(round_hint=eng.capacity // 2) is eng
    bigger = get_mesh_engine(round_hint=eng.capacity * 2)
    assert bigger is not eng


# ------------------------------------------- single dispatch owner ----

def test_all_mesh_dispatches_ride_the_runtime_as_mine():
    """Every warm + round dispatch shows up in the device runtime's
    per-source accounting under "mine" — no side-channel dispatches."""
    from upow_tpu.device.runtime import get_runtime

    runtime = get_runtime()
    before = runtime.stats()["per_source"].get("mine", 0)
    eng = _armed_engine(batch_per_device=64)  # warm rides source "mine"
    eng.set_job(_seeded_job(42))
    n = 3
    for i in range(n):
        eng.dispatch(i * eng.capacity, eng.capacity)
    after = runtime.stats()["per_source"].get("mine", 0)
    assert after - before == n + 1  # n rounds + the arm-time warm


# ------------------------------------------------------- arm ladder ----

def test_arm_captures_real_exception_text(monkeypatch):
    """The one arm (through the runtime) fails with a real exception:
    its text + traceback fingerprint are kept, the engine's failure
    reason carries them (no "hung/failed"), and nothing else is tried —
    no second attempt under another environment, no probing child."""
    from upow_tpu.device import runtime as rt_mod

    arms = []

    class WedgedRuntime:
        def arm(self, **kw):
            arms.append(kw)
            raise RuntimeError(
                "PJRT INTERNAL: device held by another process")

        def platform(self):
            return None

        def stats(self):
            return {"arm": {}}

    monkeypatch.setattr(rt_mod, "get_runtime", lambda: WedgedRuntime())
    eng = MeshEngine()
    info = eng.arm(timeout=1.0)
    assert not info["armed"]
    ladder = info["ladder"]
    assert [r["attempt"] for r in ladder] == ["runtime"]
    assert arms == [{"deadline": 1.0}]
    assert not ladder[0]["ok"]
    assert "held by another process" in ladder[0]["error"]
    assert ladder[0]["traceback_fingerprint"]
    assert eng.arm_failure_reason.startswith("runtime: ")
    # the dispatcher path surfaces the same reason, verbatim
    with pytest.raises(RuntimeError, match="held by another process"):
        eng.dispatcher(_seeded_job(1))


def test_arm_ladder_success_records_platform_rung():
    eng = _armed_engine()
    assert eng.arm_failure_reason is None
    ladder = eng.arm_ladder
    assert ladder and ladder[-1]["ok"]
    assert "cpu x8" in ladder[-1]["detail"]
    # re-arming is a no-op that returns the same ladder
    again = eng.arm()
    assert again["armed"] and again["ladder"] == ladder


# ------------------------------------- two jobs laid at once (ISSUE 44) ----

@pytest.mark.parametrize("next_is", ["a_new_key", "the_same_key"])
def test_the_next_job_laid_leaves_the_rounds_in_flight_to_their_own_job(
        next_is):
    """The seam as the miner makes it: job A has a round in flight and
    one still to issue when job B is laid and issues its first.  Every
    answer is its own job's (the serial path's lowest hit), every round
    record names its job, a hit's latency runs from its own job's
    layout, a new key costs one layout and a key set again none, and
    nothing is compiled after the arm."""
    from upow_tpu.telemetry import device as ktel

    eng = _armed_engine(batch_per_device=64)
    cap = eng.capacity
    job_a = _seeded_job(21, difficulty="1")
    job_b = job_a if next_is == "the_same_key" \
        else _seeded_job(22, difficulty="1")
    jit_entries = eng.stats()["jit_entries"]
    rounds_a = eng.dispatcher(job_a)
    laid_a = eng._job
    in_flight = rounds_a(0, cap)
    time.sleep(0.02)                       # A's layout is the older
    rounds_b = eng.dispatcher(job_b)       # set_job while A's round is out
    first_b = rounds_b(0, cap)
    last_a = rounds_a(cap, cap)            # A's own, after B was set
    assert (eng._job is laid_a) == (next_is == "the_same_key")
    layouts = 1 if next_is == "the_same_key" else 2
    assert eng.stats()["job_layouts"] == layouts
    assert metrics.counters()["mine.mesh.job_layouts"] == layouts
    # the arm's one program (the process's others are other tests')
    assert eng.stats()["jit_entries"] == jit_entries

    def serial(job, start):
        return int(pow_search_jnp(
            make_template(job.prefix),
            target_spec(job.previous_hash, job.difficulty),
            nonce_base=start, batch=cap))

    assert int(in_flight) == serial(job_a, 0)
    assert int(first_b) == serial(job_b, 0)
    assert int(last_a) == serial(job_a, cap)
    assert [(r["job"], r["lo"], r["hi"]) for r in eng.stats()["rounds"]] \
        == [(1, 0, cap), (layouts, 0, cap), (1, cap, 2 * cap)]
    # a hit of A, found after B was set, is timed from A's layout
    seen = []
    real = ktel.record_mine_hit
    try:
        ktel.record_mine_hit = seen.append
        eng.note_hit(job_a)
        eng.note_hit(job_b)
        eng.note_hit()                     # the job set last: B
    finally:
        ktel.record_mine_hit = real
    assert seen[0] >= 0.02
    assert (seen[0] - seen[1] >= 0.015) == (next_is == "a_new_key")
    assert abs(seen[2] - seen[1]) < 0.015
    # a third key drops the oldest layout, and with it that job's base
    eng.set_job(_seeded_job(23))
    eng.note_hit(job_a if next_is == "a_new_key" else _seeded_job(24))
    assert metrics.histograms().get("mine.hit_latency", {}).get(
        "count", 0) == 0


# ------------------------------------------------------- telemetry ----

def test_mine_round_telemetry_families():
    eng = _armed_engine(batch_per_device=128)
    eng.set_job(_seeded_job(5, difficulty="1"))
    eng.dispatch(0, eng.capacity // 2)  # half occupancy
    eng.note_hit()
    counters = metrics.counters()
    assert counters.get("kernel.mine_mesh.lanes_real", 0) == eng.capacity // 2
    assert counters.get("kernel.mine_mesh.lanes_padded", 0) == eng.capacity
    hists = metrics.histograms()
    assert hists["mine.shard_occupancy"]["count"] == eng.n_devices
    assert hists["mine.hit_latency"]["count"] == 1


@pytest.mark.parametrize("counts", [(1024,), (512,), (1024, 1024, 1001)],
                         ids=["whole", "half", "ragged-third"])
def test_a_rounds_families_value_for_value(counts):
    """What ``record_mine_round`` leaves a round, as one registry update
    since ISSUE 42: ``kernel.mine_mesh.*`` (the round as one batch of
    real over padded lanes; a compile key seen before is a hit) and one
    ``mine.shard_occupancy`` observation a shard."""
    from upow_tpu.telemetry.device import OCCUPANCY_BUCKETS

    eng = _armed_engine(batch_per_device=128)
    assert eng.capacity == 1024
    eng.set_job(_seeded_job(5, difficulty="9"))
    seen = metrics.counters()

    def bucket(share):
        return next(i for i, b in enumerate(OCCUPANCY_BUCKETS)
                    if share <= b)

    start = 0
    occupancy = [0] * (len(OCCUPANCY_BUCKETS) + 1)
    shard_occupancy = list(occupancy)
    for count in counts:
        int(eng.dispatch(start, count))
        start += count
        occupancy[bucket(count / 1024)] += 1
        for lo, hi in eng.plan_round(0, count):
            shard_occupancy[bucket((hi - lo) / 128)] += 1

    def grew(name):
        return metrics.counters().get(name, 0) - seen.get(name, 0)

    assert grew("kernel.mine_mesh.lanes_real") == sum(counts)
    assert grew("kernel.mine_mesh.lanes_padded") == 1024 * len(counts)
    # the arm's warm dispatch records nothing: the first round is the
    # program's one miss, every later one a hit
    assert grew("kernel.mine_mesh.compile_cache_misses") == 1
    assert grew("kernel.mine_mesh.compile_cache_hits") == len(counts) - 1
    hists = metrics.histograms()
    assert hists["kernel.mine_mesh.occupancy"]["counts"] == occupancy
    assert hists["kernel.mine_mesh.occupancy"]["sum"] == \
        pytest.approx(sum(c / 1024 for c in counts))
    assert hists["mine.shard_occupancy"]["counts"] == shard_occupancy
    assert hists["mine.shard_occupancy"]["count"] == 8 * len(counts)
    assert hists["kernel.mine_mesh.dispatch_seconds"]["count"] == 0


def test_a_dispatch_is_a_plan_then_a_submit_with_the_call_inside(
        span_events):
    """One ``MeshEngine.dispatch`` over the 8-device CPU mesh: on the
    caller's thread ``mine.round.plan`` closed before
    ``mine.round.submit`` opens, and the device owner's ``runtime.call``
    begun and ended inside the submit: submit less call is the hop
    between the two threads and back."""
    import threading

    eng = _armed_engine(batch_per_device=128)
    eng.set_job(_seeded_job(6, difficulty="9"))
    del span_events[:]          # the arm's and the job's own
    before = telemetry.stats()
    with telemetry.span("mine.round.issue", light=True):
        int(eng.dispatch(0, eng.capacity))
    me, owner = threading.current_thread().name, "upow-device-runtime"
    assert span_events == [
        ("open", "mine.round.issue", me),
        ("open", "mine.round.plan", me), ("close", "mine.round.plan", me),
        ("open", "mine.round.submit", me),
        ("open", "runtime.call", owner), ("close", "runtime.call", owner),
        ("close", "mine.round.submit", me),
        ("close", "mine.round.issue", me)]
    after = telemetry.stats()

    def took(name):
        assert after[name]["count"] \
            - before.get(name, {"count": 0})["count"] == 1
        return after[name]["total_s"] \
            - before.get(name, {"total_s": 0.0})["total_s"]

    # the books of the issue: its two parts and a self time
    assert took("runtime.call") <= took("mine.round.submit")
    parts = took("mine.round.plan") + took("mine.round.submit")
    assert parts <= took("mine.round.issue") < parts + 0.05
    # a refused round is still a timed plan, and no submit
    with pytest.raises(ValueError):
        eng.dispatch(0, eng.capacity + 1)
    assert telemetry.stats()["mine.round.plan"]["count"] \
        == after["mine.round.plan"]["count"] + 1
    assert telemetry.stats()["mine.round.submit"]["count"] \
        == after["mine.round.submit"]["count"]


def test_engine_stats_exported_for_node_gauges():
    assert mesh_engine.engine_stats() is None  # before first use
    eng = _armed_engine(batch_per_device=64)
    st = mesh_engine.engine_stats()
    assert st["armed"] and st["devices"] == 8
    assert st["capacity"] == eng.capacity
    assert st["job_layouts"] == 0 and st["jit_entries"] >= 1


def test_a_rounds_answer_says_when_it_is_ready_and_the_mesh_line_the_depth(
        monkeypatch, capsys):
    """The handle of a round asks its array's host side (no transfer of
    its own, and nothing of the program touched: the jit cache stays as
    it was), and its words are on their way to the host from the issue
    on; the miner's ``mesh:`` line carries the round loop's depth in
    force."""
    import json

    from upow_tpu.mine import engine, miner

    eng = _armed_engine(batch_per_device=64)
    eng.set_job(_seeded_job(5))
    entries = _jit_entries()
    asked = []
    to_host = type(jax.numpy.zeros(1)).copy_to_host_async
    monkeypatch.setattr(type(jax.numpy.zeros(1)), "copy_to_host_async",
                        lambda words: asked.append(words) or to_host(words))
    handle = eng.dispatch(0, eng.capacity)
    # the answer's host copy is asked for at the issue, once a round
    assert asked == [handle._words]
    assert handle.ready() in (True, False)
    hit = int(handle)
    assert handle.ready() and int(handle) == hit
    assert engine._unfinished([handle, handle]) == 0
    assert _jit_entries() == entries
    for depth in (2, 4):
        monkeypatch.setattr(engine, "_depth", depth)
        miner._print_mesh_accounting(0)
        line = capsys.readouterr().out.splitlines()[-1]
        assert line.startswith("mesh: ")
        said = json.loads(line[len("mesh: "):])
        assert said["rounds_in_flight"] == depth and said["jit_entries"] \
            == eng.stats()["jit_entries"]


# ------------------------- --device tpu: the engine on a one-device mesh ----

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def powref():
    bench = os.path.join(REPO, "benchmarks")
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location(
            "harness.powref", os.path.join(bench, "harness", "powref.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module
    finally:
        sys.path.remove(bench)


def _tail_spans() -> int:
    return telemetry.stats().get("mine.round.tail", {}).get("count", 0)


def _one_device_engine(body: str, batch: int) -> MeshEngine:
    """The process-wide engine ``mine(.., "mesh", mesh_devices=1)`` will
    meet, armed over one device with a round of ``batch`` lanes; the
    Pallas body (what the chip runs) in interpret mode, a shard of two
    tiles."""
    if body == "pallas":
        mesh_engine._ENGINE = MeshEngine(
            mesh_devices=1, batch_per_device=batch, interpret=True)
    eng = get_mesh_engine(mesh_devices=1, round_hint=batch)
    assert eng.arm()["armed"]
    assert (eng.n_devices, eng.capacity, eng.stats()["body"]) == (
        1, batch, body)
    return eng


@pytest.mark.parametrize("device,configured,want", [
    ("tpu", 0, ("mesh", 1)),      # one chip, whatever the config says
    ("tpu", 4, ("mesh", 1)),
    ("mesh", 0, ("mesh", 0)),     # device.mesh_devices, 0 = all
    ("mesh", 4, ("mesh", 4)),
    ("pallas", 4, ("pallas", 4)),  # the static engine: no mesh to size
])
def test_select_backend_gives_tpu_the_resident_engine_over_one_device(
        device, configured, want):
    from upow_tpu.mine.miner import select_backend

    assert select_backend(device, configured) == want


def test_device_tpu_mines_through_the_resident_engine_on_one_device():
    """What ``run`` does with ``--device tpu`` on a host of eight devices
    configured for all of them: the resident engine over the first one,
    a round of ``batch`` lanes on it, the nonce the python loop finds."""
    from upow_tpu.mine.miner import select_backend

    backend, mesh_devices = select_backend("tpu", 0)
    job = _seeded_job(55, difficulty="1")
    kw = dict(start=0, stride_end=1 << 14, batch=1 << 10, ttl=60.0)
    got = mine(job, backend, mesh_devices=mesh_devices, **kw)
    eng = get_mesh_engine(mesh_devices=1)
    assert eng.armed and eng.stats()["dispatches"] > 0   # the one that mined
    assert eng.mesh_devices() == jax.devices()[:1]
    assert (eng.n_devices, eng.batch_per_device) == (1, 1 << 10)
    want = mine(job, "python", **kw)
    assert got.nonce == want.nonce and job.check(got.nonce)
    assert got.hashes_tried == want.hashes_tried
    # the static engine's per-tip programs: exported, and none
    counters = metrics.counters()
    assert counters["kernel.sha256_search.compile_cache_misses"] == 0
    assert counters["kernel.mine_mesh.compile_cache_misses"] == 1
    assert metrics.histograms()["mine.hit_latency"]["count"] == 1


@pytest.mark.parametrize("body", ["jnp", "pallas"])
def test_two_tips_on_one_device_are_one_program(body):
    """Two jobs of other previous hashes and difficulties (a target of
    whole chars, one with a fractional char) through ``mine()`` on a
    one-device mesh: the arm's one jit entry serves both, the compile
    key misses once, every round is counted for the body it ran."""
    # per-shard batches no other test compiles: the arm's entry is new
    batch = 3072 if body == "pallas" else 832
    before = _jit_entries()
    eng = _one_device_engine(body, batch)
    armed = _jit_entries()
    rounds = 0
    for seed, difficulty in ((21, "1"), (22, "1.5")):
        job = _seeded_job(seed, difficulty=difficulty)
        got = mine(job, "mesh", mesh_devices=1, start=seed * batch,
                   stride_end=(seed + 2) * batch, batch=batch, ttl=60.0)
        want = next((n for n in range(seed * batch, (seed + 2) * batch)
                     if job.check(n)), None)
        assert got.nonce == want
        rounds += -(-got.hashes_tried // batch)
    assert armed == before + 1 and _jit_entries() == armed
    st = eng.stats()
    assert st["jit_entries"] == armed and st["job_layouts"] == 2
    counters = metrics.counters()
    assert counters["kernel.mine_mesh.compile_cache_misses"] == 1
    assert counters["kernel.sha256_search.compile_cache_misses"] == 0
    assert counters["mine.mesh.job_layouts"] == 2
    assert counters["mine.rounds"] == rounds
    # in-flight rounds past a hit were dispatched too
    assert counters["mine.mesh.rounds_pallas"] == (
        st["dispatches"] if body == "pallas" else 0)
    assert st["dispatches"] >= rounds


@pytest.mark.parametrize("body", ["jnp", "pallas"])
def test_a_ragged_last_round_on_one_device_issues_under_mine_round_tail(
        body, powref):
    """Two whole rounds and one of 100 short: the short one runs the one
    program with its end in the ``ranges`` row, opens ``mine.round.tail``
    once and is counted; a hit that lies only in it is the python
    reference's lowest, and the range cut below that hit ends with
    none."""
    batch = 2048
    eng = _one_device_engine(body, batch)
    job = _seeded_job(77, difficulty="3")
    hits = [n for n in range(1 << 17) if job.check(n)]
    hit = next(h for below, h in zip(hits, hits[1:])
               if h - below > 2 * batch + 50)
    start = hit - (2 * batch + 50)
    entries, tails = _jit_entries(), _tail_spans()
    found = mine(job, "mesh", mesh_devices=1, batch=batch, start=start,
                 stride_end=hit + 50, ttl=60.0)
    assert found.nonce == hit == powref.lowest_hit(
        job.prefix, start, hit + 50, job.previous_hash, job.difficulty,
        workers=1)
    assert found.hashes_tried == 2 * batch + 100
    assert _tail_spans() - tails == 1
    counters = metrics.counters()
    assert counters["mine.rounds_masked"] == 1
    assert counters["mine.lanes_masked"] == batch - 100
    assert counters["mine.rounds"] == 3
    assert counters["mine.nonces"] == 2 * batch + 100
    assert eng.stats()["rounds"][-1]["shards"] == [(hit - 50, hit + 50)]
    missed = mine(job, "mesh", mesh_devices=1, batch=batch, start=start,
                  stride_end=hit, ttl=60.0)
    assert missed.nonce is None and missed.hashes_tried == 2 * batch + 50
    assert _tail_spans() - tails == 2
    assert metrics.counters()["mine.rounds_masked"] == 2
    assert _jit_entries() == entries


def test_a_whole_round_on_the_mesh_opens_no_tail_span():
    batch = 512
    _one_device_engine("jnp", batch)
    tails = _tail_spans()
    result = mine(_seeded_job(9, difficulty="9"), "mesh", mesh_devices=1,
                  batch=batch, start=0, stride_end=3 * batch, ttl=60.0)
    assert result.nonce is None and result.hashes_tried == 3 * batch
    assert _tail_spans() == tails
    assert metrics.counters().get("mine.rounds_masked", 0) == 0


def test_the_full_range_plan_on_one_device_is_255_rounds_and_a_ragged_one():
    """``[0, 2^32 - 1)`` in rounds of 2^24 on one device: each round is
    one ``ranges`` row, ``[start, start + count)`` itself, the 256th one
    short; the rows tile the range and the sentinel is in none."""
    batch = 1 << 24
    eng = MeshEngine(mesh_devices=1, batch_per_device=batch)
    eng._n_dev = 1          # the plan needs no armed program
    rows, cursor = [], 0
    while cursor < MAX_SEARCH_END:
        count = min(batch, MAX_SEARCH_END - cursor)
        (row,) = eng.plan_round(cursor, count)
        rows.append(row)
        cursor += count
    assert len(rows) == 256
    assert all(hi - lo == batch for lo, hi in rows[:-1])
    assert rows[-1] == (255 * batch, NONCE_SPACE - 1)      # 2^24 - 1 lanes
    assert rows[0][0] == 0 and all(
        a[1] == b[0] for a, b in zip(rows, rows[1:]))
    assert int(SENTINEL) not in range(*rows[-1])


@pytest.mark.parametrize("body", ["jnp", "pallas"])
def test_the_top_of_the_nonce_space_on_one_device_matches_powref(
        body, powref):
    """Bases above 2^31, the ragged round that ends at the sentinel's
    neighbour 2^32 - 2, and that neighbour alone: what the one-device
    mesh answers is what ``powref.lowest_hit`` finds by hashlib (−1
    where it finds none: the engine's SENTINEL)."""
    batch = 2048
    eng = _one_device_engine(body, batch)
    # a job whose header at nonce 2^32 - 2 meets its target: the last
    # lane ever searched has to answer
    job = next(j for j in (_seeded_job(s, difficulty="1.5")
                           for s in range(1000, 1400))
               if j.check(MAX_SEARCH_END - 1))
    eng.set_job(job)

    def reference(lo, hi):
        ref = powref.lowest_hit(job.prefix, lo, hi, job.previous_hash,
                                job.difficulty, workers=1)
        return int(SENTINEL) if ref < 0 else ref

    top = MAX_SEARCH_END
    for start, count in (((1 << 31) + 12345, batch),
                         (top - batch - 777, batch),
                         (top - 1349, 1349),          # the ragged round
                         (top - 1, 1)):               # the neighbour alone
        assert int(eng.dispatch(start, count)) == reference(
            start, start + count), (start, count)
    assert int(eng.dispatch(top - 1, 1)) == top - 1
    # and through mine(): the cap takes the sentinel off the range's end
    tails = _tail_spans()
    hard = _seeded_job(1234, difficulty="9")
    result = mine(hard, "mesh", mesh_devices=1, batch=batch,
                  start=NONCE_SPACE - 2 * batch - 1, stride_end=NONCE_SPACE,
                  ttl=60.0)
    assert result.nonce is None and result.hashes_tried == 2 * batch
    assert _tail_spans() == tails
