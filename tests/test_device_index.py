"""DeviceUtxoIndex: exact membership, twin-fingerprint safety, batched
fingerprinting, incremental sorted maintenance
(upow_tpu/state/device_index.py; SURVEY §2.2, ISSUE 7 tentpole a)."""

import numpy as np
import pytest

from upow_tpu.state.device_index import (DeviceUtxoIndex, fingerprint,
                                         fingerprint_batch)


def _op(i: int, idx: int = 0):
    return (i.to_bytes(32, "big").hex(), idx)


def test_prefilter_membership_and_updates():
    ops = [_op(1), _op(2), _op(3, 254)]
    idx = DeviceUtxoIndex(ops[:2])
    assert list(idx.maybe_contains_batch(ops)) == [True, True, False]
    idx.add([ops[2]])
    assert list(idx.maybe_contains_batch(ops)) == [True, True, True]
    idx.remove([ops[0]])
    assert list(idx.maybe_contains_batch(ops)) == [False, True, True]
    assert idx.missing(ops) == [ops[0]]
    assert len(idx) == 2


def test_exact_membership_no_escalation():
    """contains_batch answers exactly — the SQL escalation the old
    prefilter needed is gone from the hot path."""
    ops = [_op(i) for i in range(64)]
    idx = DeviceUtxoIndex(ops[:32])
    mask = idx.contains_batch(ops)
    assert mask[:32].all() and not mask[32:].any()
    # same txid, different output index: distinct outpoints
    assert list(idx.contains_batch([(ops[0][0], 0), (ops[0][0], 1)])) == \
        [True, False]


def test_empty_and_large_batches():
    idx = DeviceUtxoIndex()
    assert idx.maybe_contains_batch([]).shape == (0,)
    assert idx.contains_batch([]).shape == (0,)
    ops = [_op(i) for i in range(1000)]
    idx.add(ops)
    mask = idx.contains_batch(ops + [_op(10_000)])
    assert mask[:1000].all() and not mask[1000]


def test_collision_twin_not_over_removed(monkeypatch):
    """Two live outpoints sharing a 64-bit fingerprint: spending one must
    NOT make the survivor report absent (that would reject a valid
    block).  The exact map resolves the twins individually."""
    import upow_tpu.state.device_index as di

    monkeypatch.setattr(  # force a universal collision
        di, "fingerprint_batch",
        lambda ops: np.full(len(ops), 42, dtype=np.uint64))
    idx = di.DeviceUtxoIndex([_op(1), _op(2)])
    idx.remove([_op(1)])
    # the survivor is still exactly present; the spent twin is not
    assert list(idx.contains_batch([_op(2)])) == [True]
    assert list(idx.contains_batch([_op(1)])) == [False]
    # the prefilter still hits on the shared fingerprint (sound: it only
    # promises that False is definitive absence)
    assert list(idx.maybe_contains_batch([_op(2)])) == [True]
    idx.remove([_op(2)])
    assert list(idx.contains_batch([_op(2)])) == [False]
    assert list(idx.maybe_contains_batch([_op(2)])) == [False]
    assert len(idx) == 0


def test_fingerprint_is_stable_uint64_and_batch_identical():
    fp = fingerprint(_op(7, 3))
    assert fp == fingerprint(_op(7, 3))
    assert 0 <= fp < (1 << 64)
    assert fingerprint(_op(7, 4)) != fp
    ops = [_op(i, i % 5) for i in range(200)]
    batch = fingerprint_batch(ops)
    assert batch.dtype == np.uint64
    assert batch.tolist() == [fingerprint(o) for o in ops]


def test_remove_absent_outpoint_is_noop():
    idx = DeviceUtxoIndex([_op(1)])
    idx.remove([_op(99)])  # matches the SQL DELETE / old set semantics
    assert list(idx.contains_batch([_op(1), _op(99)])) == [True, False]
    assert len(idx) == 1


def test_incremental_insert_keeps_keys_sorted():
    """add() splices sorted slabs into place — no full re-sort — and the
    host key array must stay sorted through interleaved adds/removes
    (searchsorted correctness depends on it)."""
    idx = DeviceUtxoIndex([_op(i) for i in range(0, 100, 2)])
    idx.add([_op(i) for i in range(1, 100, 2)])
    assert (np.diff(idx._host_keys.astype(np.uint64)) >= 0).all()
    idx.remove([_op(i) for i in range(0, 100, 3)])
    assert (np.diff(idx._host_keys.astype(np.uint64)) >= 0).all()
    expect = {i for i in range(100)} - set(range(0, 100, 3))
    mask = idx.contains_batch([_op(i) for i in range(100)])
    assert {i for i in range(100) if mask[i]} == expect


def test_twin_collisions_at_full_block_scale(monkeypatch):
    """8k-tx block scale with every fingerprint shared by a twin pair:
    the probe kernel declares all of them ambiguous, the shadow map
    resolves each exactly, and a batched spend of one twin per pair
    never takes the survivor down with it (ISSUE 11 satellite)."""
    import upow_tpu.state.device_index as di

    # pairwise collisions: outpoints 2k and 2k+1 share fingerprint k
    monkeypatch.setattr(
        di, "fingerprint_batch",
        lambda ops: np.array([int(o[0], 16) >> 1 for o in ops],
                             dtype=np.uint64))
    n = 8192
    ops = [_op(i) for i in range(n)]
    idx = di.DeviceUtxoIndex(
        ops, values=[(i + 1, 0, 4) for i in range(n)])
    assert idx.stats()["twin_fingerprints"] == n // 2

    absent = [_op(i) for i in range(n, n + 64)]
    mask = idx.contains_batch(ops + absent)
    assert mask[:n].all() and not mask[n:].any()
    # every live probe went through an exact shadow resolution
    assert idx.stats()["shadow_consults"] >= n

    # one batched block: spend the even twin of every pair, create a
    # fresh (collision-free at this range's fps) replacement set
    spent = [_op(i) for i in range(0, n, 2)]
    created = [_op(i) for i in range(2 * n, 2 * n + n // 2)]
    idx.apply_block(created, spent,
                    created_values=[(7, 0, 5)] * len(created))
    assert not idx.contains_batch(spent).any()
    # the odd twins all survive their partner's spend
    assert idx.contains_batch([_op(i) for i in range(1, n, 2)]).all()
    assert idx.contains_batch(created).all()

    # O(delta) rollback restores the pre-block membership exactly
    assert idx.rollback_block()
    after = idx.contains_batch(ops + created)
    assert after[:n].all() and not after[n:].any()
    assert len(idx) == n


def test_rollback_across_three_blocks_restores_values():
    """A ≥3-block reorg unwinds the undo log block by block; membership
    AND the resident value store (amounts) must match the snapshot taken
    before each block, including re-created spends (ISSUE 11)."""
    genesis = [_op(i) for i in range(64)]
    idx = DeviceUtxoIndex(
        genesis, values=[(10 * (i + 1), 0, 1) for i in range(64)])

    blocks = [
        ([_op(100), _op(101)], [_op(0), _op(1), _op(2)]),
        ([_op(200), _op(201), _op(202)], [_op(100), _op(3)]),
        ([_op(300)], [_op(200), _op(101), _op(4)]),
    ]
    universe = genesis + [_op(i) for i in
                          (100, 101, 200, 201, 202, 300, 999)]

    def snapshot():
        present, amounts = idx.lookup_batch(universe)
        return present.tolist(), amounts.tolist()

    snaps = [snapshot()]
    for height, (created, spent) in enumerate(blocks):
        idx.apply_block(created, spent,
                        created_values=[(1000 + height, 0, 2 + height)]
                        * len(created))
        snaps.append(snapshot())
    assert idx.undo_depth() == 3
    # sanity: each block actually changed the observable state
    assert len({tuple(s[0]) for s in snaps}) == 4

    for depth in (3, 2, 1):
        assert idx.undo_depth() == depth
        assert idx.rollback_block()
        assert snapshot() == snaps[depth - 1]
    assert idx.undo_depth() == 0
    assert not idx.rollback_block()  # exhausted log reports False


def test_accept_path_steady_state_zero_shadow_consults():
    """End-to-end block accept through the fused resident path on a
    collision-free block: the device probes fire (index.probes grows)
    and NOT ONE membership answer needed the host shadow map
    (index.shadow_consults stays flat) — the zero-per-tx-host-round-trip
    acceptance criterion, asserted on telemetry (ISSUE 11)."""
    import asyncio

    from upow_tpu.loadgen.fixtures import chain_with_utxo_fanout, leaf_spends
    from upow_tpu.core import clock, difficulty
    from upow_tpu.telemetry import metrics

    async def scenario():
        state, manager, d, pub, addr, mids, mine_block = \
            await chain_with_utxo_fanout(8, 4, 0x1DE7)
        try:
            state.enable_device_index()
            assert state.resident_indexes(), "device index failed to arm"
            manager.fused_accept = True
            txs = leaf_spends(mids, addr, d, pub)
            before = dict(metrics.counters())
            await mine_block(txs)
            after = dict(metrics.counters())

            # differential: resident probe vs SQL over spends + creations
            idx = state.resident_indexes()["unspent_outputs"]
            spent = [i.outpoint for t in txs for i in t.inputs]
            created = [(t.hash(), 0) for t in txs]
            sample = spent + created
            dev = [bool(v) for v in idx.contains_batch(sample)]
            sql = [bool(v) for v in
                   await state.outpoints_exist(sample, "unspent_outputs")]
            assert dev == sql
            assert idx.stats()["twin_fingerprints"] == 0
            return before, after
        finally:
            state.close()

    start_diff = difficulty.START_DIFFICULTY
    clock.freeze(1_700_000_000)
    try:
        before, after = asyncio.run(scenario())
    finally:
        clock.reset()
        difficulty.START_DIFFICULTY = start_diff

    probes = after.get("index.probes", 0) - before.get("index.probes", 0)
    consults = (after.get("index.shadow_consults", 0)
                - before.get("index.shadow_consults", 0))
    assert probes > 0, "fused accept path never dispatched a probe"
    assert consults == 0, "steady state accept consulted the host map"


@pytest.fixture(scope="module")
def accept_stages():
    """One 32-tx block through the fused resident accept path and
    through the serial SQL path (same frozen clock, so the same block
    hashes), then a FORCED REORG (``remove_blocks``) and a re-accept on
    the resident side.  Each stage keeps the three membership answers
    over spends + creations + absent outpoints — resident probe, host
    shadow map, SQL — and the unspent-set fingerprint."""
    import asyncio

    from upow_tpu.core import clock, difficulty
    from upow_tpu.loadgen.fixtures import chain_with_utxo_fanout, leaf_spends

    absent = [("ff" * 32, i) for i in range(16)]

    async def scenario(resident: bool) -> dict:
        state, manager, d, pub, addr, mids, mine_block = \
            await chain_with_utxo_fanout(8, 4, 0xACC7)
        try:
            manager.fused_accept = resident
            if resident:
                state.enable_device_index()
                assert state.resident_indexes(), "device index failed to arm"
            txs = leaf_spends(mids, addr, d, pub)
            sample = ([i.outpoint for t in txs for i in t.inputs]
                      + [(t.hash(), 0) for t in txs] + absent)

            async def stage() -> dict:
                out = {"hash": await state.get_unspent_outputs_hash(),
                       "sql": [bool(v) for v in await state.outpoints_exist(
                           sample, "unspent_outputs")]}
                if resident:
                    idx = state.resident_indexes()["unspent_outputs"]
                    out["dev"] = [bool(v)
                                  for v in idx.contains_batch(sample)]
                    out["shadow"] = [
                        bool(v) for v in idx.shadow_contains_batch(sample)]
                return out

            stages = {"before": await stage()}
            await mine_block(txs)
            stages["accept"] = await stage()
            if resident:
                await state.remove_blocks(4)    # drop the leaf block
                stages["reorg"] = await stage()
                # the re-mined header gets a fresh timestamp, so its
                # coinbase outpoint differs: the three-way parity is the
                # byte-identity check of this stage
                await mine_block(txs)
                stages["reaccept"] = await stage()
            stages["n_sample"] = len(sample)
            return stages
        finally:
            state.close()

    start_diff = difficulty.START_DIFFICULTY
    try:
        # both paths must see identical per-block timestamps or the block
        # hashes (and the coinbase outpoints) diverge
        clock.freeze(1_700_000_000)
        serial = asyncio.run(scenario(False))
        clock.freeze(1_700_000_000)
        resident = asyncio.run(scenario(True))
    finally:
        clock.reset()
        difficulty.START_DIFFICULTY = start_diff
    return serial, resident


@pytest.mark.parametrize("stage", ["accept", "reorg", "reaccept"])
def test_accept_three_way_differential(accept_stages, stage):
    """Resident probe == host shadow == SQL at every stage of accept,
    forced reorg and re-accept; the fused path leaves the same unspent
    set as the serial path, and the reorg returns it EXACTLY."""
    serial, resident = accept_stages
    got = resident[stage]
    assert len(got["sql"]) == resident["n_sample"]
    assert got["dev"] == got["shadow"] == got["sql"]
    assert not any(got["sql"][-16:])            # absent stays absent
    n = (resident["n_sample"] - 16) // 2
    if stage == "reorg":
        assert got["hash"] == resident["before"]["hash"]
        assert got["sql"] == resident["before"]["sql"]
        assert all(got["sql"][:n]) and not any(got["sql"][n:])
    else:
        assert not any(got["sql"][:n]) and all(got["sql"][n:2 * n])
    if stage == "accept":
        assert got["hash"] == serial["accept"]["hash"]
        assert got["sql"] == serial["accept"]["sql"]
        assert got["hash"] != resident["before"]["hash"]


def test_apply_block_and_reorg_rollback_roundtrip():
    """Block accept applies (created, spent) in one batched call; a reorg
    rollback applies the inverse and must restore the exact pre-block
    membership, twins included."""
    genesis = [_op(i) for i in range(16)]
    idx = DeviceUtxoIndex(genesis)
    before = idx.contains_batch(genesis + [_op(100), _op(101)]).tolist()

    created = [_op(100), _op(101)]
    spent = [_op(0), _op(1), _op(2)]
    idx.apply_block(created, spent)
    assert list(idx.contains_batch(spent)) == [False, False, False]
    assert list(idx.contains_batch(created)) == [True, True]

    # rollback: the spent set is re-created, the created set removed
    idx.apply_block(spent, created)
    after = idx.contains_batch(genesis + [_op(100), _op(101)]).tolist()
    assert after == before
    assert len(idx) == len(genesis)
