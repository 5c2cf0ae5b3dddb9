"""The fixture (``harness/blockfixture.py``): the same seed gives the
same keys, amounts and block hashes; every block is full; timestamps are
one apart; a forged push differs from what it forges in the stated field
and nothing else."""

import hashlib

import pytest

from harness import blockfixture, chainref, powref

SIZES = {"fan_out": 4, "per_output": 4, "valid_blocks": 5,
         "forge_within": 3, "timestamp_base": 1_700_000_000}


@pytest.fixture(scope="module")
def fixture():
    return blockfixture.build(11, SIZES, workers=2)


def _every(fx):
    return fx.base + fx.setup + fx.window + fx.twins


def test_the_same_seed_gives_the_same_chain_and_another_seed_another(
        fixture):
    again = blockfixture.build(11, SIZES, workers=1)
    assert [(p.name, p.content, p.txs) for p in _every(again)] == \
        [(p.name, p.content, p.txs) for p in _every(fixture)]
    assert again.miner_address == fixture.miner_address
    other = blockfixture.build(12, SIZES, workers=2)
    assert other.miner_address != fixture.miner_address
    assert not {t for p in _every(other) for t in p.txs} & \
        {t for p in _every(fixture) for t in p.txs}
    # a seed as large as the driver's
    big = blockfixture.build(2**31 + 12345, dict(SIZES, valid_blocks=3),
                             workers=2)
    assert len(big.window) == 5


def test_widths_heights_and_timestamps(fixture):
    assert fixture.lanes == 16
    assert [len(p.txs) for p in fixture.base] == [0, 1, 4]
    assert all(len(p.txs) == 16 for p in fixture.setup + fixture.window
               + fixture.twins)
    valid = [p for p in fixture.base + fixture.setup + fixture.window
             if p.valid]
    assert [p.height for p in valid] == list(range(1, 10))
    stamps = [powref.parse_header(p.content)["timestamp"] for p in valid]
    assert stamps == [SIZES["timestamp_base"] + h for h in range(1, 10)]
    # a chain: each header names the hash of the one before
    for prev, nxt in zip(valid, valid[1:]):
        assert powref.parse_header(nxt.content)["previous_hash"] == \
            powref.digest_hex(prev.content)
        assert powref.satisfies(powref.digest_hex(nxt.content),
                                *powref.target(powref.digest_hex(
                                    prev.content), "6.0"))
    # every lane a key of its own, the same in every block
    paid = [[chainref.parse_tx(bytes.fromhex(t))["outputs"][0][0]
             for t in p.txs] for p in fixture.window if p.kind == "valid"]
    assert len(set(paid[0])) == 16 and all(row == paid[0] for row in paid)
    kinds = [p.kind for p in fixture.window]
    assert kinds.count("forged_sig") == kinds.count("forged_spend") == 1
    assert kinds[0] == "valid" and kinds.count("valid") == 5
    # a forged push goes ahead of the true block of its height
    for i, p in enumerate(fixture.window):
        if not p.valid:
            assert fixture.window[i + 1].valid
            assert fixture.window[i + 1].height == p.height <= 4 + 3


def test_a_forged_signature_differs_from_its_twin_in_one_bit_of_s(fixture):
    twins = {t.name: t for t in fixture.twins}
    forged = [p for p in fixture.setup + fixture.window
              if p.kind == "forged_sig"]
    assert len(forged) == 1
    for p in forged:
        twin = twins[p.name + "-twin"]
        assert twin.height == p.height and len(twin.txs) == len(p.txs)
        differ = [(a, b) for a, b in zip(p.txs, twin.txs) if a != b]
        assert len(differ) == 1
        bad, good = (bytes.fromhex(t) for t in differ[0])
        assert bad[:-32] == good[:-32]          # all but s
        s_bad, s_good = (int.from_bytes(r[-32:], "little")
                         for r in (bad, good))
        assert s_bad ^ s_good == 1 << p.forged["bit"]
        assert p.txs.index(differ[0][0]) == p.forged["lane"]
        assert hashlib.sha256(good).hexdigest() == \
            p.forged["tx_hash_unforged"]
        # no signature of the twin is ever in a true block
        true = {t for q in fixture.setup + fixture.window if q.valid
                for t in q.txs}
        assert not true & set(twin.txs)
        # and the header is made again: merkle root and proof of work
        assert p.content != twin.content
        assert powref.parse_header(p.content)["merkle_root"] != \
            powref.parse_header(twin.content)["merkle_root"]


def test_a_forged_spend_differs_from_the_true_block_in_one_input(fixture):
    at = next(i for i, p in enumerate(fixture.window)
              if p.kind == "forged_spend")
    bad, true = fixture.window[at], fixture.window[at + 1]
    differ = [(a, b) for a, b in zip(bad.txs, true.txs) if a != b]
    assert len(differ) == 1
    assert bad.txs.index(differ[0][0]) == bad.forged["lane"]
    a, b = (chainref.parse_tx(bytes.fromhex(t)) for t in differ[0])
    assert a["outputs"] == b["outputs"] and a["inputs"] != b["inputs"]
    # rightly signed by the lane's key: only the input is at fault
    earlier = [p for p in fixture.base + fixture.setup + fixture.window
               if p.valid and p.height == bad.forged["spent_at_height"]][0]
    spent_there = {chainref.parse_tx(bytes.fromhex(t))["inputs"][0]
                   for t in earlier.txs}
    assert a["inputs"][0] in spent_there


def test_sizes_that_cannot_be_built_are_refused():
    with pytest.raises(blockfixture.BenchError):
        blockfixture.build(1, dict(SIZES, forge_within=2))
    with pytest.raises(blockfixture.BenchError):
        blockfixture.build(1, dict(SIZES, forge_within=9))
