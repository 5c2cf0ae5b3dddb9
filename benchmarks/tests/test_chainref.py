"""The plain reference of block acceptance (``harness/chainref.py``)
against a small fixture: the valid chain is taken whole, each forged
push is refused and leaves the state as it was, and the fingerprint
tells one output from another.  It imports nothing of the program."""

import ast
import os
import time

import pytest

from harness import blockfixture, chainref
from harness.manifest import BENCH

SIZES = {"fan_out": 4, "per_output": 4, "valid_blocks": 5,
         "forge_within": 3, "timestamp_base": 1_700_000_000}


@pytest.fixture(scope="module")
def fixture():
    return blockfixture.build(11, SIZES, workers=2)


def _chain_after(pushes):
    chain = chainref.Chain()
    for p in pushes:
        assert chain.push(p.content, p.txs, time.time()) == (True, "")
    return chain


def test_the_valid_chain_is_taken_whole(fixture):
    valid = [p for p in fixture.base + fixture.setup + fixture.window
             if p.valid]
    chain = _chain_after(valid)
    state = chain.state()
    assert state["height"] == len(valid) == 3 + 1 + 5
    assert state["tip"] == chainref.powref.digest_hex(valid[-1].content)
    # one output a lane, and a coinbase a block but the first, whose
    # six coins the fan-out spent
    assert state["utxo_count"] == fixture.lanes + len(valid) - 1
    assert sum(v for _a, v in chain.utxo.values()) == \
        chainref.BLOCK_REWARD * len(valid)


@pytest.mark.parametrize("kind,why", [
    ("forged_sig", "a signature does not verify"),
    ("forged_spend", "is not unspent"),
])
def test_each_forgery_is_refused_and_the_state_unmoved(fixture, kind, why):
    pushes = fixture.base + fixture.setup + fixture.window
    at = next(i for i, p in enumerate(pushes) if p.kind == kind)
    chain = _chain_after([p for p in pushes[:at] if p.valid])
    before, utxo = chain.state(), dict(chain.utxo)
    ok, said = chain.push(pushes[at].content, pushes[at].txs, time.time())
    assert not ok and why in said
    assert chain.state() == before and chain.utxo == utxo
    # the true block of that height follows
    assert chain.push(pushes[at + 1].content, pushes[at + 1].txs,
                      time.time()) == (True, "")


def test_the_unforged_twin_of_a_forged_signature_is_sound(fixture):
    pushes = fixture.base + fixture.setup + fixture.window
    at = next(i for i, p in enumerate(pushes) if p.kind == "forged_sig")
    chain = _chain_after([p for p in pushes[:at] if p.valid])
    (twin,) = fixture.twins
    assert twin.name == "forged_sig-twin"
    chain.judge(twin.content, twin.txs, time.time())       # no Refused
    with pytest.raises(chainref.Refused, match="signature"):
        chain.judge(pushes[at].content, pushes[at].txs, time.time())


@pytest.mark.parametrize("break_it,why", [
    (lambda c, t: (c[:-8] + "00000000", t), "proof of work"),
    (lambda c, t: (c, t[:-1]), "merkle root"),
    (lambda c, t: (c[:2] + "00" * 32 + c[66:], t), "previous hash"),
    (lambda c, t: (c, t[:-1] + [t[-1][:-2]]), "merkle root"),
])
def test_a_block_altered_anywhere_is_refused(fixture, break_it, why):
    chain = _chain_after(fixture.base + fixture.setup)
    block = fixture.window[0]
    content, txs = break_it(block.content, list(block.txs))
    ok, said = chain.push(content, txs, time.time())
    assert not ok and why in said


def test_a_timestamp_past_now_or_not_past_the_tip_is_refused(fixture):
    chain = _chain_after(fixture.base)
    (warm,) = fixture.setup
    ok, said = chain.push(warm.content, warm.txs,
                          SIZES["timestamp_base"] + warm.height - 1)
    assert not ok and "timestamp" in said
    chain.tip_timestamp = SIZES["timestamp_base"] + warm.height
    ok, said = chain.push(warm.content, warm.txs, time.time())
    assert not ok and "timestamp" in said


def test_the_fingerprint_differs_when_one_output_differs(fixture):
    chain = _chain_after(fixture.base)
    base = chainref.fingerprint(chain.utxo)
    assert base == chain.state()["utxo_fingerprint"]
    (outpoint, (address, amount)) = sorted(chain.utxo.items())[0]
    for other in ({**chain.utxo, outpoint: (address, amount + 1)},
                  {**chain.utxo, outpoint: (address[:-1] + "1", amount)},
                  {k: v for k, v in chain.utxo.items() if k != outpoint},
                  {**chain.utxo, (outpoint[0], 200): (address, amount)}):
        assert chainref.fingerprint(other) != base


def test_the_pool_gives_the_verdicts_of_the_plain_call(fixture):
    chain = _chain_after(fixture.base + fixture.setup)
    block = fixture.window[0]
    txs = [chainref.parse_tx(bytes.fromhex(t)) for t in block.txs]
    items = [(chain._keys[tx["inputs"][0]], *tx["signatures"][0],
              tx["signing"]) for tx in txs]
    items[3] = (items[3][0], items[3][1], items[3][2] ^ 2, items[3][3])
    want = chainref.verify_signatures(items)
    assert want.count(False) == 1 and not want[3]
    with chainref.Verifier(2, chunk=5) as verify:
        assert verify(items) == want


def test_the_reference_imports_nothing_of_the_program():
    for name in ("chainref.py", "powref.py"):
        with open(os.path.join(BENCH, "harness", name)) as f:
            tree = ast.parse(f.read())
        names = [a.name for n in ast.walk(tree)
                 if isinstance(n, ast.Import) for a in n.names]
        names += [n.module or "" for n in ast.walk(tree)
                  if isinstance(n, ast.ImportFrom)]
        assert not [n for n in names if n.split(".")[0]
                    in ("upow_tpu", "jax", "numpy")], (name, names)
