"""Seeded load fixtures shared by the load generators and the tests:
signature-check tuples with a known valid/invalid mix, and an in-memory
chain fanned out into spendable leaf outputs with the spends that
consume them.
"""

from __future__ import annotations

import hashlib


def pipeline_verify_fixture(n_txs: int, n_unique: int = 128,
                            invalid_every: int = 13, rng_base: int = 9100):
    """Per-tx signature-check tuples (the txverify check shape:
    ``(digest, digest_hexform, sig, pub)``) with a deterministic mix of
    valid and invalid signatures — every ``invalid_every``-th check
    carries a corrupted ``s``, which fails BOTH verify passes (raw and
    hex-form digest) exactly like a forged wire signature would.
    ``n_unique`` keypairs/messages tiled to ``n_txs``, so a large
    fixture costs ``n_unique`` signings."""
    from ..core import curve

    base = []
    for i in range(n_unique):
        d, pub = curve.keygen(rng=rng_base + i)
        m = (b"vp" + i.to_bytes(4, "big")) * 6
        digest = hashlib.sha256(m).digest()
        hexform = hashlib.sha256(m.hex().encode()).digest()
        base.append((digest, hexform, curve.sign(m, d), pub))
    checks = []
    for i in range(n_txs):
        digest, hexform, (r, s), pub = base[i % n_unique]
        if invalid_every and i % invalid_every == 0:
            s = s - 1 if s > 1 else s + 1
        checks.append((digest, hexform, (r, s), pub))
    return checks


async def chain_with_utxo_fanout(n_fan: int, n_per: int, rng_key: int):
    """3-block in-memory chain fanning one coinbase into n_fan x n_per
    spendable leaf outputs — shared scaffolding for the loadgen
    funded-wallet fixture and the accept tests.
    Returns (state, manager, d, pub, addr, mids, mine_block) where
    ``mine_block(txs)`` accepts one more block and returns its accept
    seconds.  Mutates process-global difficulty/clock state; callers
    must ``clock.reset()`` when done (the loadgen harness and readpath
    scenario do)."""
    import time
    from decimal import Decimal

    from ..core import clock, curve, difficulty, point_to_string
    from ..core.header import BlockHeader
    from ..core.merkle import merkle_root
    from ..core.tx import Tx, TxInput, TxOutput
    from ..mine.engine import MiningJob, mine
    from ..state import ChainState
    from ..verify import BlockManager

    difficulty.START_DIFFICULTY = Decimal("1.0")
    genesis_prev = (18_884_643).to_bytes(32, "little").hex()

    state = ChainState()
    manager = BlockManager(state)
    d, pub = curve.keygen(rng=rng_key)
    addr = point_to_string(pub)
    pub_of = lambda _i: pub  # noqa: E731

    async def mine_block(txs):
        clock.advance(60)
        diff, last = await manager.calculate_difficulty()
        prev = last["hash"] if last else genesis_prev
        header = BlockHeader(
            previous_hash=prev, address=addr, merkle_root=merkle_root(txs),
            timestamp=clock.timestamp(), difficulty_x10=int(diff * 10),
            nonce=0)
        if last:
            r = mine(MiningJob(header.prefix_bytes(), prev, diff),
                     "python", batch=1 << 14, ttl=600)
            header.nonce = r.nonce
        errors = []
        t0 = time.perf_counter()
        ok = await manager.create_block(header.hex(), txs, errors=errors)
        dt = time.perf_counter() - t0
        assert ok, errors
        return dt

    await mine_block([])                      # block 1: coinbase to addr
    coin = (await state.get_spendable_outputs(addr))[0]
    reward = coin.amount

    per = reward // n_fan
    outs = [TxOutput(addr, per)] * (n_fan - 1)
    outs = outs + [TxOutput(addr, reward - per * (n_fan - 1))]
    fan = Tx([coin], outs).sign([d], pub_of)
    await mine_block([fan])

    mids = []
    for j in range(n_fan):
        amt = fan.outputs[j].amount
        sub = amt // n_per
        souts = [TxOutput(addr, sub)] * (n_per - 1)
        souts = souts + [TxOutput(addr, amt - sub * (n_per - 1))]
        mids.append(Tx([TxInput(fan.hash(), j)], souts).sign([d], pub_of))
    await mine_block(mids)
    return state, manager, d, pub, addr, mids, mine_block


def leaf_spends(parents, addr, d, pub):
    """One 1-in-1-out spend per output of each parent tx (the loadgen
    push_tx payload generator)."""
    from ..core.tx import Tx, TxInput, TxOutput

    out = []
    for m in parents:
        h = m.hash()
        for k, o in enumerate(m.outputs):
            out.append(Tx([TxInput(h, k)], [TxOutput(addr, o.amount)])
                       .sign([d], lambda _i: pub))
    return out
