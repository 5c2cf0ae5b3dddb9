"""The chain a block-accept run pushes, all of it from ``--seed``, built
off line: no node and no JAX backend has a hand in it.

    heights 1-3   the base: a coinbase, one transaction 1 -> ``fan_out``,
                  ``fan_out`` transactions -> ``fan_out * per_output``
                  outputs, one a lane, each lane a key of its own
    height 4      the warm block
    heights 5..   ``valid_blocks`` full blocks for the window: transaction
                  i of a block spends the output lane i got in the block
                  before and pays it whole (fees zero) to lane i's key, so
                  a block is ``lanes`` signatures nobody has seen over
                  ``lanes`` live outputs

and the forged pushes, each whole but for its one fault, its merkle root
and proof of work made again:

    forged_sig    the *twin* of a height (the same inputs, paid to the
                  next lane's key, so no signature of it is ever in a
                  true block and no cached verdict carries over) with one
                  bit of ``s`` flipped in one seeded lane.  The unforged
                  twin is kept beside it: the reference has to call that
                  one valid, which shows the flipped bit is the only fault
    forged_spend  the true block of a height in which one seeded lane's
                  transaction spends, rightly signed, the output that
                  lane's transaction two blocks back already spent

The bytes are the program's own transaction and header codecs (an input
of the run, not an answer: ``chainref`` parses them again on its own).
Signatures are OpenSSL's through ``cryptography`` (RFC 6979, so the same
seed signs the same bytes), about 100 times faster than the repo's
pure-Python signer, the lanes shared out over worker processes (a lane's
chain of spends needs no other lane); proof of work at the protocol's START_DIFFICULTY is
searched by the repo's C++ library over a few threads, else by
``powref.lowest_hit``.  Timestamps are ``timestamp_base + height``: whole
seconds, one apart, years before any run, so every hash follows from the
seed alone.
"""

from __future__ import annotations

import hashlib
import random
import time
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor
from dataclasses import dataclass, field
from multiprocessing import get_context

from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec, utils

from upow_tpu.core import point_to_string
from upow_tpu.core.header import BlockHeader
from upow_tpu.core.merkle import merkle_root
from upow_tpu.core.rewards import get_block_reward
from upow_tpu.core.tx import CoinbaseTx, Tx, TxInput, TxOutput

from . import powref
from .manifest import BenchError

#: order of the P-256 group, for keys and for keeping a flipped ``s`` in
#: range (the curve is the configuration's, stated in its file)
CURVE_N = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551
GENESIS_PREVIOUS = (30_06_2005).to_bytes(32, "little").hex()
DIFFICULTY = "6.0"          # START_DIFFICULTY, every height under 100
SEARCH_CHUNK = 1 << 19      # nonces one native call scans


@dataclass
class Push:
    """One ``push_block`` request and what the fixture claims of it."""
    name: str
    kind: str            # base | warm | valid | forged_sig | forged_spend
    height: int
    content: str         # header hex
    txs: list            # full transaction hex, coinbase not among them
    valid: bool
    forged: dict = field(default_factory=dict)   # what was altered


@dataclass
class Fixture:
    base: list           # heights 1-3, for the device=cpu child
    setup: list          # the warm block
    window: list         # the window's pushes in order, forged among them
    twins: list          # the forged_sig pushes' unforged twins
    miner_address: str
    lanes: int
    signer: str
    searcher: str
    seconds: dict        # how long each part of the build took


def _scalar(seed: int, tag: str, i: int) -> int:
    h = hashlib.sha256(f"upow-bench/{seed}/{tag}/{i}".encode()).digest()
    return int.from_bytes(h, "big") % (CURVE_N - 1) + 1


class _Key:
    """One P-256 key: OpenSSL's handle, the address as the program
    spells it, and a signer of raw bytes."""

    _RFC6979 = ec.ECDSA(hashes.SHA256(), deterministic_signing=True)

    def __init__(self, d: int):
        self._key = ec.derive_private_key(d, ec.SECP256R1())
        n = self._key.public_key().public_numbers()
        self.address = point_to_string((n.x, n.y))

    def sign(self, message: bytes) -> tuple:
        return utils.decode_dss_signature(
            self._key.sign(message, self._RFC6979))


def _split(rng: random.Random, amount: int, n: int) -> list:
    """``n`` positive amounts from the seed that add up to ``amount``."""
    if amount < 2 * n:
        raise BenchError(f"cannot split {amount} into {n} outputs")
    weights = [rng.randint(1 << 10, 1 << 11) for _ in range(n)]
    total = sum(weights)
    parts = [max(1, amount * w // total) for w in weights]
    parts[-1] += amount - sum(parts)
    return parts


def _spend(outpoint: tuple, signer: _Key, *outputs, flip=None):
    """A signed one-input transaction of the program's own classes.
    ``outputs``: (address, amount).  ``flip``: a bit of ``s`` to invert
    after signing."""
    tx = Tx([TxInput(*outpoint)], [TxOutput(a, v) for a, v in outputs])
    r, s = signer.sign(bytes.fromhex(tx.hex(False)))
    if flip is not None:
        s ^= 1 << flip
        if not 0 < s < CURVE_N:
            raise BenchError("the flipped s left the group's range")
    tx.inputs[0].signature = (r, s)
    return tx


class _Searcher:
    """Proof of work for one header: the lowest nonce that meets the
    target, by the C++ library over a few threads, else by hashlib."""

    def __init__(self, workers: int):
        from upow_tpu import native

        self._native = native if native.load() is not None else None
        self.workers = max(1, workers)
        self.name = (f"upow_tpu.native.pow_search x{self.workers} threads"
                     if self._native else
                     f"hashlib x{self.workers} processes")
        self._pool = ThreadPoolExecutor(self.workers) \
            if self._native else None

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown()

    def lowest(self, prefix: bytes, previous_hash: str) -> int:
        want, allowed = powref.target(previous_hash, DIFFICULTY)
        if self._native is None:
            hit = powref.lowest_hit(prefix, 0, 1 << 32, previous_hash,
                                    DIFFICULTY, workers=self.workers)
            if hit < 0:
                raise BenchError("no nonce of 2^32 meets the target")
            return hit
        charset = len(allowed) if allowed else 16
        for wave in range(0, 1 << 32, SEARCH_CHUNK * self.workers):
            starts = [wave + k * SEARCH_CHUNK for k in range(self.workers)]
            hits = list(self._pool.map(
                lambda lo: self._native.pow_search(
                    prefix, want, charset, lo,
                    min(SEARCH_CHUNK, (1 << 32) - lo)), starts))
            for hit in hits:            # in range order: first is lowest
                if hit is not None:
                    return hit
        raise BenchError("no nonce of 2^32 meets the target")


def _lane_chains(job: tuple) -> dict:
    """The transactions of lanes [lo, hi) at every height of the plan (a
    lane's chain of spends needs no other lane, so the lanes are built
    side by side in worker processes).  ``held``: the outpoints these
    lanes hold before the first height.  Gives {"true": {height: [hex]},
    "twin": {height: [hex]}, "forged": {(kind, height): hex}}."""
    seed, lo, hi, lanes, held, amounts, first, last, plan = job
    # one key past the end: a twin pays the next lane's key
    keys = [_Key(_scalar(seed, "lane", i % lanes)) for i in range(lo, hi + 1)]
    held = {first - 1: held}
    out = {"true": {}, "twin": {}, "forged": {}}

    def pay(height, k, to, flip=None, back=1):
        return _spend(held[height - back][k], keys[k],
                      (keys[to].address, amounts[k]), flip=flip)

    for height in range(first, last + 1):
        true = [pay(height, k, k) for k in range(hi - lo)]
        out["true"][height] = [tx.hex() for tx in true]
        if height in plan["twins"]:
            out["twin"][height] = [pay(height, k, k + 1).hex()
                                   for k in range(hi - lo)]
            lane, bit = plan["twins"][height]
            if lo <= lane < hi:
                out["forged"]["forged_sig", height] = pay(
                    height, lane - lo, lane - lo + 1, flip=bit).hex()
        lane = plan["respends"].get(height)
        if lane is not None and lo <= lane < hi:
            out["forged"]["forged_spend", height] = pay(
                height, lane - lo, lane - lo, back=3).hex()
        held[height] = [(tx.hash(), 0) for tx in true]
        held.pop(height - 4, None)
    return out


def build(seed: int, sizes: dict, workers: int = 4, say=None) -> Fixture:
    """The whole fixture.  ``sizes``: fan_out, per_output, valid_blocks,
    forge_within (a forged push goes ahead of one of the window's first
    so many valid blocks, never the first), timestamp_base."""
    fan, per = int(sizes["fan_out"]), int(sizes["per_output"])
    lanes, n_valid = fan * per, int(sizes["valid_blocks"])
    within = int(sizes["forge_within"])
    base_ts = int(sizes["timestamp_base"])
    if not 3 <= within <= n_valid:
        raise BenchError(f"forge_within {within} outside [3, {n_valid}]")
    rng = random.Random(0xB10C0000 + seed)
    took, t0 = {}, time.time()
    miner = _Key(_scalar(seed, "miner", 0))
    addresses = [_Key(_scalar(seed, "lane", i)).address
                 for i in range(lanes)]
    took["keys_s"] = time.time() - t0
    searcher = _Searcher(workers)
    tip = {"hash": None, "height": 0}

    def mine(name, kind, txs, valid=True, forged=None, on=None) -> Push:
        """A block of ``txs`` (hex) on the tip, or on ``on``, a height's
        parent, for a block that is not to become the tip."""
        parent = on or tip
        height = parent["height"] + 1
        head = BlockHeader(parent["hash"] or GENESIS_PREVIOUS,
                           miner.address, merkle_root(txs),
                           base_ts + height, 60, 0)
        if parent["hash"] is not None:
            head.nonce = searcher.lowest(head.prefix_bytes(),
                                         parent["hash"])
        if on is None:
            tip.update(height=height, hash=hashlib.sha256(
                head.tobytes()).hexdigest())
        return Push(name, kind, height, head.hex(), txs, valid,
                    forged or {})

    try:
        t0 = time.time()
        base = [mine("base-1", "base", [])]
        coinbase = CoinbaseTx(tip["hash"], miner.address,
                              get_block_reward(1))
        fan_tx = _spend((coinbase.hash(), 0), miner, *[
            (miner.address, v)
            for v in _split(rng, get_block_reward(1), fan)])
        base.append(mine("base-2", "base", [fan_tx.hex()]))
        mids = [_spend((fan_tx.hash(), j), miner, *[
            (addresses[j * per + k], v) for k, v in enumerate(
                _split(rng, fan_tx.outputs[j].amount, per))])
            for j in range(fan)]
        base.append(mine("base-3", "base", [m.hex() for m in mids]))
        took["base_s"] = time.time() - t0
        # the plan, all of it drawn before a lane is built: a forged push
        # goes ahead of the true block of its height; amounts never
        # change from here on (fees zero)
        first, last = 4, 4 + n_valid
        at = rng.sample(range(1, within), 2)
        sig_at, spend_at = (first + 1 + n for n in at)
        plan = {"twins": {sig_at: (rng.randrange(lanes),
                                   rng.randrange(200))},
                "respends": {spend_at: rng.randrange(lanes)}}
        held = [(m.hash(), k) for m in mids for k in range(per)]
        amounts = [o.amount for m in mids for o in m.outputs]
        t0 = time.time()
        n_jobs = max(1, min(workers, lanes // 8))
        step = -(-lanes // n_jobs)
        jobs = [(seed, lo, min(lo + step, lanes), lanes,
                 held[lo:lo + step], amounts[lo:lo + step], first, last,
                 plan) for lo in range(0, lanes, step)]
        if n_jobs == 1:
            parts = [_lane_chains(jobs[0])]
        else:
            with ProcessPoolExecutor(
                    n_jobs, mp_context=get_context("spawn")) as pool:
                parts = list(pool.map(_lane_chains, jobs))
        built = {which: {h: [t for part in parts for t in part[which][h]]
                         for h in parts[0][which]}
                 for which in ("true", "twin")}
        forged = {k: v for part in parts for k, v in part["forged"].items()}
        took["lanes_s"] = time.time() - t0

        t0 = time.time()
        setup, window, twins = [], [], []

        def forged_sig(name, height):
            lane, bit = plan["twins"][height]
            twin = built["twin"][height]
            bad = list(twin)
            bad[lane] = forged["forged_sig", height]
            what = {"lane": lane, "field": "s", "bit": bit,
                    "tx_hash_unforged": hashlib.sha256(
                        bytes.fromhex(twin[lane])).hexdigest()}
            twins.append(mine(name + "-twin", "twin", twin, on=dict(tip)))
            return mine(name, "forged_sig", bad, valid=False, forged=what,
                        on=dict(tip))

        setup.append(mine("warm", "warm", built["true"][first]))
        for n in range(n_valid):
            height = tip["height"] + 1
            if height == sig_at:
                window.append(forged_sig("forged_sig", height))
            if height == spend_at:
                lane = plan["respends"][height]
                txs = list(built["true"][height])
                txs[lane] = forged["forged_spend", height]
                window.append(mine(
                    "forged_spend", "forged_spend", txs, valid=False,
                    forged={"lane": lane, "field": "input",
                            "spent_at_height": height - 2}, on=dict(tip)))
            window.append(mine(f"valid-{n}", "valid",
                               built["true"][height]))
        took["headers_and_pow_s"] = time.time() - t0
    finally:
        searcher.close()
    fixture = Fixture(base, setup, window, twins, miner.address, lanes,
                      "OpenSSL (cryptography, RFC 6979)", searcher.name,
                      took)
    if say:
        say(f"[fixture] {lanes} lanes, {len(window)} window pushes "
            f"({n_valid} valid, forged_sig at height {sig_at}, "
            f"forged_spend at {spend_at}), signer {fixture.signer} in "
            f"{len(jobs)} processes, proof of work by {fixture.searcher}; "
            + " ".join(f"{k}={v:.2f}" for k, v in took.items()))
    return fixture
