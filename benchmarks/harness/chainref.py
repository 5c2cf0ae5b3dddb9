"""The plain reference for block acceptance: what a uPow node that keeps
upstream's rules does with a pushed block, written from the wire formats
down and importing nothing of the program.

A push is the header's hex and the full hex of every transaction but the
coinbase, exactly the bytes ``push_block`` carries.  ``Chain.push`` says
whether the block is to be acknowledged and, if so, applies it to a plain
dict of unspent outputs; ``judge`` says the same and changes nothing.

Rules (upstream manager.py ``check_block`` / ``create_block``,
transaction.py ``verify``), as far as the deployment
``configs/validator-2mb.json`` reaches them (regular transactions with
compressed keys and no message, no inodes, heights under 100):

* header: 108 bytes, version 2; its previous hash is the tip's; its
  sha256 meets the target the tip's hash sets at START_DIFFICULTY 6.0
  (``powref``; the first block has no tip and needs none); tip's
  timestamp < its timestamp <= now; its address is the first block's
  (no inode is registered, so only the genesis key may mine);
* body: at most MAX_BLOCK_SIZE_HEX hex chars; the merkle field is the
  sha256 over the transactions' sha256, in the order of their raw bytes;
* every input is an unspent output and is spent once in the block; a
  transaction's outputs are positive and add up to no more than its
  inputs; each input is signed by the key its output was paid to: ECDSA
  on P-256 over the sha256 of the signing bytes (version to outputs) or,
  upstream's fallback, of their hex text.  OpenSSL verifies, through
  ``cryptography``, in worker processes where a pool is given;
* applied: inputs leave the set, outputs enter it under the
  transaction's sha256, and the coinbase (reward of six coins plus fees,
  to the header's address) under its own.
"""

from __future__ import annotations

import functools
import hashlib
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context

from . import powref

MAX_BLOCK_SIZE_HEX = 4096 * 1024      # upstream constants.py
START_DIFFICULTY = "6.0"              # manager.py, every height under 100
BLOCK_REWARD = 6 * 100_000_000        # smallest units, first 1,576,800 blocks
B58 = "123456789ABCDEFGHJKLMNPQRSTUVWXYZabcdefghijkmnopqrstuvwxyz"


class Refused(Exception):
    """The block is not to be acknowledged; the text says why."""


@functools.lru_cache(maxsize=1 << 16)
def b58encode(data: bytes) -> str:
    n, out = int.from_bytes(data, "big"), ""
    while n:
        n, r = divmod(n, 58)
        out = B58[r] + out
    return "1" * (len(data) - len(data.lstrip(b"\0"))) + out


def parse_tx(raw: bytes) -> dict:
    """One regular transaction off the wire: version(1) | inputs(1) |
    (tx hash 32, index 1, type 1)* | outputs(1) | (key 33, amount length
    1, amount LE, type 1)* | specifier(1)=0 | (r 32 LE, s 32 LE)*."""
    version, n_in, at = raw[0], raw[1], 2
    if version != 3:
        raise Refused(f"transaction version {version}, not 3")
    inputs = []
    for _ in range(n_in):
        if raw[at + 33]:
            raise Refused("input type is not regular")
        inputs.append((raw[at:at + 32].hex(), raw[at + 32]))
        at += 34
    n_out, at = raw[at], at + 1
    outputs = []
    for _ in range(n_out):
        key, size = raw[at:at + 33], raw[at + 33]
        amount = int.from_bytes(raw[at + 34:at + 34 + size], "little")
        if raw[at + 34 + size]:
            raise Refused("output type is not regular")
        outputs.append((key, amount))
        at += 35 + size
    signing, specifier = raw[:at], raw[at]
    if specifier != 0:
        raise Refused("a message or a coinbase, which this deployment "
                      "does not push")
    rest = raw[at + 1:]
    if len(rest) % 64 or not rest:
        raise Refused("signatures are not whole")
    signatures = [(int.from_bytes(rest[k:k + 32], "little"),
                   int.from_bytes(rest[k + 32:k + 64], "little"))
                  for k in range(0, len(rest), 64)]
    if len(signatures) not in (1, len(inputs)) or not inputs:
        raise Refused("signatures do not pair with inputs")
    if len(signatures) == 1:
        signatures *= len(inputs)
    return {"txid": hashlib.sha256(raw).hexdigest(), "inputs": inputs,
            "outputs": outputs, "signing": signing,
            "signatures": signatures}


def coinbase_raw(block_hash: str, address: bytes, amount: int) -> bytes:
    """Upstream coinbase_transaction.py: version 2 for a compressed key,
    one input (the block's hash, index 0), specifier 36."""
    size = (amount.bit_length() + 7) // 8
    return (bytes([2, 1]) + bytes.fromhex(block_hash) + bytes([0, 0, 1])
            + address + bytes([size]) + amount.to_bytes(size, "little")
            + bytes([0, 36]))


def verify_signatures(items: list) -> list:
    """[(key 33 bytes as upstream compresses it: 42 | 43 then x LE, r, s,
    signing bytes)] -> [bool], by OpenSSL."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec, utils

    algorithm, curve = ec.ECDSA(hashes.SHA256()), ec.SECP256R1()
    out = []
    for key, r, s, signing in items:
        try:
            if key[0] not in (42, 43) or len(key) != 33:
                raise ValueError("not a compressed key")
            public = ec.EllipticCurvePublicKey.from_encoded_point(
                curve, bytes([key[0] - 40]) + key[:0:-1])
            der = utils.encode_dss_signature(r, s)
        except ValueError:
            out.append(False)
            continue
        for message in (signing, signing.hex().encode()):
            try:
                public.verify(der, message, algorithm)
            except InvalidSignature:
                continue
            out.append(True)
            break
        else:
            out.append(False)
    return out


class Verifier:
    """``verify_signatures`` over ``workers`` spawned processes."""

    def __init__(self, workers: int, chunk: int = 1024):
        self.chunk = chunk
        self._pool = ProcessPoolExecutor(
            workers, mp_context=get_context("spawn")) if workers > 1 \
            else None

    def __call__(self, items: list) -> list:
        if self._pool is None:
            return verify_signatures(items)
        parts = [items[k:k + self.chunk]
                 for k in range(0, len(items), self.chunk)]
        return [ok for part in self._pool.map(verify_signatures, parts)
                for ok in part]

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def fingerprint(utxo: dict) -> str:
    """sha256 over the sorted unspent outputs, each with the key it was
    paid to (as the address the node stores) and its amount: one output
    that differs in any of the four gives another fingerprint."""
    h = hashlib.sha256()
    for (txid, index), (address, amount) in sorted(utxo.items()):
        h.update(f"{txid}:{index}:{address}:{amount}\n".encode())
    return h.hexdigest()


class Chain:
    """Tip, height and unspent outputs {(tx hash, index): (address as
    base58 text, amount)}, from nothing."""

    def __init__(self, verify=verify_signatures):
        self.verify = verify
        self.tip = None
        self.height = 0
        self.tip_timestamp = 0
        self.genesis_address = None
        self.utxo: dict = {}
        self._keys: dict = {}      # outpoint -> the 33 key bytes

    def state(self) -> dict:
        return {"height": self.height, "tip": self.tip,
                "utxo_fingerprint": fingerprint(self.utxo),
                "utxo_count": len(self.utxo)}

    def judge(self, content_hex: str, tx_hexes: list, now: float):
        """What applying the block would do, or ``Refused``."""
        try:
            head = powref.parse_header(content_hex)
        except ValueError as e:
            raise Refused(str(e))
        if self.tip is not None:
            if head["previous_hash"] != self.tip:
                raise Refused("previous hash is not the tip")
            if not powref.satisfies(powref.digest_hex(content_hex),
                                    *powref.target(self.tip,
                                                   START_DIFFICULTY)):
                raise Refused("proof of work misses the target")
            if head["address"] != self.genesis_address:
                raise Refused("only the genesis key may mine: no inode")
        if not self.tip_timestamp < head["timestamp"] <= now:
            raise Refused("timestamp not after the tip's and not past now")
        if sum(len(t) for t in tx_hexes) > MAX_BLOCK_SIZE_HEX:
            raise Refused("block is too big")
        raws = sorted(bytes.fromhex(t) for t in tx_hexes)
        merkle = hashlib.sha256(b"".join(
            hashlib.sha256(raw).digest() for raw in raws)).hexdigest()
        if merkle != head["merkle_root"]:
            raise Refused("merkle root does not match")
        try:
            txs = [parse_tx(raw) for raw in raws]
        except IndexError:
            raise Refused("a transaction ends before its fields do")
        spent, checks, fees = set(), [], 0
        for tx in txs:
            paid_in = 0
            for outpoint, (r, s) in zip(tx["inputs"], tx["signatures"]):
                if outpoint not in self.utxo or outpoint in spent:
                    raise Refused(f"input {outpoint} is not unspent")
                spent.add(outpoint)
                paid_in += self.utxo[outpoint][1]
                checks.append((self._keys[outpoint], r, s, tx["signing"]))
            paid_out = sum(amount for _key, amount in tx["outputs"])
            if paid_out > paid_in or \
                    any(amount <= 0 for _key, amount in tx["outputs"]):
                raise Refused(f"transaction {tx['txid']} pays out more "
                              "than it spends, or nothing")
            fees += paid_in - paid_out
        if not all(self.verify(checks)):
            raise Refused("a signature does not verify")
        block_hash = powref.digest_hex(content_hex)
        reward = coinbase_raw(block_hash, head["address"],
                              BLOCK_REWARD + fees)
        created = [((tx["txid"], k), key, amount) for tx in txs
                   for k, (key, amount) in enumerate(tx["outputs"])]
        created.append(((hashlib.sha256(reward).hexdigest(), 0),
                        head["address"], BLOCK_REWARD + fees))
        return {"hash": block_hash, "head": head, "spent": spent,
                "created": created}

    def push(self, content_hex: str, tx_hexes: list, now: float) -> tuple:
        """(acknowledged, why not): judged, and applied if sound."""
        try:
            block = self.judge(content_hex, tx_hexes, now)
        except Refused as e:
            return False, str(e)
        for outpoint in block["spent"]:
            del self.utxo[outpoint]
            del self._keys[outpoint]
        for outpoint, key, amount in block["created"]:
            self.utxo[outpoint] = (b58encode(key), amount)
            self._keys[outpoint] = key
        if self.tip is None:
            self.genesis_address = block["head"]["address"]
        self.tip, self.height = block["hash"], self.height + 1
        self.tip_timestamp = block["head"]["timestamp"]
        return True, ""
