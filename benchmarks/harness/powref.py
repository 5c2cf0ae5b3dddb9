"""The plain reference for proof of work: ``hashlib`` and nothing of the
program.

uPow's rule (upstream manager.py ``check_block_is_valid``): the sha256 of
the header, in hex, starts with the last ``int(difficulty)`` hex chars of
the previous block's hash, and for a fractional difficulty the next char
is among the first ``ceil(16 * (1 - frac))`` of ``0123456789abcdef``.
Header v2, 108 bytes: version(1)=2 | previous hash(32) | address(33) |
merkle root(32) | timestamp(4 LE) | difficulty*10 (2 LE) | nonce(4 LE).
"""

from __future__ import annotations

import hashlib
import math
from concurrent.futures import ProcessPoolExecutor
from decimal import Decimal
from multiprocessing import get_context

HEX = "0123456789abcdef"


def target(previous_hash: str, difficulty) -> tuple:
    """(required prefix, allowed next chars or None)."""
    d = Decimal(str(difficulty))
    k = int(d)
    frac = d - k
    prefix = previous_hash[-k:] if k else previous_hash
    allowed = HEX[:math.ceil(16 * (1 - frac))] if frac > 0 else None
    return prefix, allowed


def satisfies(digest_hex: str, prefix: str, allowed) -> bool:
    if not digest_hex.startswith(prefix):
        return False
    return allowed is None or digest_hex[len(prefix)] in allowed


def parse_header(content_hex: str) -> dict:
    raw = bytes.fromhex(content_hex)
    if len(raw) != 108 or raw[0] != 2:
        raise ValueError(f"not a 108-byte v2 header ({len(raw)} bytes)")
    return {"previous_hash": raw[1:33].hex(), "address": raw[33:66],
            "merkle_root": raw[66:98].hex(),
            "timestamp": int.from_bytes(raw[98:102], "little"),
            "difficulty_x10": int.from_bytes(raw[102:104], "little"),
            "nonce": int.from_bytes(raw[104:108], "little"),
            "prefix": raw[:104]}


def miner_merkle(tx_hashes: list) -> str:
    """What upstream's miner puts in the header: sha256 over the raw
    pending-transaction hashes, joined."""
    return hashlib.sha256(
        b"".join(bytes.fromhex(h) for h in tx_hashes)).hexdigest()


def digest_hex(content_hex: str) -> str:
    return hashlib.sha256(bytes.fromhex(content_hex)).hexdigest()


def check_block(content_hex: str, job: dict) -> list:
    """Faults of a pushed block against the job it was served: []
    means it is a valid answer.  ``job``: previous_hash, difficulty,
    pending_hashes, address_bytes."""
    try:
        h = parse_header(content_hex)
    except ValueError as e:
        return [str(e)]
    faults = []
    if h["previous_hash"] != job["previous_hash"]:
        faults.append("previous hash is not the served tip")
    if h["merkle_root"] != miner_merkle(job["pending_hashes"]):
        faults.append("merkle root is not that of the served hashes")
    if h["difficulty_x10"] != int(Decimal(str(job["difficulty"])) * 10):
        faults.append(f"difficulty field {h['difficulty_x10']} is not the "
                      f"served {job['difficulty']}")
    if h["address"] != job["address_bytes"]:
        faults.append("address is not the miner's")
    digest = digest_hex(content_hex)
    want = target(job["previous_hash"], job["check_difficulty"])
    if not satisfies(digest, *want):
        faults.append(f"sha256 {digest[:16]}.. misses the target {want}")
    return faults


def _scan(args) -> int:
    prefix_bytes, lo, hi, want, allowed = args
    base = hashlib.sha256(prefix_bytes)
    for nonce in range(lo, hi):
        h = base.copy()
        h.update(nonce.to_bytes(4, "little"))
        if satisfies(h.hexdigest(), want, allowed):
            return nonce
    return -1


def lowest_hit(prefix_bytes: bytes, lo: int, hi: int, previous_hash: str,
               difficulty, workers: int = 8) -> int:
    """The lowest nonce in [lo, hi) whose header meets the target, or -1:
    a plain loop over ``hashlib``, split over ``workers`` processes."""
    want, allowed = target(previous_hash, difficulty)
    if hi <= lo:
        return -1
    workers = max(1, min(workers, (hi - lo) // 4096 or 1))
    step = -(-(hi - lo) // workers)
    parts = [(prefix_bytes, a, min(a + step, hi), want, allowed)
             for a in range(lo, hi, step)]
    if workers == 1:
        return _scan(parts[0])
    with ProcessPoolExecutor(workers, mp_context=get_context("spawn")) as ex:
        for hit in ex.map(_scan, parts):   # in range order: first is lowest
            if hit >= 0:
                return hit
    return -1
