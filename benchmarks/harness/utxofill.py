"""The filler of a deployment-sized unspent-output table, all of it from
``--seed``, as numpy columns: distinct random 32-byte transaction hashes,
output index 0, addresses drawn from ``n_addresses`` seeded ones (chains
reuse addresses), seeded amounts.  Nothing here imports the program: the
rows go into the node's own ``unspent_outputs`` table by plain SQL, and
the same columns give the digest the table is held against afterwards.

An address is the base58 text of 33 bytes (42 or 43, then 32 seeded
bytes): the shape and the 45 characters of a compressed-key address.  No
filler row is ever spent, so no key stands behind one.

The digest is order-free: a 64-bit hash a row (hash, index, address,
amount), the rows' sum mod 2^64 and their xor.  Both are group
operations, so the digest of a table is the filler's combined with the
live rows', and a table can be read in any order, in chunks.
"""

from __future__ import annotations

import sqlite3
import time
import zlib

import numpy as np

_B58 = np.frombuffer(
    b"123456789ABCDEFGHJKLMNPQRSTUVWXYZabcdefghijkmnopqrstuvwxyz", np.uint8)
_M = (0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F, 0x165667B19E3779F9,
      0x27D4EB2F165667C5, 0xFF51AFD7ED558CCD)
CHUNK = 1 << 18


def base58_33(raw: np.ndarray) -> np.ndarray:
    """(N,) ``S45`` base58 texts of (N, 33) bytes whose first byte is 42
    or 43 (so each has 45 digits): long division by 58^5, five digits a
    pass, on 32-bit limbs."""
    n = len(raw)
    padded = np.zeros((n, 36), np.uint8)
    padded[:, 3:] = raw
    limbs = padded.view(">u4").astype(np.uint64)          # (N, 9)
    digits = np.empty((n, 45), np.uint8)
    group = np.uint64(58 ** 5)
    for g in range(8, -1, -1):
        rem = np.zeros(n, np.uint64)
        for k in range(9):
            cur = (rem << np.uint64(32)) | limbs[:, k]
            limbs[:, k], rem = np.divmod(cur, group)
        for d in range(4, -1, -1):
            rem, digits[:, 5 * g + d] = np.divmod(rem, np.uint64(58))
    return np.ascontiguousarray(_B58[digits]).view("S45").reshape(n)


def addresses(seed: int, n: int) -> np.ndarray:
    """(n,) ``S45``: the seeded addresses the filler draws from."""
    rng = np.random.default_rng([seed, 0xADD2])
    raw = rng.integers(0, 256, (n, 33), dtype=np.uint8)
    raw[:, 0] = 42 + (raw[:, 0] & 1)
    return base58_33(raw)


def columns(seed: int, count: int, n_addresses: int) -> dict:
    """The filler's rows, sorted by hash: ``hash`` (count, 32) uint8,
    ``address`` (count,) ``S45``, ``amount`` (count,) int64; the output
    index of every row is 0.  A hash that came twice (2^-200 a run) is
    drawn again."""
    rng = np.random.default_rng([seed, 0xF111])
    raw = rng.integers(0, 256, (count, 32), dtype=np.uint8)
    keys = raw.view("S32").reshape(count)
    order = np.argsort(keys, kind="stable")
    raw, keys = raw[order], keys[order]
    if count > 1 and (keys[1:] == keys[:-1]).any():
        return columns(seed + 1, count, n_addresses)
    table = addresses(seed, n_addresses)
    return {"hash": raw,
            "address": table[rng.integers(0, n_addresses, count)],
            "amount": rng.integers(1, 1 << 40, count, dtype=np.int64)}


def _row_hashes(lanes: np.ndarray, idx: np.ndarray, address_crc: np.ndarray,
                amount: np.ndarray) -> np.ndarray:
    """(N,) uint64, one a row: the four u64 lanes of the hash, the
    index, the address's crc32 and the amount, each times an odd
    constant, folded."""
    with np.errstate(over="ignore"):
        h = lanes[:, 0] * np.uint64(_M[0])
        for k in range(1, 4):
            h = (h ^ (h >> np.uint64(29))) + lanes[:, k] * np.uint64(_M[k])
        h ^= (idx.astype(np.uint64) + np.uint64(1)) * np.uint64(_M[4])
        h = (h ^ (h >> np.uint64(31))) * np.uint64(_M[0])
        h += address_crc.astype(np.uint64) * np.uint64(_M[1])
        h ^= amount.astype(np.uint64) * np.uint64(_M[2])
        return (h ^ (h >> np.uint64(33))) * np.uint64(_M[3])


def _fold(hashes: np.ndarray) -> tuple:
    with np.errstate(over="ignore"):
        return (int(hashes.sum(dtype=np.uint64)),
                int(np.bitwise_xor.reduce(hashes)) if len(hashes) else 0)


def combine(*digests: tuple) -> tuple:
    """The digest of the rows of several sets together."""
    total, xor, count = 0, 0, 0
    for s, x, c in digests:
        total, xor, count = (total + s) & (2 ** 64 - 1), xor ^ x, count + c
    return total, xor, count


def digest_of_columns(cols: dict) -> tuple:
    """(sum, xor, count) of the filler's rows."""
    lanes = np.ascontiguousarray(cols["hash"]).view("<u8").reshape(-1, 4)
    crc = np.fromiter(map(zlib.crc32, cols["address"].tolist()),
                      np.uint32, len(lanes))
    return _fold(_row_hashes(lanes, np.zeros(len(lanes), np.uint64), crc,
                             cols["amount"])) + (len(lanes),)


def digest_of_rows(rows) -> tuple:
    """(sum, xor, count) of rows given as (hash hex, index, address,
    amount) tuples: the reference's live set, a few thousand."""
    rows = list(rows)
    if not rows:
        return 0, 0, 0
    hashes, idxs, addrs, amounts = zip(*rows)
    lanes = np.frombuffer(bytes.fromhex("".join(hashes)),
                          "<u8").reshape(-1, 4)
    crc = np.fromiter((zlib.crc32((a or "").encode()) for a in addrs),
                      np.uint32, len(rows))
    return _fold(_row_hashes(lanes, np.array(idxs, np.uint64), crc,
                             np.array(amounts, np.int64))) + (len(rows),)


def digest_of_table(db: str) -> tuple:
    """(sum, xor, count) of every row of ``unspent_outputs`` in the
    sqlite file, streamed in chunks."""
    con = sqlite3.connect(f"file:{db}?mode=ro", uri=True)
    total = (0, 0, 0)
    try:
        cur = con.execute(
            "SELECT tx_hash, idx, CAST(COALESCE(address, '') AS BLOB),"
            " amount FROM unspent_outputs")
        while True:
            rows = cur.fetchmany(CHUNK)
            if not rows:
                return total
            hashes, idxs, addrs, amounts = zip(*rows)
            lanes = np.frombuffer(bytes.fromhex("".join(hashes)),
                                  "<u8").reshape(-1, 4)
            crc = np.fromiter(map(zlib.crc32, addrs), np.uint32, len(rows))
            total = combine(total, _fold(_row_hashes(
                lanes, np.array(idxs, np.uint64), crc,
                np.array(amounts, np.int64))) + (len(rows),))
    finally:
        con.close()


def load(db: str, cols: dict) -> dict:
    """The filler into the node's ``unspent_outputs`` table, while no
    node has the file open: rows in hash order (the primary key's tree
    is appended to, never split), the address index dropped for the load
    and made again after it, one transaction, the write-ahead log
    folded back at the end.  Seconds of each part, for the ``[fill]``
    lines."""
    took = {}
    t0 = time.time()
    count = len(cols["hash"])
    hashes = np.frombuffer(cols["hash"].tobytes().hex().encode(),
                           "S64").astype("U64")
    addrs = cols["address"].astype("U45")
    took["rows_s"] = time.time() - t0
    con = sqlite3.connect(db)
    try:
        t0 = time.time()
        con.execute("PRAGMA synchronous=OFF")
        con.execute("DROP INDEX IF EXISTS unspent_address_idx")
        con.execute("BEGIN")
        for lo in range(0, count, CHUNK):
            hi = min(count, lo + CHUNK)
            n = hi - lo
            con.executemany(
                "INSERT INTO unspent_outputs (tx_hash, idx, address, "
                "amount) VALUES (?,?,?,?)",
                zip(hashes[lo:hi].tolist(), [0] * n, addrs[lo:hi].tolist(),
                    cols["amount"][lo:hi].tolist()))
        con.commit()
        took["insert_s"] = time.time() - t0
        t0 = time.time()
        con.execute("CREATE INDEX IF NOT EXISTS unspent_address_idx ON "
                    "unspent_outputs (address)")
        con.commit()
        con.execute("PRAGMA wal_checkpoint(TRUNCATE)")
        took["index_s"] = time.time() - t0
        took["rows"] = con.execute(
            "SELECT COUNT(*) FROM unspent_outputs").fetchone()[0]
    finally:
        con.close()
    return took
