"""Chain state storage: the role of the reference's ``Database`` singleton.

The reference couples all chain state to asyncpg/PostgreSQL through an
~80-method ``Database`` class (database.py, 1654 LoC).  This framework
keeps the same *logical* schema (schema.sql: blocks, transactions, six
UTXO-class tables, pending tables) but:

* backs it with stdlib ``sqlite3`` (file or ``:memory:``) — a zero-dep,
  durable, transactional store; the storage API is the seam where a
  Postgres backend could be swapped in for reference interop,
* keeps amounts as **int smallest-units** end to end (the reference's
  NUMERIC/Decimal appears only in governance ratio math, which is
  Decimal-exact here too — core/rewards.py),
* avoids the reference's LIKE-'%hex%' address scans (database.py:864-937)
  by materializing an ``address`` column on outputs and a JSON address
  array on transactions,
* exposes the *state-view* callbacks the pure consensus kernel needs
  (core/tx.py ``AddressResolver``) instead of letting codecs import the
  database (the circular-import knot SURVEY.md §1 flags).

All methods are ``async def`` to slot into the asyncio node shell; sqlite
calls are short and synchronous under a process-wide connection with WAL.
Block acceptance is wrapped in one transaction (``atomic``) — the
serializable-retry loop the reference hand-rolls (database.py:640-672)
comes for free from sqlite's locking.
"""

from __future__ import annotations

import json
import os
import sqlite3
import struct
import threading
import time as _time
from contextlib import asynccontextmanager
from decimal import Decimal
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

from ..core.clock import timestamp as now_ts
from ..core.codecs import OutputType, TransactionType
from ..core.constants import MAX_BLOCK_SIZE_HEX, SMALLEST
from ..core.rewards import round_up_decimal
from ..core.tx import CoinbaseTx, Tx, TxInput, tx_from_hex
from ..logger import get_logger
from .. import telemetry

log = get_logger("state")

AnyTx = Union[Tx, CoinbaseTx]

_SCHEMA = """
CREATE TABLE IF NOT EXISTS blocks (
    id INTEGER PRIMARY KEY,
    hash TEXT UNIQUE NOT NULL,
    content TEXT NOT NULL,
    address TEXT NOT NULL,
    random INTEGER NOT NULL,
    difficulty TEXT NOT NULL,
    reward INTEGER NOT NULL,
    timestamp INTEGER NOT NULL
);
CREATE TABLE IF NOT EXISTS transactions (
    block_hash TEXT NOT NULL,
    tx_hash TEXT UNIQUE NOT NULL,
    tx_hex TEXT NOT NULL,
    inputs_addresses TEXT NOT NULL,
    outputs_addresses TEXT NOT NULL,
    outputs_amounts TEXT NOT NULL,
    fees INTEGER NOT NULL
);
CREATE INDEX IF NOT EXISTS tx_block_hash_idx ON transactions (block_hash);
CREATE TABLE IF NOT EXISTS pending_transactions (
    tx_hash TEXT UNIQUE NOT NULL,
    tx_hex TEXT NOT NULL,
    inputs_addresses TEXT NOT NULL,
    fees INTEGER NOT NULL,
    propagation_time INTEGER NOT NULL
);
CREATE TABLE IF NOT EXISTS pending_spent_outputs (
    tx_hash TEXT NOT NULL,
    idx INTEGER NOT NULL
);
CREATE TABLE IF NOT EXISTS unspent_outputs (
    tx_hash TEXT NOT NULL,
    idx INTEGER NOT NULL,
    address TEXT,
    amount INTEGER NOT NULL,
    is_stake INTEGER NOT NULL DEFAULT 0,
    PRIMARY KEY (tx_hash, idx)
);
CREATE INDEX IF NOT EXISTS unspent_address_idx ON unspent_outputs (address);
"""

# The five governance tables share one row shape (outpoint + address).
_GOV_TABLES = (
    "inode_registration_output",
    "validator_registration_output",
    "validators_voting_power",
    "delegates_voting_power",
    "inodes_ballot",
    "validators_ballot",
)

for _t in _GOV_TABLES:
    _SCHEMA += f"""
CREATE TABLE IF NOT EXISTS {_t} (
    tx_hash TEXT NOT NULL,
    idx INTEGER NOT NULL,
    address TEXT,
    amount INTEGER NOT NULL DEFAULT 0,
    PRIMARY KEY (tx_hash, idx)
);
CREATE INDEX IF NOT EXISTS {_t}_address_idx ON {_t} (address);
"""

# OutputType -> table routing (reference database.py:524-580)
_OUTPUT_TABLE = {
    OutputType.REGULAR: "unspent_outputs",
    OutputType.STAKE: "unspent_outputs",
    OutputType.UN_STAKE: "unspent_outputs",
    OutputType.INODE_REGISTRATION: "inode_registration_output",
    OutputType.VALIDATOR_REGISTRATION: "validator_registration_output",
    OutputType.VALIDATOR_VOTING_POWER: "validators_voting_power",
    OutputType.DELEGATE_VOTING_POWER: "delegates_voting_power",
    OutputType.VOTE_AS_VALIDATOR: "inodes_ballot",
    OutputType.VOTE_AS_DELEGATE: "validators_ballot",
}

# TransactionType -> which table its *inputs* spend from
# (reference database.py:589-622 remove_outputs partitioning)
_INPUT_TABLE = {
    TransactionType.INODE_DE_REGISTRATION: "inode_registration_output",
    TransactionType.VOTE_AS_VALIDATOR: "validators_voting_power",
    TransactionType.VOTE_AS_DELEGATE: "delegates_voting_power",
    TransactionType.REVOKE_AS_VALIDATOR: "inodes_ballot",
    TransactionType.REVOKE_AS_DELEGATE: "validators_ballot",
}


from .views import StateViews


def _host_peak_mb() -> float:
    """This process's peak resident memory in MB (``ru_maxrss``, which
    Linux counts in KB).  The index's build says it before and after
    itself: a TPU runtime's own mappings are in both."""
    import resource

    return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0,
                 1)


#: frames of write-ahead log from which a commit wakes the keeper:
#: sqlite's own ``wal_autocheckpoint`` default, so the cadence is the one
#: the connection had (a full block's commit is thousands of frames: one
#: checkpoint a block; mempool writes gather until they make a thousand)
_CHECKPOINT_PAGES = 1000
#: frames past which the committing thread checkpoints itself, as sqlite
#: did before the keeper: three and a half full blocks over a 4 M-row
#: table (18,2xx frames each).  The log begins again only when a
#: transaction's first write finds every frame folded, so a keeper that
#: is still copying then (commits that follow one another faster than a
#: checkpoint takes, a long reader that pins the frames) lets it grow; a
#: longer log slows every read that goes through it, and it must not
#: grow without end
_INLINE_CHECKPOINT_PAGES = 1 << 16


def _checkpoint(db: sqlite3.Connection, sp) -> None:
    """One passive checkpoint on ``db``: the log's committed frames
    copied into the main file as far as no reader pins them, the file
    synced.  Never waits for a lock of sqlite's: with another
    connection's checkpoint in flight the pragma answers ``busy`` at
    once, has done nothing and is not counted.  ``sp`` is the
    ``state.wal_checkpoint`` span that times it, given the pragma's
    row."""
    busy, frames, backfilled = db.execute(
        "PRAGMA wal_checkpoint(PASSIVE)").fetchone()
    if sp is not None:
        sp.fields.update(frames=frames, backfilled=backfilled, busy=busy)
    if not busy:
        telemetry.update((("state.checkpoints", 1),
                          ("state.checkpoint_frames", backfilled)))


class _WalKeeper:
    """The thread that folds a sqlite file's write-ahead log back into
    the file, on a connection of its own: what sqlite's automatic
    checkpoint did inside the writer's ``commit()``, behind it.

    A commit is durable once its frames are in the log and the log is
    synced (``synchronous`` FULL, untouched); copying them into the main
    file moves frames that are durable already, so nothing acknowledged
    waits for it.  The writer wakes the keeper after a commit that
    leaves the log at ``_CHECKPOINT_PAGES`` frames or more; the keeper
    runs ``PRAGMA wal_checkpoint(PASSIVE)`` (one C call, the interpreter
    lock released) and does nothing else.  It never touches the writer's
    connection, nor the writer its own."""

    def __init__(self, path: str):
        self._path = path
        # the wal-index: sqlite's ``-shm`` file, there for as long as a
        # connection has the file open in WAL mode
        self._shm = os.open(path + "-shm", os.O_RDONLY)
        self._wake = threading.Event()
        self._stopping = False
        #: held around a checkpoint, the keeper's or the writer's own:
        #: a writer that has to checkpoint waits for the one in flight
        self.checkpointing = threading.Lock()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="wal-keeper")
        self._thread.start()

    def log_frames(self) -> int:
        """Frames in the log now: ``mxFrame`` of the wal-index header
        (sqlite.org/walformat.html, the u32 at byte 16, in the host's
        byte order).  Not the ``-wal`` file's size: a log that restarts
        from its first frame keeps its length, so the file says the
        longest the log has ever been."""
        return struct.unpack("=I", os.pread(self._shm, 4, 16))[0]

    def wake(self) -> None:
        self._wake.set()

    def stop(self) -> None:
        """End the thread, a checkpoint in flight finished and its
        connection closed."""
        self._stopping = True
        self._wake.set()
        self._thread.join()
        os.close(self._shm)

    def _run(self) -> None:
        db = sqlite3.connect(self._path)
        try:
            while True:
                self._wake.wait()
                self._wake.clear()
                if self._stopping:
                    return
                try:
                    with self.checkpointing, telemetry.request_trace(
                            "state.wal_checkpoint", inline=False) as sp:
                        _checkpoint(db, sp)
                # the log stands and the next commit asks again
                except sqlite3.Error:
                    log.exception("write-ahead log checkpoint failed")
        finally:
            db.close()


class ChainState(StateViews):
    """One chain's durable state.  ``path=None`` -> in-memory (tests).

    The backend-independent consensus views (balance/stake aggregation,
    the active-inode cascade, fee math, fingerprints) live in
    :class:`StateViews`; this class implements the sqlite storage
    primitives under them.  :class:`upow_tpu.state.pg.PgChainState` is
    the PostgreSQL implementation of the same seam.

    **The write-ahead log's checkpoint.**  A file-backed state that is
    the file's sole writer (``path`` given, ``sole_writer`` true: the
    node) sets ``wal_autocheckpoint=0`` on its connection and hands the
    checkpoint to a :class:`_WalKeeper` thread: the cadence is sqlite's
    own (a commit that leaves 1,000 frames or more in the log, so once a
    full block), but the accept lock and the peer's acknowledgement no
    longer wait for 18,000 pages to be copied into the main file.
    Durability is as it was: ``synchronous`` stays FULL and
    ``journal_mode`` WAL, every commit stays where it was and syncs the
    log before it returns, and a process that dies between a commit and
    its checkpoint loses nothing (the next open reads the log).  Should
    a commit find the log past ``_INLINE_CHECKPOINT_PAGES`` frames (the
    keeper cannot keep up: a long reader on another connection pins the
    frames), the committing thread checkpoints itself, as sqlite did,
    and counts ``state.checkpoint_inline``.  ``path=None`` has no log
    and ``sole_writer=False`` (the wallet CLI beside a node) keeps
    sqlite's automatic checkpoint: neither starts a thread.
    ``close()`` joins the keeper and leaves the log folded."""

    def __init__(self, path: Optional[str] = None,
                 device_index: bool = False,
                 sole_writer: bool = True):
        self.path = path or ":memory:"
        # sole_writer=False (e.g. a wallet CLI reading a file the node is
        # writing) disables the 50 ms rate limit on the data_version
        # check: every memo read verifies no other connection committed,
        # so a secondary reader never serves stale amounts/addresses into
        # fee/coinbase computation.
        self.sole_writer = sole_writer
        self.db = sqlite3.connect(self.path)
        self.db.row_factory = sqlite3.Row
        wal = bool(path) and self.db.execute(
            "PRAGMA journal_mode=WAL").fetchone()[0] == "wal"
        self.db.execute("PRAGMA foreign_keys=OFF")
        self.db.executescript(_SCHEMA)
        self.db.commit()
        #: frames in the write-ahead log at the last commit (the gauge
        #: ``upow_state_wal_pages``); None where no keeper runs
        self.wal_pages: Optional[int] = None
        self._keeper: Optional[_WalKeeper] = None
        if wal and sole_writer:
            self.db.execute("PRAGMA wal_autocheckpoint=0")
            self._keeper = _WalKeeper(self.path)
        # emission audit sidecar (reference: emission_details.json pickledb)
        self.emission_path = (
            os.path.splitext(path)[0] + ".emission.json" if path else None
        )
        # optional device-resident membership prefilter per UTXO table
        # (SURVEY.md §2.2; the block-accept hot path's spend check)
        if device_index:
            self.enable_device_index()
        # decoded-mempool cache: several read paths walk every pending tx
        # (balance/stake with check_pending, builder guards); decoding the
        # whole mempool hex per call is the reference's O(mempool)
        # anti-pattern (database.py:1138-1205) — decode once per intake.
        self._pending_cache: Optional[Dict[str, Tx]] = None
        self._pending_stamp: tuple = (-1, -1, -1)
        self._pending_gen = 0  # bumped on every LOCAL mempool mutation
        # reorg mempool re-injection (mempool subsystem policy; the Node
        # turns it on from MempoolConfig — off at the library level so
        # state-only embedders keep the reference rollback semantics)
        self.reinject_reorg_txs = False
        # reorg notification (state/hotcache.py): called with the first
        # removed block id AFTER a remove_blocks rollback commits.  Sync
        # and swarm heal call remove_blocks directly on state, so the
        # read cache's generation hook has to live here rather than on
        # the BlockManager.
        self.on_blocks_removed = None
        # cold-block archive fallthrough (upow_tpu/archive/,
        # docs/ARCHIVE.md): the node attaches an ArchiveReader when
        # ArchiveConfig.dir is set; None keeps every read path exactly
        # on its hot-only query.
        self.archive = None
        from collections import OrderedDict as _OD

        self._amount_cache: "_OD[tuple, object]" = _OD()
        self._data_version = self._db_data_version()
        self._data_version_checked = 0.0

    def _db_data_version(self) -> int:
        return self.db.execute("PRAGMA data_version").fetchone()[0]

    def _amount_cache_get(self, key):
        """Cached output amount/address, guarded against writes from
        OTHER connections on the same db file (the wallet CLI opens its
        own ChainState): sqlite's data_version counter bumps whenever a
        different connection commits, and any such commit may have
        deleted source txs — so the whole memo is dropped then.

        The version check is rate-limited to one PRAGMA per 50 ms — at
        ~25k lookups per 8k-tx block the per-hit pragma cost halved the
        warm accept rate.  The window only affects SECONDARY processes
        reading a file another process mutates (this connection's own
        deletions invalidate explicitly and see no window); those reads
        race ongoing commits by >=50 ms anyway.
        """
        now = _time.monotonic()
        if not self.sole_writer or now - self._data_version_checked >= 0.05:
            self._data_version_checked = now
            version = self._db_data_version()
            if version != self._data_version:
                self._data_version = version
                self._amount_cache.clear()
                return None
        return self._amount_cache.get(key)

    def _amount_cache_put(self, key, value) -> None:
        self._amount_cache[key] = value
        while len(self._amount_cache) > (1 << 16):
            self._amount_cache.popitem(last=False)

    def _amount_cache_drop(self, tx_hashes) -> None:
        """Forget cached output amounts for deleted txs (see
        get_output_amount: existence must not depend on cache warmth)."""
        gone = set(tx_hashes)
        if gone:
            for key in [k for k in self._amount_cache if k[0] in gone]:
                del self._amount_cache[key]

    async def _pending_decoded(self) -> Dict[str, Tx]:
        # (count, max rowid) detects writes from OTHER connections (the
        # wallet CLI's direct-mempool fallback shares the sqlite file):
        # inserts bump max rowid, deletes drop the count.  The local
        # generation counter covers the one combination they miss —
        # delete-the-newest-then-insert reuses the freed max rowid at an
        # unchanged count (sqlite rowid reuse without AUTOINCREMENT).
        r = self.db.execute(
            "SELECT COUNT(*) AS c, COALESCE(MAX(rowid), 0) AS m"
            " FROM pending_transactions").fetchone()
        stamp = (r["c"], r["m"], self._pending_gen)
        if self._pending_cache is None or self._pending_stamp != stamp:
            rows = self.db.execute(
                "SELECT tx_hash, tx_hex FROM pending_transactions").fetchall()
            self._pending_cache = {
                row["tx_hash"]: tx_from_hex(row["tx_hex"], check_signatures=False)
                for row in rows
            }
            self._pending_stamp = stamp
        return self._pending_cache

    # ------------------------------------------------------ device index --
    def enable_device_index(self) -> None:
        """Mirror every UTXO-class table into a :class:`DeviceUtxoIndex`.

        Maintained incrementally by the output add/remove paths; bulk
        operations (reorg rollback, full replay) rebuild from the tables
        — the index is reconstructible at any height, which is its
        checkpoint/resume story.

        No-op (with a warning) when the jax backend cannot initialize —
        an unreachable device can HANG backend init, and a node must boot and
        validate on the sqlite path rather than wedge here."""
        from ..device.runtime import get_runtime

        if get_runtime().platform() is None:
            import logging

            logging.getLogger("upow_tpu.state").warning(
                "jax backend init hung/failed; device UTXO index disabled "
                "— sqlite membership checks only")
            self._dev_index = None
            return
        from .. import trace
        from .device_index import DeviceUtxoIndex

        self._dev_index = {}
        for table in ("unspent_outputs",) + _GOV_TABLES:
            t0, peak_before = _time.perf_counter(), _host_peak_mb()
            index = DeviceUtxoIndex.from_columns(*self._index_columns(table))
            index.materialize()
            self._dev_index[table] = index
            stats = index.stats()
            trace.event("index_built", table=table,
                        entries=stats["entries"],
                        capacity=stats["capacity"],
                        resident_bytes=stats["resident_bytes"],
                        seconds=round(_time.perf_counter() - t0, 3),
                        host_peak_before_mb=peak_before,
                        host_peak_mb=_host_peak_mb())

    #: rows a fetch of the index's build: the table streams through in
    #: chunks, so the build holds columns and never 4 M row objects
    _INDEX_CHUNK = 1 << 18

    def _index_columns(self, table: str) -> tuple:
        """(fingerprints, check fingerprints, amounts, script hashes) of
        a table's rows as numpy columns, with no Python statement a row:
        each chunk is transposed, its hashes decoded in one
        ``fromhex`` and its addresses hashed through ``map``."""
        import numpy as np

        from .device_index import (check_lanes, fingerprint_lanes,
                                   script_hash_batch)

        cur = self.db.cursor()
        cur.row_factory = None
        cur.execute(f"SELECT tx_hash, idx, COALESCE(amount, 0),"
                    f" CAST(COALESCE(address, '') AS BLOB) FROM {table}")
        parts = [(np.zeros(0, np.uint64), np.zeros(0, np.uint64),
                  np.zeros(0, np.int64), np.zeros(0, np.uint32))]
        while True:
            rows = cur.fetchmany(self._INDEX_CHUNK)
            if not rows:
                break
            hashes, idxs, amounts, addresses = zip(*rows)
            del rows
            lanes = np.frombuffer(bytes.fromhex("".join(hashes)),
                                  dtype="<u8").reshape(-1, 4)
            idx = np.array(idxs, dtype=np.uint64)
            parts.append((fingerprint_lanes(lanes, idx),
                          check_lanes(lanes, idx),
                          np.array(amounts, dtype=np.int64),
                          script_hash_batch(addresses)))
        return tuple(np.concatenate(col) for col in zip(*parts))

    @staticmethod
    def _index_values(outputs) -> tuple:
        """The index's value columns (amount, script hash, height) of a
        block's outputs."""
        import numpy as np

        from .device_index import script_hash_batch

        return (np.array([o.amount for o in outputs], dtype=np.int64),
                script_hash_batch([o.address or "" for o in outputs]),
                np.zeros(len(outputs), dtype=np.uint32))

    def _index_rebuild(self) -> None:
        if self._dev_index is not None:
            self.enable_device_index()

    def close(self):
        # the keeper's connection goes first, so that this one is the
        # file's last and its close folds the log
        if self._keeper is not None:
            self._keeper.stop()
            self._keeper = None
        self.db.close()

    @asynccontextmanager
    async def atomic(self):
        """One transaction around a whole block acceptance.  While it is
        open, the per-method ``_commit()`` calls inside are no-ops — a
        partial block must never become durable (an inner commit would
        make atomic()'s rollback silently keep the committed half:
        accepted block + mempool removals with the spent UTXOs still
        unspent)."""
        with self._index_batch():
            # (the index hears of the block below this ``try``, once it
            # has committed: nothing after the commit may raise)
            self._in_atomic = True
            try:
                self.db.execute("BEGIN")
                yield
                self.db.commit()
                self._committed()
            except BaseException:
                self.db.rollback()
                self._amount_cache.clear()  # may hold rolled-back rows
                self._bump_fees_gen()
                raise
            finally:
                self._in_atomic = False

    def _commit(self) -> None:
        if not getattr(self, "_in_atomic", False):
            self.db.commit()
            self._committed()

    def _committed(self) -> None:
        """After a commit of this connection's: who folds the log.
        Never raises (the commit stands whatever becomes of its
        checkpoint)."""
        keeper = self._keeper
        if keeper is None:
            return
        self.wal_pages = pages = keeper.log_frames()
        if pages > _INLINE_CHECKPOINT_PAGES:
            telemetry.inc("state.checkpoint_inline")
            try:
                with keeper.checkpointing, telemetry.span(
                        "state.wal_checkpoint", inline=True) as sp:
                    _checkpoint(self.db, sp)
            except sqlite3.Error:
                log.exception("write-ahead log checkpoint failed")
        elif pages >= _CHECKPOINT_PAGES:
            keeper.wake()

    # ------------------------------------------------------------- blocks --

    async def add_block(self, block_id: int, block_hash: str, content: str,
                        address: str, nonce: int, difficulty, reward: int,
                        ts: int) -> None:
        self.db.execute(
            "INSERT INTO blocks (id, hash, content, address, random, difficulty,"
            " reward, timestamp) VALUES (?,?,?,?,?,?,?,?)",
            (block_id, block_hash, content, address, nonce, str(difficulty),
             reward, ts),
        )

    @staticmethod
    def _block_dict(r) -> dict:
        return {
            "id": r["id"],
            "hash": r["hash"],
            "content": r["content"],
            "address": r["address"],
            "random": r["random"],
            "difficulty": Decimal(r["difficulty"]),
            "reward": Decimal(r["reward"]) / SMALLEST,
            "timestamp": r["timestamp"],
        }

    @staticmethod
    def _archive_block_dict(b: list) -> dict:
        """Canonical archive block row -> the same dict _block_dict
        builds from a hot row (difficulty is archived as str, reward as
        int smallest-units — identical to the hot column encodings)."""
        return {
            "id": b[0],
            "hash": b[1],
            "content": b[2],
            "address": b[3],
            "random": b[4],
            "difficulty": Decimal(b[5]),
            "reward": Decimal(b[6]) / SMALLEST,
            "timestamp": b[7],
        }

    async def get_block(self, block_hash: str) -> Optional[dict]:
        r = self.db.execute("SELECT * FROM blocks WHERE hash = ?", (block_hash,)).fetchone()
        if r is None and self.archive is not None:
            b = await self.archive.block_by_hash(block_hash)
            return self._archive_block_dict(b) if b else None
        return self._block_dict(r) if r else None

    async def get_block_by_id(self, block_id: int) -> Optional[dict]:
        r = self.db.execute("SELECT * FROM blocks WHERE id = ?", (block_id,)).fetchone()
        if r is None and self.archive is not None:
            b = await self.archive.block_by_height(block_id)
            return self._archive_block_dict(b) if b else None
        return self._block_dict(r) if r else None

    async def get_last_block(self) -> Optional[dict]:
        r = self.db.execute("SELECT * FROM blocks ORDER BY id DESC LIMIT 1").fetchone()
        return self._block_dict(r) if r else None

    async def get_next_block_id(self) -> int:
        r = self.db.execute("SELECT MAX(id) AS m FROM blocks").fetchone()
        return (r["m"] or 0) + 1

    async def get_blocks(self, offset: int, limit: int,
                         tx_details: bool = False,
                         size_capped: bool = False) -> List[dict]:
        """Blocks with embedded full transactions, ordered by id
        (reference database.py:380-408's get_blocks).

        One transactions query for the whole page, grouped host-side —
        a couple of statements per 500-block page instead of 501 (same
        shape as the pg backend's; ``tx_details`` swaps the tx hex for
        explorer-shaped dicts at the reference's per-tx lookup cost,
        database.py:405).  ``size_capped`` truncates the running page
        once the accumulated hex passes 8 full blocks' worth — the HTTP
        serving layer passes it (a 1000-block page of 2 MB blocks must
        not serialize a 2 GB response).  Documented divergence: the
        reference caps INSIDE Database.get_blocks unconditionally,
        which silently truncates its own reorg-window scan; we cap only
        at the wire boundary so internal callers always see the full
        window (and the reorg scan pairs blocks by id, app.py)."""
        rows = self.db.execute(
            "SELECT * FROM blocks WHERE id >= ? ORDER BY id LIMIT ?",
            (offset, limit),
        ).fetchall()
        by_hash: dict = {r["hash"]: [] for r in rows}
        hashes = list(by_hash)
        # chunk the IN list: SQLITE_MAX_VARIABLE_NUMBER is 999 before
        # sqlite 3.32, and the endpoint serves pages up to 1000 blocks
        for lo in range(0, len(hashes), 900):
            chunk = hashes[lo:lo + 900]
            marks = ",".join("?" * len(chunk))
            for t in self.db.execute(
                    f"SELECT block_hash, tx_hash, tx_hex FROM transactions"
                    f" WHERE block_hash IN ({marks})", chunk):
                by_hash[t["block_hash"]].append((t["tx_hash"], t["tx_hex"]))
        entries = [(r["id"], self._block_dict(r), by_hash[r["hash"]])
                   for r in rows]
        if self.archive is not None:
            cov = await self.archive.coverage()
            if cov is not None and offset <= cov[1]:
                # the page reaches into the archived span: overlay
                # archived blocks (hot wins on overlap — same content
                # either way; witness blocks stay hot below the archive
                # horizon, so hot gaps can appear anywhere in the page)
                hot_ids = {e[0] for e in entries}
                for b, atxs in await self.archive.span(
                        offset, offset + limit - 1):
                    if b[0] not in hot_ids:
                        entries.append((b[0], self._archive_block_dict(b),
                                        [(t[1], t[2]) for t in atxs]))
                entries.sort(key=lambda e: e[0])
                entries = entries[:limit]
        out = []
        size = 0
        for _bid, block, txs in entries:
            size += sum(len(h) for _th, h in txs)
            if size_capped and size > MAX_BLOCK_SIZE_HEX * 8:
                break
            block = dict(block)
            block["difficulty"] = float(block["difficulty"])
            block["reward"] = str(block["reward"])
            if tx_details:
                # per-tx lookups are inherent to the explorer shape
                # (fees + per-input amounts need resolution; the
                # reference pays the same, database.py:405).  A tx can
                # vanish mid-page under a concurrent reorg — drop the
                # None instead of embedding null in the response.
                nice = [await self.get_nice_transaction(th)
                        for th, _h in txs]
                tx_list = [t for t in nice if t is not None]
            else:
                tx_list = [h for _th, h in txs]
            out.append({"block": block, "transactions": tx_list})
        return out

    async def remove_blocks(self, from_block_id: int) -> None:
        """Reorg rollback: restore outputs spent by the removed blocks, drop
        the blocks and everything their transactions created
        (reference database.py:146-169)."""
        rows = self.db.execute(
            "SELECT t.tx_hex FROM transactions t JOIN blocks b ON t.block_hash = b.hash"
            " WHERE b.id >= ?", (from_block_id,),
        ).fetchall()
        txs = [tx_from_hex(r["tx_hex"], check_signatures=False) for r in rows]
        from .. import trace

        trace.event("reorg", from_block=from_block_id,
                    removed_txs=len(txs))
        # drop outputs created by removed txs (from whichever table)
        created = [tx.hash() for tx in txs]
        for table in ("unspent_outputs",) + _GOV_TABLES:
            self.db.executemany(
                f"DELETE FROM {table} WHERE tx_hash = ?", [(h,) for h in created]
            )
        # the removals and the restored spends below reach the resident
        # index as one delta a table
        with self._index_batch():
            # O(delta) index maintenance (ISSUE 11): enumerate the removed
            # txs' outputs by class and delta-remove them — already-spent
            # outputs are absent and no-op, matching the blanket SQL DELETE.
            # The restored spends below delta-add through the same hooks, so
            # the full rebuild a reorg used to pay is gone.
            if self._dev_index is not None:
                doomed_by_table: Dict[str, list] = {}
                for tx in txs:
                    h = tx.hash()
                    for index, out in enumerate(tx.outputs):
                        doomed_by_table.setdefault(
                            _OUTPUT_TABLE[out.output_type], []).append((h, index))
                for table, outpoints in doomed_by_table.items():
                    self._index_remove(table, outpoints)
            # restore outputs their inputs had spent — but not outputs of txs
            # that are themselves being removed (reference database.py
            # remove_blocks filters `tx_input.tx_hash not in transactions_hashes`;
            # restoring those would leave orphaned UTXO rows after a reorg of
            # dependent txs and diverge the UTXO fingerprint)
            created_set = set(created)
            restore = [
                tx_input for tx in txs if not tx.is_coinbase
                for tx_input in tx.inputs if tx_input.tx_hash not in created_set
            ]
            await self._restore_spent_outputs(restore)
        self.db.executemany(
            "DELETE FROM transactions WHERE tx_hash = ?", [(h,) for h in created]
        )
        self.db.execute("DELETE FROM blocks WHERE id >= ?", (from_block_id,))
        self._amount_cache_drop(created)
        if self.reinject_reorg_txs:
            # mempool re-injection: txs the losing fork confirmed go
            # back into the pending journal (their spent outputs were
            # just restored above) so the winning fork can mine them
            # instead of silently dropping user transactions.  Skips
            # txs that spend an output of another removed tx (source
            # gone) or conflict with the existing pending overlay.
            for tx in txs:
                if tx.is_coinbase or any(
                        i.tx_hash in created_set for i in tx.inputs):
                    continue
                await self._reinject_pending(tx)
        self._bump_fees_gen()
        self._pending_gen += 1
        self._commit()
        if self.on_blocks_removed is not None:
            self.on_blocks_removed(from_block_id)

    async def _reinject_pending(self, tx) -> bool:
        """INSERT-OR-IGNORE a reorged-out tx back into the journal.
        Returns True when the row (and its spent-output overlay rows)
        actually landed."""
        outpoints = [i.outpoint for i in tx.inputs]
        if await self.get_pending_spent_outpoints(outpoints):
            return False  # conflicts with a live pending tx
        try:
            inputs_addresses = [
                await self.resolve_output_address(i.tx_hash, i.index) or ""
                for i in tx.inputs
            ]
            fees = await self.tx_fees(tx)
        except (ValueError, KeyError, IndexError):
            return False  # source txs unresolvable post-rollback
        cur = self.db.execute(
            "INSERT OR IGNORE INTO pending_transactions (tx_hash, tx_hex,"
            " inputs_addresses, fees, propagation_time) VALUES (?,?,?,?,?)",
            (tx.hash(), tx.hex(), json.dumps(inputs_addresses), fees,
             now_ts()),
        )
        if cur.rowcount == 0:
            return False  # already pending (re-propagated meanwhile)
        self.db.executemany(
            "INSERT INTO pending_spent_outputs (tx_hash, idx) VALUES (?,?)",
            [(i.tx_hash, i.index) for i in tx.inputs],
        )
        from .. import trace

        trace.inc("mempool.reinjected")
        return True

    async def _restore_spent_outputs(self, inputs: List[TxInput]) -> None:
        """Re-materialize spent outputs by decoding their source txs.
        Index delta-adds are gated on the INSERT actually landing
        (OR IGNORE may hit an existing row, e.g. a whitelisted
        historical double-spend restoring one outpoint twice) so the
        resident index never drifts a duplicate ahead of the table."""
        for tx_input in inputs:
            src = await self.get_transaction(tx_input.tx_hash, include_pending=False)
            if src is None:
                continue
            out = src.outputs[tx_input.index]
            table = _OUTPUT_TABLE[out.output_type]
            if table == "unspent_outputs":
                cur = self.db.execute(
                    "INSERT OR IGNORE INTO unspent_outputs (tx_hash, idx, address,"
                    " amount, is_stake) VALUES (?,?,?,?,?)",
                    (tx_input.tx_hash, tx_input.index, out.address, out.amount,
                     int(out.is_stake)),
                )
            else:
                cur = self.db.execute(
                    f"INSERT OR IGNORE INTO {table} (tx_hash, idx, address, amount)"
                    " VALUES (?,?,?,?)",
                    (tx_input.tx_hash, tx_input.index, out.address, out.amount),
                )
            if cur.rowcount > 0:
                self._index_add(table, [(tx_input.tx_hash, tx_input.index)],
                                values=[(out.amount, out.address or "", 0)])

    # ------------------------------------------------------- transactions --

    async def add_transactions(self, txs: Sequence[AnyTx], block_hash: str) -> None:
        rows = []
        for tx in txs:
            inputs_addresses = [] if tx.is_coinbase else [
                await self.resolve_output_address(i.tx_hash, i.index) or ""
                for i in tx.inputs
            ]
            fees = 0 if tx.is_coinbase else await self.tx_fees(tx)
            rows.append((
                block_hash, tx.hash(), tx.hex(),
                json.dumps(inputs_addresses),
                json.dumps([o.address for o in tx.outputs]),
                json.dumps([o.amount for o in tx.outputs]),
                fees,
            ))
        self.db.executemany(
            "INSERT OR REPLACE INTO transactions (block_hash, tx_hash, tx_hex,"
            " inputs_addresses, outputs_addresses, outputs_amounts, fees)"
            " VALUES (?,?,?,?,?,?,?)", rows,
        )

    async def get_transaction(self, tx_hash: str,
                              include_pending: bool = False) -> Optional[AnyTx]:
        r = self.db.execute(
            "SELECT tx_hex FROM transactions WHERE tx_hash = ?", (tx_hash,)
        ).fetchone()
        if r is None and include_pending:
            r = self.db.execute(
                "SELECT tx_hex FROM pending_transactions WHERE tx_hash = ?",
                (tx_hash,),
            ).fetchone()
        if r is None and self.archive is not None:
            hit = await self.archive.tx_by_hash(tx_hash)
            if hit is not None:
                return tx_from_hex(hit[0][2], check_signatures=False)
        return tx_from_hex(r["tx_hex"], check_signatures=False) if r else None

    async def get_transaction_info(self, tx_hash: str) -> Optional[dict]:
        r = self.db.execute(
            "SELECT * FROM transactions WHERE tx_hash = ?", (tx_hash,)
        ).fetchone()
        if r is None:
            if self.archive is not None:
                hit = await self.archive.tx_by_hash(tx_hash)
                if hit is not None:
                    t = hit[0]
                    return {
                        "block_hash": t[0], "tx_hash": t[1],
                        "tx_hex": t[2], "inputs_addresses": t[3],
                        "outputs_addresses": t[4],
                        "outputs_amounts": t[5], "fees": t[6],
                    }
            return None
        return {
            "block_hash": r["block_hash"],
            "tx_hash": r["tx_hash"],
            "tx_hex": r["tx_hex"],
            "inputs_addresses": json.loads(r["inputs_addresses"]),
            "outputs_addresses": json.loads(r["outputs_addresses"]),
            "outputs_amounts": json.loads(r["outputs_amounts"]),
            "fees": r["fees"],
        }

    async def get_block_transactions(self, block_hash: str,
                                     hex_only: bool = False) -> List:
        rows = self.db.execute(
            "SELECT tx_hex FROM transactions WHERE block_hash = ?", (block_hash,)
        ).fetchall()
        if not rows and self.archive is not None:
            # pruned blocks lose their ENTIRE tx set (never split), so
            # an empty hot read is the only case needing fallthrough
            atxs = await self.archive.txs_for_block(block_hash)
            if atxs:
                if hex_only:
                    return [t[2] for t in atxs]
                return [tx_from_hex(t[2], check_signatures=False)
                        for t in atxs]
        if hex_only:
            return [r["tx_hex"] for r in rows]
        return [tx_from_hex(r["tx_hex"], check_signatures=False) for r in rows]

    async def resolve_output_address(self, tx_hash: str, index: int) -> Optional[str]:
        """AddressResolver for the codec's ambiguous-signature relink
        (core/tx.py tx_from_hex).  Memoized with the same
        content-addressed + dropped-on-tx-deletion discipline as
        :func:`get_output_amount` (shared cache, misses not cached)."""
        key = (tx_hash, -1 - index)  # distinct key space from amounts
        addr = self._amount_cache_get(key)
        if addr is not None:
            return addr
        r = self.db.execute(
            "SELECT outputs_addresses FROM transactions WHERE tx_hash = ?",
            (tx_hash,),
        ).fetchone()
        if r is None:
            r = self.db.execute(
                "SELECT tx_hex FROM pending_transactions WHERE tx_hash = ?",
                (tx_hash,),
            ).fetchone()
            if r is None:
                if self.archive is not None:
                    hit = await self.archive.tx_by_hash(tx_hash)
                    if hit is not None:
                        addresses = hit[0][4]
                        addr = (addresses[index]
                                if index < len(addresses) else None)
                        if addr is not None:
                            self._amount_cache_put(key, addr)
                        return addr
                return None
            tx = tx_from_hex(r["tx_hex"], check_signatures=False)
            addr = (tx.outputs[index].address
                    if index < len(tx.outputs) else None)
        else:
            addresses = json.loads(r["outputs_addresses"])
            addr = addresses[index] if index < len(addresses) else None
        if addr is not None:
            self._amount_cache_put(key, addr)
        return addr

    async def get_output_amount(self, tx_hash: str, index: int) -> Optional[int]:
        # content-addressed (tx_hash = sha256(full tx hex), so a hash's
        # outputs can never change), but existence matters: tx_fees
        # returns 0 when the source tx is GONE, and that decision must
        # not depend on cache warmth (consensus-adjacent — it feeds the
        # coinbase miner_amount).  Every path that deletes txs
        # (remove_blocks, pending removals) drops the affected entries.
        key = (tx_hash, index)
        amount = self._amount_cache_get(key)
        if amount is not None:
            return amount
        r = self.db.execute(
            "SELECT outputs_amounts FROM transactions WHERE tx_hash = ?",
            (tx_hash,),
        ).fetchone()
        if r is not None:
            amounts = json.loads(r["outputs_amounts"])
            amount = amounts[index] if index < len(amounts) else None
        else:
            r = self.db.execute(
                "SELECT tx_hex FROM pending_transactions WHERE tx_hash = ?",
                (tx_hash,),
            ).fetchone()
            if r is None:
                if self.archive is not None:
                    hit = await self.archive.tx_by_hash(tx_hash)
                    if hit is not None:
                        amounts = hit[0][5]
                        amount = (amounts[index]
                                  if index < len(amounts) else None)
                        if amount is not None:
                            self._amount_cache_put(key, amount)
                        return amount
                return None
            tx = tx_from_hex(r["tx_hex"], check_signatures=False)
            amount = (tx.outputs[index].amount
                      if index < len(tx.outputs) else None)
        if amount is not None:
            self._amount_cache_put(key, amount)
        return amount

    # ------------------------------------------------------------ mempool --

    async def add_pending_transaction(self, tx: Tx) -> int:
        """Insert one journal row; returns its journal sequence (the
        sqlite rowid) — with no interleaved foreign writer, the stamp's
        MAX(rowid) after this call equals the returned value, which is
        what lets the mempool intake predict the stamp its own batch
        should produce (Mempool.reconcile)."""
        inputs_addresses = [
            await self.resolve_output_address(i.tx_hash, i.index) or ""
            for i in tx.inputs
        ]
        fees = await self.tx_fees(tx)
        cur = self.db.execute(
            "INSERT INTO pending_transactions (tx_hash, tx_hex, inputs_addresses,"
            " fees, propagation_time) VALUES (?,?,?,?,?)",
            (tx.hash(), tx.hex(), json.dumps(inputs_addresses), fees, now_ts()),
        )
        seq = cur.lastrowid
        self.db.executemany(
            "INSERT INTO pending_spent_outputs (tx_hash, idx) VALUES (?,?)",
            [(i.tx_hash, i.index) for i in tx.inputs],
        )
        self._commit()
        self._pending_gen += 1
        return seq

    async def pending_transaction_exists(self, tx_hash: str) -> bool:
        r = self.db.execute(
            "SELECT 1 FROM pending_transactions WHERE tx_hash = ?", (tx_hash,)
        ).fetchone()
        return r is not None

    async def get_pending_transactions_limit(
        self, limit_hex_chars: int = 4096 * 1024, hex_only: bool = False
    ) -> List:
        """Fee-rate-ordered mempool slice capped by total hex size
        (reference database.py:171-186 ORDER BY fees/LENGTH(tx_hex) DESC,
        cap MAX_BLOCK_SIZE_HEX)."""
        rows = self.db.execute(
            "SELECT tx_hex FROM pending_transactions ORDER BY"
            " CAST(fees AS REAL)/LENGTH(tx_hex) DESC, tx_hash"
        ).fetchall()
        out, total = [], 0
        for r in rows:
            if total + len(r["tx_hex"]) > limit_hex_chars:
                break
            total += len(r["tx_hex"])
            out.append(r["tx_hex"])
        if hex_only:
            return out
        return [tx_from_hex(h, check_signatures=False) for h in out]

    async def get_pending_transactions_by_hash(self, hashes: List[str]) -> List[str]:
        """Batched: chunked ``IN (...)`` like the removal path instead of
        one SELECT per hash (push_block resolves up to a whole block's
        txs through here).  Found hexes come back in request order."""
        found: Dict[str, str] = {}
        for i in range(0, len(hashes), 500):
            chunk = hashes[i:i + 500]
            ph = ",".join("?" * len(chunk))
            for r in self.db.execute(
                    "SELECT tx_hash, tx_hex FROM pending_transactions"
                    f" WHERE tx_hash IN ({ph})", chunk):
                found[r["tx_hash"]] = r["tx_hex"]
        return [found[h] for h in hashes if h in found]

    async def get_pending_spent_outpoints(self, outpoints=None) -> set:
        """Pending-spent overlay; with ``outpoints`` only the matching
        subset is fetched (the reference's get_pending_spent_outputs
        filters the same way, database.py:126-133 caller) — intake
        checks one tx's inputs, and a full-overlay scan per incoming tx
        is quadratic in mempool depth (profiled: 28% of push_tx)."""
        if outpoints is None:
            rows = self.db.execute(
                "SELECT tx_hash, idx FROM pending_spent_outputs").fetchall()
            return {(r["tx_hash"], r["idx"]) for r in rows}
        want = {tuple(o) for o in outpoints}
        if not want:
            return set()
        hashes = list({h for h, _ in want})
        marks = ",".join("?" * len(hashes))
        rows = self.db.execute(
            f"SELECT tx_hash, idx FROM pending_spent_outputs"
            f" WHERE tx_hash IN ({marks})", hashes).fetchall()
        return {(r["tx_hash"], r["idx"]) for r in rows} & want

    async def remove_pending_transactions_by_hash(self, hashes: List[str]) -> None:
        """Batched (8k-tx block profile): the spent-output overlay rows
        only ever exist alongside a live pending_transactions row (see
        add_pending_transaction), so one SELECT per chunk over the
        pending table finds every tx whose overlay needs cleanup — no
        per-hash lookup, no re-parsing just-accepted txs out of the
        transactions table."""
        to_drop: List[str] = []
        for i in range(0, len(hashes), 500):
            chunk = hashes[i:i + 500]
            ph = ",".join("?" * len(chunk))
            rows = self.db.execute(
                "SELECT tx_hex FROM pending_transactions"
                f" WHERE tx_hash IN ({ph})", chunk).fetchall()
            spent = []
            for r in rows:
                tx = tx_from_hex(r["tx_hex"], check_signatures=False)
                if not tx.is_coinbase:
                    spent.extend((inp.tx_hash, inp.index) for inp in tx.inputs)
            if spent:
                self.db.executemany(
                    "DELETE FROM pending_spent_outputs"
                    " WHERE tx_hash = ? AND idx = ?", spent)
            self.db.execute(
                f"DELETE FROM pending_transactions WHERE tx_hash IN ({ph})",
                chunk)
            confirmed = {r["tx_hash"] for r in self.db.execute(
                f"SELECT tx_hash FROM transactions WHERE tx_hash IN ({ph})",
                chunk).fetchall()}
            to_drop.extend(h for h in chunk if h not in confirmed)
        self._amount_cache_drop(to_drop)
        self._commit()
        self._pending_gen += 1

    async def remove_pending_transactions(self) -> None:
        self.db.execute("DELETE FROM pending_transactions")
        self.db.execute("DELETE FROM pending_spent_outputs")
        self._amount_cache.clear()
        self._commit()
        self._pending_gen += 1

    async def get_pending_transactions_count(self) -> int:
        return self.db.execute(
            "SELECT COUNT(*) AS c FROM pending_transactions").fetchone()["c"]

    # The pending_transactions table doubles as the mempool subsystem's
    # write-behind journal (upow_tpu/mempool/): the in-memory pool is
    # the read authority, this table provides restart recovery and the
    # wallet CLI's direct-insert interop.  The stamp below is how the
    # pool detects journal movement it did not make itself — same
    # (count, max rowid, local generation) triple _pending_decoded uses.

    async def pending_journal_stamp(self) -> tuple:
        """Cheap change detector for the mempool journal."""
        r = self.db.execute(
            "SELECT COUNT(*) AS c, COALESCE(MAX(rowid), 0) AS m"
            " FROM pending_transactions").fetchone()
        return (r["c"], r["m"], self._pending_gen)

    async def load_pending_journal(self) -> List[dict]:
        """Every journal row the pool needs to rebuild itself
        (recovery load at startup, stamp-triggered reconcile after)."""
        rows = self.db.execute(
            "SELECT tx_hash, tx_hex, fees FROM pending_transactions"
        ).fetchall()
        return [{"tx_hash": r["tx_hash"], "tx_hex": r["tx_hex"],
                 "fees": r["fees"]} for r in rows]

    async def get_need_propagate_transactions(self, older_than: int = 300) -> List[str]:
        """Piggyback re-propagation queue (reference database.py:188-207)."""
        rows = self.db.execute(
            "SELECT tx_hex FROM pending_transactions WHERE propagation_time < ?",
            (now_ts() - older_than,),
        ).fetchall()
        return [r["tx_hex"] for r in rows]

    async def update_pending_transaction_propagation(self, tx_hash: str) -> None:
        self.db.execute(
            "UPDATE pending_transactions SET propagation_time = ? WHERE tx_hash = ?",
            (now_ts(), tx_hash),
        )
        self._commit()

    # --------------------------------------------------------------- UTXO --

    async def add_transaction_outputs(self, txs: Sequence[AnyTx]) -> None:
        """Route every output into its UTXO-class table
        (reference database.py:524-580).  Grouped into one executemany
        per table: an 8k-tx block is a handful of statement dispatches,
        not one per output."""
        by_table: Dict[str, list] = {}
        for tx in txs:
            h = tx.hash()
            for index, out in enumerate(tx.outputs):
                table = _OUTPUT_TABLE[out.output_type]
                by_table.setdefault(table, []).append((h, index, out))
        for table, entries in by_table.items():
            if table == "unspent_outputs":
                self.db.executemany(
                    "INSERT OR REPLACE INTO unspent_outputs (tx_hash, idx,"
                    " address, amount, is_stake) VALUES (?,?,?,?,?)",
                    [(h, i, o.address, o.amount, int(o.is_stake))
                     for h, i, o in entries],
                )
            else:
                self.db.executemany(
                    f"INSERT OR REPLACE INTO {table} (tx_hash, idx, address,"
                    " amount) VALUES (?,?,?,?)",
                    [(h, i, o.address, o.amount) for h, i, o in entries],
                )
            if self._dev_index is not None:
                self._index_add(table, [(h, i) for h, i, _ in entries],
                                values=self._index_values(
                                    [o for _h, _i, o in entries]))

    async def remove_outputs(self, txs: Sequence[AnyTx]) -> None:
        """Spend inputs from the table their tx type targets
        (reference database.py:589-622).  Grouped per table so a whole
        block is one DELETE executemany + one batched index apply per
        UTXO class, not one per tx."""
        by_table: Dict[str, list] = {}
        for tx in txs:
            if tx.is_coinbase:
                continue
            table = _INPUT_TABLE.get(tx.transaction_type, "unspent_outputs")
            by_table.setdefault(table, []).extend(
                (i.tx_hash, i.index) for i in tx.inputs)
        for table, outpoints in by_table.items():
            self.db.executemany(
                f"DELETE FROM {table} WHERE tx_hash = ? AND idx = ?",
                outpoints,
            )
            self._index_remove(table, outpoints)

    async def get_unspent_outpoints(self, table: str = "unspent_outputs") -> set:
        rows = self.db.execute(f"SELECT tx_hash, idx FROM {table}").fetchall()
        return {(r["tx_hash"], r["idx"]) for r in rows}

    async def outpoints_exist(self, outpoints: List[Tuple[str, int]],
                              table: str = "unspent_outputs") -> List[bool]:
        """Batched membership test: one keyed IN query per 900 outpoints
        instead of a query per outpoint — an 8k-input block is ~10 queries.
        (The reference does a set-diff against a full-column fetch,
        manager.py:531-615.)  With the device index enabled, the answer
        is EXACT and SQL-free: one ``searchsorted`` dispatch rejects
        definite misses, and the index's host-side exact map confirms
        the hits — including resolving 64-bit fingerprint twins down to
        the precise outpoint (see device_index.py).  The index follows
        the tables a COMMITTED transaction at a time: what an open
        ``atomic()`` body (or ``remove_blocks``) inserts and deletes is
        held back and reaches the index as one delta once the
        transaction has committed, never if it rolls back.  So while
        such a body is open the index answers from the last committed
        state where this connection's SQL would already show the open
        transaction's rows; block accepts are serialised by the accept
        lock, and a bulk rewrite of the tables rebuilds the index."""
        if not outpoints:
            return []
        if self._dev_index is not None and table in self._dev_index:
            present = self._dev_index[table].contains_batch(
                [tuple(o) for o in outpoints])
            return [bool(p) for p in present]
        return await self._outpoints_exist_sql(outpoints, table)

    async def _outpoints_exist_sql(self, outpoints: List[Tuple[str, int]],
                                   table: str) -> List[bool]:
        """By the primary key's leading column: ``tx_hash IN (...)`` is
        a SEARCH of the covering index a hash, and the few rows of each
        hash are matched on ``idx`` here.  (A row-value ``(tx_hash, idx)
        IN (VALUES ...)`` is planned by sqlite as a SCAN of the whole
        index a query: 46 s a block over 4 M rows, PERF.md section 6,
        PR 50.)"""
        if not outpoints:
            return []
        found: set = set()
        cur = self.db.cursor()
        cur.row_factory = None
        CHUNK = 900   # one variable an outpoint; sqlite's old limit is 999
        for off in range(0, len(outpoints), CHUNK):
            hashes = [o[0] for o in outpoints[off:off + CHUNK]]
            found.update(cur.execute(
                f"SELECT tx_hash, idx FROM {table} WHERE tx_hash IN"
                f" ({','.join('?' * len(hashes))})", hashes))
        return [tuple(o) in found for o in outpoints]

    async def get_table_outpoints_hash(self, table: str) -> str:
        """sha256 over ``tx_hash || idx`` of the table's rows in key
        order (reference database.py:827-830), streamed: sqlite
        concatenates, a chunk is one ``update``, no object a row is
        kept."""
        import hashlib

        cur = self.db.cursor()
        cur.row_factory = None
        cur.execute(f"SELECT tx_hash || idx FROM {table}"
                    " ORDER BY tx_hash, idx")
        h = hashlib.sha256()
        while True:
            rows = cur.fetchmany(self._INDEX_CHUNK)
            if not rows:
                return h.hexdigest()
            (texts,) = zip(*rows)
            h.update("".join(texts).encode())

    # ------------------------------------------------------ address views --

    async def _pending_filter(self, rows, check_pending_txs: bool) -> set:
        """Pending-spent overlay narrowed to these rows' outpoints (the
        full-overlay scan per lookup was quadratic under mempool load)."""
        if not check_pending_txs:
            return set()
        # threshold: narrowing wins when the row set is small (intake,
        # per-address lookups); full-table views (registrations,
        # ballots) would ship one bind param per row and invert the
        # cost model — there the one O(overlay) fetch stays cheaper,
        # and the cap also bounds the IN-clause parameter count
        if not rows:
            return set()
        if len(rows) > 256:
            return await self.get_pending_spent_outpoints()
        return await self.get_pending_spent_outpoints(
            [(r["tx_hash"], r["idx"]) for r in rows])

    async def get_spendable_outputs(self, address: str,
                                    check_pending_txs: bool = False) -> List[TxInput]:
        """REGULAR/UN_STAKE outputs owned by the address, minus anything in
        the pending-spent overlay when requested."""
        rows = self.db.execute(
            "SELECT tx_hash, idx, amount, is_stake FROM unspent_outputs"
            " WHERE address = ? AND is_stake = 0", (address,),
        ).fetchall()
        pending = await self._pending_filter(rows, check_pending_txs)
        out = []
        for r in rows:
            if (r["tx_hash"], r["idx"]) in pending:
                continue
            i = TxInput(r["tx_hash"], r["idx"])
            i.amount = r["amount"]
            out.append(i)
        return out

    async def get_stake_outputs(self, address: str,
                                check_pending_txs: bool = False) -> List[TxInput]:
        rows = self.db.execute(
            "SELECT tx_hash, idx, amount FROM unspent_outputs"
            " WHERE address = ? AND is_stake = 1", (address,),
        ).fetchall()
        pending = await self._pending_filter(rows, check_pending_txs)
        out = []
        for r in rows:
            if (r["tx_hash"], r["idx"]) in pending:
                continue
            i = TxInput(r["tx_hash"], r["idx"])
            i.amount = r["amount"]
            out.append(i)
        return out

    async def get_address_transactions(self, address: str, limit: int = 50,
                                       offset: int = 0) -> List[dict]:
        if self.archive is None:
            rows = self.db.execute(
                "SELECT t.*, b.id AS block_id, b.timestamp AS block_ts FROM transactions t"
                " JOIN blocks b ON b.hash = t.block_hash"
                " WHERE t.inputs_addresses LIKE ? OR t.outputs_addresses LIKE ?"
                " ORDER BY b.id DESC LIMIT ? OFFSET ?",
                (f'%"{address}"%', f'%"{address}"%', limit, offset),
            ).fetchall()
            return [dict(r) for r in rows]
        # archived history has to be merged in before paginating: fetch
        # the hot prefix deep enough to cover the requested page, then
        # overlay archive matches (dedup by tx_hash — witness txs below
        # the archive horizon exist in both tiers) and re-slice.  Any
        # hot row beyond the prefix sorts after >= offset+limit rows,
        # so it can never land inside the page.
        rows = self.db.execute(
            "SELECT t.*, b.id AS block_id, b.timestamp AS block_ts FROM transactions t"
            " JOIN blocks b ON b.hash = t.block_hash"
            " WHERE t.inputs_addresses LIKE ? OR t.outputs_addresses LIKE ?"
            " ORDER BY b.id DESC LIMIT ?",
            (f'%"{address}"%', f'%"{address}"%', offset + limit),
        ).fetchall()
        merged = [dict(r) for r in rows]
        seen = {r["tx_hash"] for r in merged}
        for b, t in await self.archive.address_history(address):
            if t[1] in seen:
                continue
            merged.append({
                "block_hash": t[0], "tx_hash": t[1], "tx_hex": t[2],
                "inputs_addresses": json.dumps(t[3]),
                "outputs_addresses": json.dumps(t[4]),
                "outputs_amounts": json.dumps(t[5]), "fees": t[6],
                "block_id": b[0], "block_ts": b[7],
            })
        merged.sort(key=lambda r: -r["block_id"])
        return merged[offset:offset + limit]

    # --------------------------------------------------------- governance --

    async def get_registered(self, table: str,
                             check_pending_txs: bool = False,
                             pending: Optional[set] = None) -> List[Tuple[str, int]]:
        """(address, registered_at block timestamp) per registration output."""
        rows = self.db.execute(
            f"SELECT g.tx_hash, g.idx, g.address FROM {table} g").fetchall()
        if pending is None:
            pending = await self._pending_filter(rows, check_pending_txs)
        out = []
        for r in rows:
            if (r["tx_hash"], r["idx"]) in pending:
                continue
            ts = self.db.execute(
                "SELECT b.timestamp AS ts FROM transactions t JOIN blocks b"
                " ON b.hash = t.block_hash WHERE t.tx_hash = ?",
                (r["tx_hash"],),
            ).fetchone()
            out.append((r["address"], ts["ts"] if ts else now_ts()))
        return out

    async def get_ballot_by_recipient(self, table: str, recipient: str,
                                      check_pending_txs: bool = False) -> List[dict]:
        """Standing votes FOR ``recipient``.

        A ballot row is a vote *output*: its address column holds the vote
        RECIPIENT (the inode/validator being voted for); the VOTER is the
        vote transaction's ``inputs_addresses[output_index]`` (reference
        database.py:939-1063 — SQL 1-based ``inputs_addresses[index+1]``),
        and the vote count is the output's amount.
        """
        rows = self.db.execute(
            f"SELECT g.tx_hash, g.idx, g.amount FROM {table} g WHERE g.address = ?",
            (recipient,),
        ).fetchall()
        pending = await self._pending_filter(rows, check_pending_txs)
        out = []
        for r in rows:
            if (r["tx_hash"], r["idx"]) in pending:
                continue
            info = await self.get_transaction_info(r["tx_hash"])
            voter = None
            if info is not None and r["idx"] < len(info["inputs_addresses"]):
                voter = info["inputs_addresses"][r["idx"]]
            out.append({
                "tx_hash": r["tx_hash"], "index": r["idx"],
                "voter": voter, "vote": Decimal(r["amount"]) / SMALLEST,
            })
        return out

    async def _all_ballot_rows(self, table: str,
                               check_pending_txs: bool = False,
                               pending: Optional[set] = None) -> List[dict]:
        """Every standing ballot row with its voter resolved — ONE join
        instead of a query per recipient per row.  The voter rule (vote
        output's ``inputs_addresses[output_index]``) lives HERE only;
        get_votes_by_voter and get_active_inodes are filters over it."""
        rows = self.db.execute(
            f"SELECT g.tx_hash, g.idx, g.address AS recipient, g.amount,"
            f" t.inputs_addresses FROM {table} g"
            f" JOIN transactions t ON t.tx_hash = g.tx_hash"
        ).fetchall()
        if pending is None:
            pending = await self._pending_filter(rows, check_pending_txs)
        out = []
        for r in rows:
            if (r["tx_hash"], r["idx"]) in pending:
                continue
            addrs = json.loads(r["inputs_addresses"])
            voter = addrs[r["idx"]] if r["idx"] < len(addrs) else None
            out.append({
                "tx_hash": r["tx_hash"], "index": r["idx"],
                "recipient": r["recipient"], "voter": voter,
                "vote": Decimal(r["amount"]) / SMALLEST,
            })
        return out

    async def get_transaction_block_timestamp(self, tx_hash: str) -> Optional[int]:
        r = self.db.execute(
            "SELECT b.timestamp AS ts FROM transactions t JOIN blocks b ON"
            " b.hash = t.block_hash WHERE t.tx_hash = ?", (tx_hash,),
        ).fetchone()
        if r is None and self.archive is not None:
            hit = await self.archive.tx_by_hash(tx_hash)
            if hit is not None:
                b = await self.archive.block_by_height(hit[1])
                return b[7] if b else None
        return r["ts"] if r else None

    async def get_delegates_voting_power(self, address: str,
                                         check_pending_txs: bool = False) -> List[Tuple[str, int]]:
        rows = self.db.execute(
            "SELECT tx_hash, idx FROM delegates_voting_power WHERE address = ?",
            (address,),
        ).fetchall()
        pending = await self._pending_filter(rows, check_pending_txs)
        return [(r["tx_hash"], r["idx"]) for r in rows
                if (r["tx_hash"], r["idx"]) not in pending]

    async def get_inode_registration_outputs(self, address: str,
                                             check_pending_txs: bool = False) -> List[Tuple[str, int]]:
        rows = self.db.execute(
            "SELECT tx_hash, idx FROM inode_registration_output WHERE address = ?",
            (address,),
        ).fetchall()
        pending = await self._pending_filter(rows, check_pending_txs)
        return [(r["tx_hash"], r["idx"]) for r in rows
                if (r["tx_hash"], r["idx"]) not in pending]

    async def get_validators_voting_power(self, address: str,
                                          check_pending_txs: bool = False) -> List[Tuple[str, int]]:
        """Unspent VALIDATOR_VOTING_POWER outputs owned by the address."""
        rows = self.db.execute(
            "SELECT tx_hash, idx FROM validators_voting_power WHERE address = ?",
            (address,),
        ).fetchall()
        pending = await self._pending_filter(rows, check_pending_txs)
        return [(r["tx_hash"], r["idx"]) for r in rows
                if (r["tx_hash"], r["idx"]) not in pending]

    async def get_multiple_address_stakes(
            self, addresses: Iterable[str],
            check_pending_txs: bool = False,
            pending: Optional[set] = None) -> Dict[str, Decimal]:
        """Batch stake query (reference database.py:1208-1290): one pass over
        unspent stake outputs + one pass over the mempool for all addresses."""
        addresses = list(set(addresses))
        if not addresses:
            return {}
        out: Dict[str, Decimal] = {a: Decimal(0) for a in addresses}
        placeholders = ",".join("?" * len(addresses))
        rows = self.db.execute(
            f"SELECT tx_hash, idx, address, amount FROM unspent_outputs"
            f" WHERE is_stake = 1 AND address IN ({placeholders})", addresses,
        ).fetchall()
        if pending is None:
            pending = await self._pending_filter(rows, check_pending_txs)
        for r in rows:
            if (r["tx_hash"], r["idx"]) in pending:
                continue
            out[r["address"]] += Decimal(r["amount"]) / SMALLEST
        if check_pending_txs:
            want = set(addresses)
            for tx in (await self._pending_decoded()).values():
                for o in tx.outputs:
                    if o.is_stake and o.address in want:
                        out[o.address] += Decimal(o.amount) / SMALLEST
        return out

    async def get_outputs_by_address(self, table: str, address: str,
                                     check_pending_txs: bool = False,
                                     is_stake: Optional[bool] = None) -> List[dict]:
        """Generic per-table output listing: {tx_hash, index, amount} rows
        (the shape the address-info endpoint sections need)."""
        sql = f"SELECT tx_hash, idx, amount FROM {table} WHERE address = ?"
        params: list = [address]
        if is_stake is not None and table == "unspent_outputs":
            sql += " AND is_stake = ?"
            params.append(int(is_stake))
        rows = self.db.execute(sql, params).fetchall()
        pending = await self._pending_filter(rows, check_pending_txs)
        return [
            {"tx_hash": r["tx_hash"], "index": r["idx"], "amount": r["amount"]}
            for r in rows if (r["tx_hash"], r["idx"]) not in pending
        ]

    # ------------------------------------------------------ explorer views --

    async def get_ballots(self, table: str, recipient: Optional[str] = None,
                          offset: int = 0, limit: int = 100) -> List[dict]:
        """Paged ballot listing for the validators/delegates info endpoints
        (reference database.py get_inode_ballot/get_validator_ballot):
        rows of {tx_hash, index, voter, recipient, vote}."""
        if recipient is not None:
            rows = self.db.execute(
                f"SELECT tx_hash, idx, address, amount FROM {table}"
                f" WHERE address = ? LIMIT ? OFFSET ?",
                (recipient, limit, offset),
            ).fetchall()
        else:
            rows = self.db.execute(
                f"SELECT tx_hash, idx, address, amount FROM {table}"
                f" LIMIT ? OFFSET ?", (limit, offset),
            ).fetchall()
        out = []
        for r in rows:
            info = await self.get_transaction_info(r["tx_hash"])
            voter = None
            if info is not None and r["idx"] < len(info["inputs_addresses"]):
                voter = info["inputs_addresses"][r["idx"]]
            out.append({
                "tx_hash": r["tx_hash"], "index": r["idx"], "voter": voter,
                "recipient": r["address"],
                "vote": Decimal(r["amount"]) / SMALLEST,
            })
        return out

    async def get_nice_transaction(self, tx_hash: str,
                                   address: Optional[str] = None) -> Optional[dict]:
        """Explorer-style decoded transaction (reference database.py:1606-1654).
        Amounts are coin-denominated floats like the reference's JSON."""
        r = self.db.execute(
            "SELECT t.*, b.id AS block_no, b.timestamp AS block_ts FROM"
            " transactions t JOIN blocks b ON b.hash = t.block_hash"
            " WHERE t.tx_hash = ?", (tx_hash,),
        ).fetchone()
        is_confirm = r is not None
        if r is None:
            r = self.db.execute(
                "SELECT tx_hash, tx_hex, inputs_addresses FROM"
                " pending_transactions WHERE tx_hash = ?", (tx_hash,),
            ).fetchone()
        if r is None and self.archive is not None:
            hit = await self.archive.tx_by_hash(tx_hash)
            if hit is not None:
                t, height = hit
                b = await self.archive.block_by_height(height)
                # plain dict stands in for the sqlite Row (same keys,
                # .keys() works; inputs_addresses json-encoded like the
                # hot column)
                r = {"tx_hash": t[1], "tx_hex": t[2],
                     "inputs_addresses": json.dumps(t[3]),
                     "block_hash": t[0], "block_no": height,
                     "block_ts": b[7] if b else None}
                is_confirm = True
        if r is None:
            return None
        keys = r.keys()
        tx = tx_from_hex(r["tx_hex"], check_signatures=False)
        inputs_addresses = json.loads(r["inputs_addresses"])

        def coins(amount: int) -> float:
            return float(Decimal(amount) / SMALLEST)

        if tx.is_coinbase:
            out = {
                "is_coinbase": True, "hash": r["tx_hash"],
                "block_hash": r["block_hash"] if "block_hash" in keys else None,
                "block_no": r["block_no"] if "block_no" in keys else None,
                "datetime": r["block_ts"] if "block_ts" in keys else None,
            }
        else:
            delta = None
            if address is not None:
                delta = 0
                for i, tx_input in enumerate(tx.inputs):
                    if i < len(inputs_addresses) and inputs_addresses[i] == address:
                        amt = await self.get_output_amount(
                            tx_input.tx_hash, tx_input.index)
                        delta -= amt or 0
                for o in tx.outputs:
                    if o.address == address:
                        delta += o.amount
                delta = coins(delta)
            inputs = []
            for i, tx_input in enumerate(tx.inputs):
                amt = await self.get_output_amount(tx_input.tx_hash, tx_input.index)
                inputs.append({
                    "index": tx_input.index,
                    "tx_hash": tx_input.tx_hash,
                    "address": (inputs_addresses[i]
                                if i < len(inputs_addresses) else None),
                    "amount": coins(amt or 0),
                })
            out = {
                "is_coinbase": False, "hash": r["tx_hash"],
                "block_hash": r["block_hash"] if "block_hash" in keys else None,
                "block_no": r["block_no"] if "block_no" in keys else None,
                "datetime": r["block_ts"] if "block_ts" in keys else None,
                "message": tx.message.hex() if tx.message is not None else None,
                "transaction_type": tx.transaction_type.name,
                "is_confirm": is_confirm,
                "inputs": inputs,
                "delta": delta,
                "fees": coins(await self.tx_fees(tx)),
            }
        out["outputs"] = [
            {"address": o.address, "amount": coins(o.amount),
             "type": o.output_type.name}
            for o in tx.outputs
        ]
        return out

    async def get_block_transaction_hashes(self, block_hash: str) -> List[str]:
        rows = self.db.execute(
            "SELECT tx_hash FROM transactions WHERE block_hash = ?",
            (block_hash,),
        ).fetchall()
        if not rows and self.archive is not None:
            atxs = await self.archive.txs_for_block(block_hash)
            if atxs:
                return [t[1] for t in atxs]
        return [r["tx_hash"] for r in rows]

    async def get_address_pending_transactions(self, address: str) -> List[Tx]:
        """Mempool txs touching the address (input spender or output
        recipient)."""
        rows = self.db.execute(
            "SELECT tx_hex, inputs_addresses FROM pending_transactions"
        ).fetchall()
        out = []
        for r in rows:
            if address in json.loads(r["inputs_addresses"]):
                out.append(tx_from_hex(r["tx_hex"], check_signatures=False))
                continue
            tx = tx_from_hex(r["tx_hex"], check_signatures=False)
            if any(o.address == address for o in tx.outputs):
                out.append(tx)
        return out

    async def get_address_pending_spent_outpoints(
            self, address: str) -> List[Tuple[str, int]]:
        """Outpoints of this address currently referenced by mempool txs."""
        rows = self.db.execute(
            "SELECT tx_hex, inputs_addresses FROM pending_transactions"
        ).fetchall()
        out = []
        for r in rows:
            addrs = json.loads(r["inputs_addresses"])
            tx = tx_from_hex(r["tx_hex"], check_signatures=False)
            for i, tx_input in enumerate(tx.inputs):
                if i < len(addrs) and addrs[i] == address:
                    out.append((tx_input.tx_hash, tx_input.index))
        return out

    # ----------------------------------------------------------- rebuild --

    async def rebuild_utxos(self) -> None:
        """Full-chain replay of every output table from the transactions log
        (reference create_unspent_outputs.py + database.py:846-862) — the
        consensus-bug detector: any divergence from live tables is a bug."""
        for table in ("unspent_outputs",) + _GOV_TABLES:
            self.db.execute(f"DELETE FROM {table}")
        rows = self.db.execute(
            "SELECT t.tx_hex FROM transactions t JOIN blocks b ON"
            " b.hash = t.block_hash ORDER BY b.id"
        ).fetchall()
        txs = [tx_from_hex(r["tx_hex"], check_signatures=False) for r in rows]
        # the replay rewrites the tables wholesale and the index is built
        # again from them below: what it would hear tx by tx is dropped
        held, self._index_stage = self._index_stage, {}
        try:
            for tx in txs:
                await self.add_transaction_outputs([tx])
                await self.remove_outputs([tx])
        finally:
            self._index_stage = held
        self._commit()
        self._index_rebuild()

    # ---------------------------------------------------------- snapshots --
    # Canonical positional row shapes shared with the pg backend (the
    # snapshot payload is backend-neutral, docs/SNAPSHOT.md):
    #   unspent_outputs  [tx_hash, idx, address|null, amount, is_stake]
    #   governance       [tx_hash, idx, address|null, amount]
    #   tx               [block_hash, tx_hash, tx_hex, inputs_addresses,
    #                     outputs_addresses, outputs_amounts, fees]
    #   block            [id, hash, content, address, random,
    #                     str(difficulty), reward, timestamp]
    # Amounts/fees/rewards are int smallest-units everywhere; lists are
    # real JSON arrays (this backend stores them json-encoded).

    async def export_snapshot_rows(self, table: str) -> List[list]:
        if table not in ("unspent_outputs",) + _GOV_TABLES:
            raise ValueError(f"not a snapshot table: {table}")
        if table == "unspent_outputs":
            rows = self.db.execute(
                "SELECT tx_hash, idx, address, amount, is_stake FROM"
                " unspent_outputs ORDER BY tx_hash, idx").fetchall()
            return [[r["tx_hash"], r["idx"], r["address"], r["amount"],
                     r["is_stake"]] for r in rows]
        rows = self.db.execute(
            f"SELECT tx_hash, idx, address, amount FROM {table}"
            " ORDER BY tx_hash, idx").fetchall()
        return [[r["tx_hash"], r["idx"], r["address"], r["amount"]]
                for r in rows]

    async def export_snapshot_txs(self, tail: int) -> List[list]:
        """Witness transactions: every tx still referenced by an
        exported outpoint (the pg schema resolves amounts through — and
        foreign-keys onto — the transactions table, so UTXO rows alone
        cannot restore there) plus all txs of the carried block tail."""
        union = " UNION ".join(
            f"SELECT tx_hash FROM {t}"
            for t in ("unspent_outputs",) + _GOV_TABLES)
        rows = self.db.execute(
            "SELECT block_hash, tx_hash, tx_hex, inputs_addresses,"
            " outputs_addresses, outputs_amounts, fees FROM transactions"
            f" WHERE tx_hash IN ({union}) OR block_hash IN"
            " (SELECT hash FROM blocks ORDER BY id DESC LIMIT ?)"
            " ORDER BY tx_hash", (tail,)).fetchall()
        return [[r["block_hash"], r["tx_hash"], r["tx_hex"],
                 json.loads(r["inputs_addresses"]),
                 json.loads(r["outputs_addresses"]),
                 json.loads(r["outputs_amounts"]), r["fees"]] for r in rows]

    async def export_snapshot_blocks(self, tail: int) -> List[list]:
        rows = self.db.execute(
            "SELECT id, hash, content, address, random, difficulty,"
            " reward, timestamp FROM blocks ORDER BY id DESC LIMIT ?",
            (tail,)).fetchall()
        return [[r["id"], r["hash"], r["content"], r["address"],
                 r["random"], str(r["difficulty"]), r["reward"],
                 r["timestamp"]] for r in reversed(rows)]

    async def restore_snapshot(self, tables: Dict[str, List[list]],
                               txs: List[list], blocks: List[list]) -> None:
        """Wholesale replace of chain state with verified snapshot rows.
        Callers verify every chunk hash AND the recomputed UTXO
        fingerprint against the manifest BEFORE calling — one
        transaction, so a crash mid-restore leaves the previous state
        intact (atomic() rolls back)."""
        for name in tables:
            if name not in ("unspent_outputs",) + _GOV_TABLES:
                raise ValueError(f"not a snapshot table: {name}")
        async with self.atomic():
            for table in ("unspent_outputs",) + _GOV_TABLES:
                self.db.execute(f"DELETE FROM {table}")
            for table in ("pending_spent_outputs", "pending_transactions",
                          "transactions", "blocks"):
                self.db.execute(f"DELETE FROM {table}")
            self.db.executemany(
                "INSERT INTO blocks (id, hash, content, address, random,"
                " difficulty, reward, timestamp) VALUES (?,?,?,?,?,?,?,?)",
                [tuple(r) for r in blocks])
            self.db.executemany(
                "INSERT INTO transactions (block_hash, tx_hash, tx_hex,"
                " inputs_addresses, outputs_addresses, outputs_amounts,"
                " fees) VALUES (?,?,?,?,?,?,?)",
                [(r[0], r[1], r[2], json.dumps(r[3]), json.dumps(r[4]),
                  json.dumps(r[5]), r[6]) for r in txs])
            self.db.executemany(
                "INSERT INTO unspent_outputs (tx_hash, idx, address,"
                " amount, is_stake) VALUES (?,?,?,?,?)",
                [tuple(r) for r in tables.get("unspent_outputs", [])])
            for table in _GOV_TABLES:
                self.db.executemany(
                    f"INSERT INTO {table} (tx_hash, idx, address, amount)"
                    " VALUES (?,?,?,?)",
                    [tuple(r) for r in tables.get(table, [])])
        self._amount_cache.clear()
        self._bump_fees_gen()
        self._index_rebuild()  # restore rewrote the tables wholesale

    # ------------------------------------------------------------- archive --
    # Compactor seam (upow_tpu/archive/compactor.py, docs/ARCHIVE.md).
    # Export reuses the canonical positional row shapes above; prune
    # evaluates the witness closure live, at delete time, so re-running
    # after a crash is an idempotent no-op for already-pruned rows.

    async def archive_export_span(self, lo: int, hi: int):
        """Canonical rows for heights [lo, hi]: (block rows ascending,
        {block_hash: [tx rows in acceptance order]})."""
        rows = self.db.execute(
            "SELECT id, hash, content, address, random, difficulty,"
            " reward, timestamp FROM blocks WHERE id BETWEEN ? AND ?"
            " ORDER BY id", (lo, hi)).fetchall()
        blocks = [[r["id"], r["hash"], r["content"], r["address"],
                   r["random"], str(r["difficulty"]), r["reward"],
                   r["timestamp"]] for r in rows]
        txs_by_block: Dict[str, list] = {}
        hashes = [b[1] for b in blocks]
        for i in range(0, len(hashes), 900):
            chunk = hashes[i:i + 900]
            marks = ",".join("?" * len(chunk))
            for t in self.db.execute(
                    "SELECT block_hash, tx_hash, tx_hex,"
                    " inputs_addresses, outputs_addresses,"
                    " outputs_amounts, fees FROM transactions WHERE"
                    f" block_hash IN ({marks}) ORDER BY rowid", chunk):
                txs_by_block.setdefault(t["block_hash"], []).append(
                    [t["block_hash"], t["tx_hash"], t["tx_hex"],
                     json.loads(t["inputs_addresses"]),
                     json.loads(t["outputs_addresses"]),
                     json.loads(t["outputs_amounts"]), t["fees"]])
        return blocks, txs_by_block

    async def archive_prune_span(self, lo: int, hi: int) -> dict:
        """Delete hot blocks in [lo, hi] whose ENTIRE tx set is outside
        the snapshot witness closure, plus those blocks' txs.  A block
        with even one witness tx keeps ALL its rows hot, so a block's
        txs are never split across the hot/archive seam and every hot
        join stays intact."""
        union = " UNION ".join(
            f"SELECT tx_hash FROM {t}"
            for t in ("unspent_outputs",) + _GOV_TABLES)
        doomed = [r["hash"] for r in self.db.execute(
            "SELECT hash FROM blocks b WHERE b.id BETWEEN ? AND ?"
            " AND NOT EXISTS (SELECT 1 FROM transactions t WHERE"
            f" t.block_hash = b.hash AND t.tx_hash IN ({union}))",
            (lo, hi)).fetchall()]
        tx_hashes: List[str] = []
        for i in range(0, len(doomed), 900):
            chunk = doomed[i:i + 900]
            marks = ",".join("?" * len(chunk))
            tx_hashes.extend(r["tx_hash"] for r in self.db.execute(
                "SELECT tx_hash FROM transactions WHERE block_hash IN"
                f" ({marks})", chunk))
            self.db.execute(
                f"DELETE FROM transactions WHERE block_hash IN ({marks})",
                chunk)
            self.db.execute(
                f"DELETE FROM blocks WHERE hash IN ({marks})", chunk)
        self._amount_cache_drop(tx_hashes)
        self._commit()
        return {"blocks": len(doomed), "txs": len(tx_hashes)}

    async def archive_hot_row_counts(self) -> dict:
        b = self.db.execute(
            "SELECT COUNT(*) AS n FROM blocks").fetchone()["n"]
        t = self.db.execute(
            "SELECT COUNT(*) AS n FROM transactions").fetchone()["n"]
        return {"blocks": b, "txs": t}
