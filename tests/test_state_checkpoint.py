"""The write-ahead log's keeper (ISSUE 51, ``state/storage.py``): a
file-backed sole-writer ``ChainState`` switches sqlite's automatic
checkpoint off and a thread of its own folds the log into the file
behind the commit.  Held here: who gets a keeper, that durability is
what it was (``synchronous`` 2, a process ended before its checkpoint
reads back whole), the cadence (one checkpoint a body of a thousand
pages or more, the log never longer than a body), the bound past which
the commit checkpoints itself, that the writer's memo and
``data_version`` never see a keeper's checkpoint, and ``close()``."""

import asyncio
import os
import sqlite3
import subprocess
import sys
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from test_node import make_config
from upow_tpu import telemetry
from upow_tpu.node.app import Node
from upow_tpu.state import storage
from upow_tpu.state.storage import ChainState

ROWS = 20000          # one body: ~1,370 pages of log, over the 1,000
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _body(state: ChainState, k: int, rows: int = ROWS) -> None:
    async def run():
        async with state.atomic():
            state.db.executemany(
                "INSERT INTO unspent_outputs (tx_hash, idx, address, amount)"
                " VALUES (?,?,?,?)",
                [("%064x" % (k * 1000000 + i), 0, "a" * 45, i)
                 for i in range(rows)])
    asyncio.run(run())


def _pragma(state: ChainState, name: str):
    return state.db.execute(f"PRAGMA {name}").fetchone()[0]


def _counter(name: str) -> int:
    return telemetry.counters().get(name, 0)


def _until(reached, seconds: float = 20.0) -> bool:
    end = time.monotonic() + seconds
    while not reached():
        if time.monotonic() > end:
            return False
        time.sleep(0.005)
    return True


def _read_only(path: str, sql: str) -> tuple:
    """One row read as the benchmark's drivers read a dead node's file."""
    con = sqlite3.connect(f"file:{path}?mode=ro", uri=True)
    try:
        return tuple(con.execute(sql).fetchone())
    finally:
        con.close()


def _keepers() -> int:
    return sum(t.name == "wal-keeper" for t in threading.enumerate())


def _checkpoints() -> list:
    """The recorded ``state.wal_checkpoint`` roots, as their fields."""
    return [t["fields"] for t in telemetry.traces()["recent"]
            if t["name"] == "state.wal_checkpoint"]


@pytest.mark.parametrize("who", ["node", "memory", "second_writer"])
def test_who_gets_a_keeper(tmp_path, who):
    before = _keepers()
    path = None if who == "memory" else str(tmp_path / "n.db")
    state = ChainState(path, sole_writer=who != "second_writer")
    try:
        assert _pragma(state, "synchronous") == 2
        if who == "node":
            assert _pragma(state, "journal_mode") == "wal"
            assert _pragma(state, "wal_autocheckpoint") == 0
            assert _keepers() == before + 1
            assert state.wal_pages is None      # no commit yet
        else:
            assert _pragma(state, "wal_autocheckpoint") == 1000
            assert _keepers() == before
            assert state._keeper is None and state.wal_pages is None
    finally:
        state.close()
    assert _keepers() == before


def test_the_keeper_folds_the_log_once_a_body(tmp_path):
    telemetry.reset()
    path = str(tmp_path / "n.db")
    state = ChainState(path)
    n, sizes = 5, []
    try:
        for k in range(n):
            _body(state, k)
            assert state.wal_pages >= storage._CHECKPOINT_PAGES
            assert _until(lambda: _counter("state.checkpoints") == k + 1)
            sizes.append(os.path.getsize(path + "-wal"))
        assert _counter("state.checkpoints") >= n - 1
        assert _counter("state.checkpoint_inline") == 0
        assert _until(lambda: len(_checkpoints()) == n)
        rows = _checkpoints()
        for fields in rows:
            assert fields["busy"] == 0 and not fields["inline"]
            assert fields["backfilled"] == fields["frames"] >= 1000
        assert _counter("state.checkpoint_frames") == sum(
            f["backfilled"] for f in rows)
        # every body found the log folded and began it again
        assert max(sizes) <= sizes[0]
        assert telemetry.stats()["state.wal_checkpoint"]["count"] == n
    finally:
        state.close()


def test_small_commits_gather_until_they_make_a_thousand_pages(tmp_path):
    telemetry.reset()
    state = ChainState(str(tmp_path / "n.db"))
    try:
        for k in range(3):
            _body(state, k, rows=50)
        assert 0 < state.wal_pages < storage._CHECKPOINT_PAGES
        time.sleep(0.05)
        assert _counter("state.checkpoints") == 0
    finally:
        state.close()


_CHILD = """
import asyncio, os, sys
sys.path.insert(0, {repo!r})
from upow_tpu.state.storage import ChainState
state = ChainState({path!r})
state._keeper.wake = lambda: None        # the keeper held back
async def run():
    async with state.atomic():
        state.db.executemany(
            "INSERT INTO unspent_outputs (tx_hash, idx, address, amount)"
            " VALUES (?,?,?,?)",
            [("%064x" % i, 0, "a" * 45, i) for i in range({rows})])
asyncio.run(run())
assert state.wal_pages >= 1000
os._exit(0)
"""


def test_a_process_ended_before_its_checkpoint_loses_nothing(tmp_path):
    path = str(tmp_path / "n.db")
    done = subprocess.run(
        [sys.executable, "-c",
         _CHILD.format(repo=REPO, path=path, rows=ROWS)],
        capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr
    # the commit is in the log alone: the main file has not heard of it
    assert os.path.getsize(path + "-wal") > 1000 * 4096
    assert _read_only(
        path, "SELECT COUNT(*), SUM(amount) FROM unspent_outputs") \
        == (ROWS, ROWS * (ROWS - 1) // 2)


def test_a_log_past_its_bound_is_checkpointed_by_the_commit(
        tmp_path, monkeypatch):
    telemetry.reset()
    monkeypatch.setattr(storage, "_INLINE_CHECKPOINT_PAGES", 2000)
    state = ChainState(str(tmp_path / "n.db"))
    state._keeper.wake = lambda: None        # the keeper held back
    try:
        _body(state, 0)
        assert 1000 <= state.wal_pages <= 2000
        assert _counter("state.checkpoints") == 0
        _body(state, 1)
        assert state.wal_pages > 2000
        assert _counter("state.checkpoint_inline") == 1
        assert _counter("state.checkpoints") == 1
        assert _counter("state.checkpoint_frames") == state.wal_pages
        # folded, the log begins again: the next body is under the bound
        _body(state, 2)
        assert 1000 <= state.wal_pages <= 2000
        assert _counter("state.checkpoint_inline") == 1
    finally:
        state.close()


def test_a_keeper_checkpoint_leaves_the_memo_and_data_version(tmp_path):
    telemetry.reset()
    state = ChainState(str(tmp_path / "n.db"))
    try:
        _body(state, 0)
        assert _until(lambda: _counter("state.checkpoints") == 1)
        version = _pragma(state, "data_version")
        state._data_version = version
        state._amount_cache_put(("ab" * 32, 0), (7, "addr"))
        _body(state, 1)
        assert _until(lambda: _counter("state.checkpoints") == 2)
        assert _pragma(state, "data_version") == version
        state._data_version_checked = 0.0       # look again now
        assert state._amount_cache_get(("ab" * 32, 0)) == (7, "addr")
    finally:
        state.close()


def test_close_joins_the_keeper_and_folds_the_log(tmp_path):
    before = _keepers()
    path = str(tmp_path / "n.db")
    state = ChainState(path)
    keeper = state._keeper
    keeper.wake = lambda: None               # nothing folded before close
    _body(state, 0)
    assert os.path.getsize(path + "-wal") > 1000 * 4096
    state.close()
    assert not keeper._thread.is_alive() and _keepers() == before
    # nothing to replay: the last connection's close folded the log
    assert not os.path.exists(path + "-wal") \
        or os.path.getsize(path + "-wal") == 0
    assert _read_only(
        path, "SELECT COUNT(*) FROM unspent_outputs") == (ROWS,)


def test_commits_that_do_not_wait_for_the_keeper_lose_no_row(
        tmp_path, monkeypatch):
    """Bodies committed back to back, so that the keeper is still
    copying when the next one writes and the log cannot begin again (a
    switch interval of 10 us): past the bound the commit waits for the
    checkpoint in flight and runs its own, so the log stays within a
    body of the bound, and every row is there."""
    telemetry.reset()
    monkeypatch.setattr(storage, "_INLINE_CHECKPOINT_PAGES", 4000)
    path = str(tmp_path / "n.db")
    state = ChainState(path)
    keeper, n, longest = state._keeper, 12, 0
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for k in range(n):
            _body(state, k)
            longest = max(longest, state.wal_pages)
        assert _until(lambda: _counter("state.checkpoints") >= 1)
    finally:
        sys.setswitchinterval(interval)
        state.close()
    assert not keeper._thread.is_alive()
    assert longest <= 4000 + 1400       # of twelve bodies' 16,400 pages
    assert _read_only(
        path, "SELECT COUNT(*) FROM unspent_outputs") == (n * ROWS,)


def test_a_node_exports_the_keepers_counters_and_the_logs_pages(tmp_path):
    async def scenario():
        telemetry.reset()
        cfg = make_config(tmp_path, "a")
        cfg.node.db_path = str(tmp_path / "a.db")
        node = Node(cfg)
        server = TestServer(node.app)
        await server.start_server()
        client = TestClient(server)
        node.started = True
        try:
            assert _pragma(node.state, "wal_autocheckpoint") == 0
            first = await (await client.get("/metrics")).text()
            for name in ("upow_state_checkpoints_total",
                         "upow_state_checkpoint_frames_total",
                         "upow_state_checkpoint_inline_total"):
                assert f"{name} 0" in first, name
            await node.state.remove_pending_transactions()
            scrape = await (await client.get("/metrics")).text()
            assert f"upow_state_wal_pages {node.state.wal_pages}" in scrape
            assert node.state.wal_pages > 0
        finally:
            await client.close()
            await server.close()
            await node.close()

    asyncio.run(scenario())
