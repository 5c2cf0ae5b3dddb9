"""A node with ``device.utxo_index = true`` (ISSUE 50): the double-spend
scan runs under the span ``block.spend_scan`` with ``path=resident``, a
block's delta reaches the index under ``index.apply``, the index's three
counters are exported from the first scrape, the build says
``index_built``, and every tenth block's fingerprint has its span and is
the old function's digest byte for byte."""

import asyncio
import hashlib
import random
from decimal import Decimal

from aiohttp.test_utils import TestClient, TestServer

from test_node import (Cluster, easy_difficulty, keys, make_config,  # noqa: F401
                       mine_via_api)
from upow_tpu import telemetry
from upow_tpu.core.clock import timestamp
from upow_tpu.core.header import BlockHeader
from upow_tpu.core.merkle import miner_merkle_root
from upow_tpu.core.tx import Tx, TxInput, TxOutput
from upow_tpu.core.codecs import string_to_point
from upow_tpu.mine.engine import MiningJob, mine
from upow_tpu.node.app import Node
from upow_tpu.state.storage import ChainState
from upow_tpu.wallet.builders import WalletBuilder


def _samples(body: str) -> dict:
    out = {}
    for line in body.splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.partition(" ")
            out[name] = float(value.partition(" # ")[0])
    return out


def _spans(tree: dict, name: str) -> list:
    out = [tree] if tree["name"] == name else []
    for child in tree.get("spans", ()):
        out.extend(_spans(child, name))
    return out


def test_an_absent_outpoint_is_refused_by_the_resident_scan(tmp_path, keys):
    async def scenario():
        cluster = Cluster(tmp_path)
        cfg = make_config(tmp_path, "a")
        cfg.device.utxo_index = True
        node = Node(cfg)
        server = TestServer(node.app)
        await server.start_server()
        client = TestClient(server)
        node.self_url = f"http://127.0.0.1:{server.port}"
        node.started = True
        cluster.nodes.append(node), cluster.servers.append(server)
        cluster.clients.append(client)
        try:
            assert node.state.resident_indexes(), "the index did not arm"
            first = _samples(await (await client.get("/metrics")).text())
            for name in ("upow_index_apply_rows_total",
                         "upow_index_upload_bytes_total",
                         "upow_index_relayouts_total",
                         "upow_utxo_index_entries",
                         "upow_utxo_index_resident_bytes"):
                assert name in first, name
            assert first["upow_utxo_index_entries"] == 0
            built = [e for e in telemetry.events.snapshot()
                     if e["kind"] == "index_built"]
            assert {e["table"] for e in built[-7:]} >= \
                {"unspent_outputs", "inodes_ballot"}
            assert all(k in built[-1] for k in (
                "entries", "capacity", "resident_bytes", "seconds"))

            for _ in range(2):
                assert (await mine_via_api(client, keys["addr"])).get("ok")
            tx = await WalletBuilder(node.state).create_transaction(
                keys["d"], keys["addr2"], Decimal("0.25"))
            resp = await client.post("/push_tx", json={"tx_hex": tx.hex()})
            assert (await resp.json())["ok"]
            telemetry.reset()
            assert (await mine_via_api(client, keys["addr"])).get("ok")
            after = _samples(await (await client.get("/metrics")).text())
            # since the reset: one spend, two outputs and the coinbase;
            # four entries no longer fit a capacity of two
            assert after["upow_index_apply_rows_total"] == 4
            assert after["upow_index_relayouts_total"] == 1
            assert after["upow_index_upload_bytes_total"] > 0
            assert after["upow_utxo_index_entries"] == 4
            assert after["upow_span_block_spend_scan_count"] >= 1
            assert after["upow_span_index_apply_count"] >= 1
            traces = (await (await client.get("/debug/traces")).json())[
                "result"]
            root = [t for t in traces["recent"]
                    if t["name"] == "http.push_block"][-1]
            scan = _spans(root, "block.spend_scan")
            assert scan and scan[0]["fields"]["path"] == "resident"
            assert _spans(root, "index.apply") and \
                _spans(root, "block.utxo_apply")

            # the same outpoint spent again, rightly signed: absent from
            # the index, refused by the probe before any signature
            dup = Tx([TxInput(tx.inputs[0].tx_hash, tx.inputs[0].index)],
                     [TxOutput(keys["addr2"], 1)])
            pub = string_to_point(keys["addr"])
            dup.sign([keys["d"]], lambda _i: pub)
            info = (await (await client.get("/get_mining_info")).json())[
                "result"]
            from upow_tpu.core import clock
            from upow_tpu.core.difficulty import BLOCK_TIME

            clock.advance(BLOCK_TIME)
            header = BlockHeader(
                previous_hash=info["last_block"]["hash"],
                address=keys["addr"],
                merkle_root=miner_merkle_root([dup.hash()]),
                timestamp=timestamp(),
                difficulty_x10=int(Decimal(str(info["difficulty"])) * 10),
                nonce=0)
            header.nonce = mine(MiningJob(
                header.prefix_bytes(), info["last_block"]["hash"],
                Decimal(str(info["difficulty"]))), "python",
                batch=1 << 14, ttl=300).nonce
            telemetry.reset()
            res = await (await client.post("/push_block", json={
                "block_content": header.hex(), "txs": [dup.hex()],
                "block_no": info["last_block"]["id"] + 1})).json()
            assert not res.get("ok")
            assert "double spend in block" in str(res.get("error")), res
            traces = (await (await client.get("/debug/traces")).json())[
                "result"]
            root = [t for t in traces["recent"]
                    if t["name"] == "http.push_block"][-1]
            scan = _spans(root, "block.spend_scan")
            assert scan and scan[0]["fields"]["path"] == "resident"
            assert not _spans(root, "block.sig_verify")
            assert not _spans(root, "index.apply")
            last = _samples(await (await client.get("/metrics")).text())
            assert last["upow_utxo_index_entries"] == 4
            assert last["upow_block_height"] == 3
        finally:
            await cluster.close()

    asyncio.run(scenario())


def test_the_sql_scan_says_its_path(tmp_path, keys):
    async def scenario():
        cluster = Cluster(tmp_path)
        try:
            node, client = await cluster.add_node("a")
            for _ in range(2):
                assert (await mine_via_api(client, keys["addr"])).get("ok")
            tx = await WalletBuilder(node.state).create_transaction(
                keys["d"], keys["addr2"], Decimal("0.25"))
            await client.post("/push_tx", json={"tx_hex": tx.hex()})
            telemetry.reset()
            assert (await mine_via_api(client, keys["addr"])).get("ok")
            traces = (await (await client.get("/debug/traces")).json())[
                "result"]
            root = [t for t in traces["recent"]
                    if t["name"] == "http.push_block"][-1]
            scan = _spans(root, "block.spend_scan")
            assert scan and scan[0]["fields"]["path"] == "sql"
            assert not _spans(root, "index.apply")
        finally:
            await cluster.close()

    asyncio.run(scenario())


def _old_outpoints_hash(db, table: str) -> str:
    """``get_table_outpoints_hash`` as it was before ISSUE 50."""
    rows = db.execute(
        f"SELECT tx_hash, idx FROM {table} ORDER BY tx_hash, idx").fetchall()
    h = hashlib.sha256()
    for r in rows:
        h.update(f"{r['tx_hash']}{r['idx']}".encode())
    return h.hexdigest()


def test_the_streamed_fingerprint_is_the_old_digest_byte_for_byte(
        monkeypatch):
    rng = random.Random(827)
    state = ChainState()
    rows = [(rng.randbytes(32).hex(), rng.choice((0, 1, 7, 10, 255, 4096)),
             "addr%d" % (i % 50), rng.randrange(1, 10 ** 12), 0)
            for i in range(3000)]
    rows += [(rows[0][0], 9, "twice", 5, 0), (rows[0][0], 11, "twice", 5, 0)]
    state.db.executemany(
        "INSERT INTO unspent_outputs VALUES (?,?,?,?,?)", rows)
    state.db.commit()
    monkeypatch.setattr(ChainState, "_INDEX_CHUNK", 256)   # a dozen chunks
    want = _old_outpoints_hash(state.db, "unspent_outputs")
    assert asyncio.run(
        state.get_table_outpoints_hash("unspent_outputs")) == want
    assert asyncio.run(state.get_unspent_outputs_hash()) == want
    assert asyncio.run(state.get_table_outpoints_hash("inodes_ballot")) == \
        hashlib.sha256().hexdigest()
    # the build streams the same table in chunks: columns, not row objects
    state.enable_device_index()
    index = state.resident_indexes()["unspent_outputs"]
    assert len(index) == 3002 and index.stats()["twin_fingerprints"] == 0
    asked = [(r[0], r[1]) for r in rows[:40]] + [("ee" * 32, 0)]
    present, amounts = index.lookup_batch(asked)
    assert present.tolist() == [True] * 40 + [False]
    assert amounts[:40].tolist() == [r[3] for r in rows[:40]]
    state.close()


def _table_of(state, n: int) -> list:
    rng = random.Random(n)
    rows = [(rng.randbytes(32).hex(), i % 3, "addr%d" % (i % 9), 10 + i, 0)
            for i in range(n)]
    state.db.executemany(
        "INSERT INTO unspent_outputs VALUES (?,?,?,?,?)", rows)
    state.db.commit()
    return rows


def test_an_index_fault_after_the_commit_never_fails_the_block(monkeypatch):
    """``atomic()`` hands the block's delta to the index after the
    commit.  A fault there must not reach the caller, who would answer a
    durable block as refused: the block stands, the index goes off and
    SQL answers."""
    from upow_tpu.state.device_index import DeviceUtxoIndex

    state = ChainState()
    rows = _table_of(state, 300)
    state.enable_device_index()
    fresh = ("ab" * 32, 0)

    def broken(self, steps):
        raise RuntimeError("mirror fault")

    monkeypatch.setattr(DeviceUtxoIndex, "apply_steps", broken)

    async def accept():
        async with state.atomic():
            state.db.execute(
                "INSERT INTO unspent_outputs VALUES (?,?,?,?,?)",
                fresh + ("addr", 5, 0))
            state._index_add("unspent_outputs", [fresh])
            # the index answers from the last committed state meanwhile
            assert await state.outpoints_exist([fresh]) == [False]

    asyncio.run(accept())                       # raises nothing
    state.db.rollback()                         # nothing left to undo
    assert state.db.execute(
        "SELECT COUNT(*) FROM unspent_outputs").fetchone()[0] == 301
    assert state.resident_indexes() is None and state.index_stats() is None
    asked = [fresh, (rows[0][0], rows[0][1]), ("cd" * 32, 1)]
    assert asyncio.run(state.outpoints_exist(asked)) == [True, True, False]
    state.close()


def test_a_rolled_back_block_never_reaches_the_index():
    state = ChainState()
    _table_of(state, 300)
    state.enable_device_index()
    index = state.resident_indexes()["unspent_outputs"]
    fresh = ("ab" * 32, 0)

    async def refuse():
        async with state.atomic():
            state.db.execute(
                "INSERT INTO unspent_outputs VALUES (?,?,?,?,?)",
                fresh + ("addr", 5, 0))
            state._index_add("unspent_outputs", [fresh])
            raise ValueError("a rule failed")

    try:
        asyncio.run(refuse())
    except ValueError:
        pass
    else:
        raise AssertionError("the body's exception was swallowed")
    assert len(index) == 300 and state._index_stage is None
    assert asyncio.run(state.outpoints_exist([fresh])) == [False]
    state.close()


def test_the_sql_scan_reads_by_the_primary_key():
    """sqlite plans ``(tx_hash, idx) IN (VALUES ...)`` as a scan of the
    whole index a query (46 s a block over 4 M rows); the scan asks by
    the key's leading column and matches ``idx`` on the host."""
    state = ChainState()
    rows = _table_of(state, 2500)
    asked = [(r[0], r[1]) for r in rows[:2000]]
    asked[7] = (asked[7][0], asked[7][1] + 1)        # the hash, not the idx
    asked.append(("ee" * 32, 0))
    seen = []
    state.db.set_trace_callback(seen.append)
    verdicts = asyncio.run(state.outpoints_exist(asked))
    state.db.set_trace_callback(None)
    assert verdicts == [i != 7 for i in range(2000)] + [False]
    selects = [q for q in seen if q.startswith("SELECT")]
    assert len(selects) == 3                         # 900 hashes a query
    for q in selects:
        plan = " ".join(r[3] for r in state.db.execute(
            "EXPLAIN QUERY PLAN " + q).fetchall())
        assert "SEARCH unspent_outputs USING COVERING INDEX" in plan
        assert "SCAN unspent_outputs" not in plan
    state.close()
