"""Node-layer integration tests: HTTP API, miner protocol, gossip, sync,
WebSocket push — multiple in-process nodes over real localhost sockets.

Each node gets an isolated in-memory ChainState; servers are aiohttp
TestServers on ephemeral ports, so gossip/sync exercise the real HTTP
plane (reference upow/node/main.py behaviors; SURVEY.md §4's "multi-node
harness" gap).  No pytest-asyncio in this environment: every test runs
its whole scenario inside one ``asyncio.run`` via :func:`run_cluster`.
"""

import asyncio
import json
from decimal import Decimal

import pytest
from aiohttp.test_utils import TestClient, TestServer

from upow_tpu.config import Config
from upow_tpu.core import curve, point_to_string
from upow_tpu.core.clock import timestamp
from upow_tpu.core.header import BlockHeader
from upow_tpu.core.merkle import miner_merkle_root
from upow_tpu.mine.engine import MiningJob, mine
from upow_tpu.node.app import GENESIS_PREV_HASH, Node
from upow_tpu.wallet.builders import WalletBuilder


@pytest.fixture(autouse=True)
def easy_difficulty(monkeypatch):
    from upow_tpu.core import clock, difficulty

    monkeypatch.setattr(difficulty, "START_DIFFICULTY", Decimal("1.0"))
    yield
    clock.reset()


@pytest.fixture
def keys():
    d, pub = curve.keygen(rng=4242)
    d2, pub2 = curve.keygen(rng=4343)
    return {"d": d, "addr": point_to_string(pub),
            "d2": d2, "addr2": point_to_string(pub2)}


def make_config(tmp_path, name: str) -> Config:
    cfg = Config()
    cfg.node.db_path = ""            # in-memory
    cfg.node.seed_url = ""           # no external seed
    cfg.node.peers_file = str(tmp_path / f"{name}_nodes.json")
    cfg.node.ip_config_file = ""
    cfg.node.sync_fetch_interval = 0.0  # no pacing floor in tests
    cfg.ws.enabled = True
    cfg.device.sig_backend = "host"
    cfg.log.path = ""
    cfg.log.console = False
    return cfg


class Cluster:
    """In-process nodes behind real localhost HTTP servers."""

    def __init__(self, tmp_path):
        self.tmp_path = tmp_path
        self.nodes = []
        self.servers = []
        self.clients = []

    async def add_node(self, name: str, state=None) -> tuple:
        node = Node(make_config(self.tmp_path, name), state=state)
        server = TestServer(node.app)
        await server.start_server()
        client = TestClient(server)
        node.self_url = f"http://127.0.0.1:{server.port}"
        node.started = True  # skip first-request bootstrap
        self.nodes.append(node)
        self.servers.append(server)
        self.clients.append(client)
        return node, client

    def url(self, i: int) -> str:
        return f"http://127.0.0.1:{self.servers[i].port}"

    async def close(self):
        # servers/clients first: a request draining through a live server
        # would otherwise reach a node whose db is already closed (its
        # middleware spawns work per response)
        for client in self.clients:
            await client.close()
        for server in self.servers:
            await server.close()
        for node in self.nodes:
            await node.close()


def run_cluster(tmp_path, scenario):
    """One event loop per test: build cluster, run scenario, tear down."""

    async def main():
        cluster = Cluster(tmp_path)
        try:
            await scenario(cluster)
        finally:
            await cluster.close()

    asyncio.run(main())


async def mine_via_api(client: TestClient, address: str,
                       _retried: bool = False) -> dict:
    """Drive the miner protocol over HTTP: get_mining_info → search →
    push_block (reference miner.py:126-156).

    Like the real miner loop, transient rejections refetch the template
    once: get_mining_info SPAWNS the interval mempool GC (app mirrors
    main.py:678-683), so a pending hash listed in the template can be
    evicted before push_block lands — the reference has the identical
    race and its miner just grabs a fresh template."""
    from upow_tpu.core import clock
    from upow_tpu.core.difficulty import BLOCK_TIME

    # one BLOCK_TIME per block: monotonic timestamps AND a neutral
    # retarget ratio, so arbitrarily long soaks keep difficulty ~1.0
    # (the retry must NOT advance again — one block, one tick)
    if not _retried:
        clock.advance(BLOCK_TIME)
    resp = await client.get("/get_mining_info")
    info = (await resp.json())["result"]
    last_block = dict(info["last_block"])
    prev_hash = last_block.get("hash", GENESIS_PREV_HASH)
    pending_hashes = info["pending_transactions_hashes"]
    header = BlockHeader(
        previous_hash=prev_hash,
        address=address,
        merkle_root=miner_merkle_root(pending_hashes),
        timestamp=timestamp(),
        difficulty_x10=int(Decimal(str(info["difficulty"])) * 10),
        nonce=0,
    )
    job = MiningJob(header.prefix_bytes(), prev_hash,
                    Decimal(str(info["difficulty"])))
    if last_block.get("hash"):
        result = mine(job, "python", batch=1 << 14, ttl=300)
        assert result.nonce is not None
        header.nonce = result.nonce
    resp = await client.post("/push_block", json={
        "block_content": header.hex(),
        "txs": pending_hashes,
        "block_no": last_block.get("id", 0) + 1,
    })
    res = await resp.json()
    if not res.get("ok") and not _retried and any(
            s in str(res.get("error", ""))
            for s in ("Transaction hash not found", "already syncing",
                      "Too old block", "Previous hash is not matched",
                      "block not valid")):
        # stale template (chain advanced / mempool GC'd / sync running):
        # the reference miner absorbs all of these by refetching
        import sys as _sys

        fresh = (await (await client.get("/get_mining_info")).json())["result"]
        print(f"mine_via_api retry: {res.get('error')!r}; template was "
              f"id={last_block.get('id')} now "
              f"id={fresh['last_block'].get('id')}", file=_sys.stderr)
        return await mine_via_api(client, address, _retried=True)
    return res


# --------------------------------------------------------------- basics ----

def test_root_and_supply(tmp_path, keys):
    async def scenario(cluster):
        node, client = await cluster.add_node("a")
        res = await (await client.get("/")).json()
        assert res["ok"] and "unspent_outputs_hash" in res
        res = await (await client.get("/get_supply_info")).json()
        assert res["ok"] and res["result"]["max_supply"] == 18884643.75

    run_cluster(tmp_path, scenario)


def test_mine_block_via_api(tmp_path, keys):
    async def scenario(cluster):
        node, client = await cluster.add_node("a")
        res = await mine_via_api(client, keys["addr"])
        assert res == {"ok": True}
        res = await (await client.get("/get_block",
                                      params={"block": "1"})).json()
        assert res["ok"]
        assert res["result"]["block"]["address"] == keys["addr"]
        res = await (await client.get(
            "/get_address_info", params={"address": keys["addr"]})).json()
        assert Decimal(res["result"]["balance"]) > 0

    run_cluster(tmp_path, scenario)


def test_push_tx_and_mempool_flow(tmp_path, keys):
    async def scenario(cluster):
        node, client = await cluster.add_node("a")
        await mine_via_api(client, keys["addr"])
        builder = WalletBuilder(node.state)
        tx = await builder.create_transaction(keys["d"], keys["addr2"], "1.5")
        res = await (await client.get("/push_tx",
                                      params={"tx_hex": tx.hex()})).json()
        assert res["ok"], res
        assert res["tx_hash"] == tx.hash()
        # duplicate rejected by the dedup cache
        res = await (await client.get("/push_tx",
                                      params={"tx_hex": tx.hex()})).json()
        assert not res["ok"]
        res = await (await client.get("/get_pending_transactions")).json()
        assert tx.hex() in res["result"]
        # mine it, then check balances and explorer views
        res = await mine_via_api(client, keys["addr"])
        assert res == {"ok": True}
        res = await (await client.get(
            "/get_address_info", params={"address": keys["addr2"]})).json()
        assert Decimal(res["result"]["balance"]) == Decimal("1.5")
        res = await (await client.get(
            "/get_transaction", params={"tx_hash": tx.hash()})).json()
        assert res["ok"] and res["result"]["is_confirm"] is True
        assert res["result"]["outputs"][0]["amount"] == 1.5
        res = await (await client.get(
            "/get_address_transactions",
            params={"address": keys["addr2"], "limit": "10"})).json()
        assert any(t["hash"] == tx.hash()
                   for t in res["result"]["transactions"])

    run_cluster(tmp_path, scenario)


def test_block_endpoints(tmp_path, keys):
    async def scenario(cluster):
        node, client = await cluster.add_node("a")
        await mine_via_api(client, keys["addr"])
        await mine_via_api(client, keys["addr"])
        res = await (await client.get(
            "/get_blocks", params={"offset": "1", "limit": "10"})).json()
        assert len(res["result"]) == 2
        assert res["result"][0]["block"]["id"] == 1
        res = await (await client.get(
            "/get_block_details", params={"block": "2"})).json()
        assert res["ok"] and len(res["result"]["transactions"]) == 1
        # tx_details page: explorer dicts instead of hex (this endpoint
        # raised TypeError until round 4 — get_blocks lacked the kwarg)
        res = await (await client.get(
            "/get_blocks_details",
            params={"offset": "1", "limit": "10"})).json()
        assert res["ok"] and len(res["result"]) == 2
        nice = res["result"][0]["transactions"][0]
        assert isinstance(nice, dict) and nice["is_coinbase"]
        res = await (await client.get(
            "/get_block", params={"block": "aa" * 32})).json()
        assert not res["ok"]

    run_cluster(tmp_path, scenario)


def test_governance_info_endpoints(tmp_path, keys):
    """The three explorer endpoints with no prior coverage (the
    /get_blocks_details TypeError hid for three rounds behind exactly
    this gap): /get_validators_info, /get_delegates_info, /dobby_info —
    exercised against populated ballots, plus a smoke GET over every
    read endpoint asserting parseable ok JSON."""

    async def scenario(cluster):
        node, client = await cluster.add_node("a")
        node.rate_limiter.enabled = False  # ~200 blocks mined via API
        from upow_tpu.wallet.builders import WalletBuilder

        builder = WalletBuilder(node.state)
        d_g, a_g = keys["d"], keys["addr"]
        for _ in range(22):  # validator registration needs 100 coins
            await mine_via_api(client, a_g)
        # governance state: stake -> validator-register -> delegate vote
        tx = await builder.create_stake_transaction(d_g, "3")
        await node.state.add_pending_transaction(tx)
        await mine_via_api(client, a_g)
        tx = await builder.create_validator_registration_transaction(d_g)
        await node.state.add_pending_transaction(tx)
        await mine_via_api(client, a_g)
        # a second actor stakes and votes for the validator
        d_o, a_o = keys["d2"], keys["addr2"]
        tx = await builder.create_transaction(d_g, a_o, "20")
        await node.state.add_pending_transaction(tx)
        await mine_via_api(client, a_g)
        tx = await builder.create_stake_transaction(d_o, "1")
        await node.state.add_pending_transaction(tx)
        await mine_via_api(client, a_g)
        tx = await builder.vote_as_delegate(d_o, 10, a_g)
        await node.state.add_pending_transaction(tx)
        await mine_via_api(client, a_g)

        # before any inode ballot exists: empty list, not an error
        assert await (await client.get("/get_validators_info")).json() == []
        res = await (await client.get("/dobby_info")).json()
        assert res["ok"] and res["result"] == []

        # populate the inode ballot too: a third actor becomes an inode
        # (1000 coins) and the validator votes for it
        from upow_tpu.core import curve as _curve, point_to_string as _pts

        d_i, pub_i = _curve.keygen(rng=0x1B0D)
        a_i = _pts(pub_i)
        for _ in range(170):  # fund the inode registration
            await mine_via_api(client, a_g)
        for chunk in ("400", "400", "210"):  # <256 inputs per send
            tx = await builder.create_transaction(d_g, a_i, chunk)
            await node.state.add_pending_transaction(tx)
            await mine_via_api(client, a_g)
        tx = await builder.create_stake_transaction(d_i, "1")
        await node.state.add_pending_transaction(tx)
        await mine_via_api(client, a_g)
        tx = await builder.create_inode_registration_transaction(d_i)
        await node.state.add_pending_transaction(tx)
        await mine_via_api(client, a_g)
        tx = await builder.vote_as_validator(d_g, 10, a_i)
        await node.state.add_pending_transaction(tx)
        await mine_via_api(client, a_g)

        validators = await (await client.get("/get_validators_info")).json()
        assert isinstance(validators, list) and len(validators) == 1
        assert validators[0]["validator"] == a_g
        assert validators[0]["vote"][0]["wallet"] == a_i
        filtered = await (await client.get(
            "/get_validators_info", params={"inode": a_i})).json()
        assert len(filtered) == 1

        # these two return BARE lists — reference parity, main.py:725/764
        delegates = await (await client.get("/get_delegates_info")).json()
        assert isinstance(delegates, list), delegates
        assert len(delegates) == 1 and delegates[0]["delegate"] == a_o
        assert delegates[0]["vote"][0]["wallet"] == a_g
        assert Decimal(delegates[0]["totalStake"]) == 1

        filtered = await (await client.get(
            "/get_delegates_info", params={"validator": a_g})).json()
        assert len(filtered) == 1

        # smoke matrix: every read endpoint answers parseable JSON with
        # its documented shape (ok envelope or reference bare list)
        for path, params, bare_list in [
            ("/get_address_info", {"address": a_g}, False),
            ("/get_address_transactions", {"address": a_g}, False),
            ("/get_block", {"block": "1"}, False),
            ("/get_block_details", {"block": "1"}, False),
            ("/get_blocks", {"offset": "1", "limit": "10"}, False),
            ("/get_blocks_details", {"offset": "1", "limit": "10"}, False),
            ("/get_delegates_info", {}, True),
            ("/get_mining_info", {}, False),
            ("/get_nodes", {}, False),
            ("/get_pending_transactions", {}, False),
            ("/get_supply_info", {}, False),
            ("/get_validators_info", {}, True),
            ("/dobby_info", {}, False),
        ]:
            res = await (await client.get(path, params=params)).json()
            if bare_list:
                assert isinstance(res, list), (path, res)
            else:
                assert res.get("ok"), (path, res)

    run_cluster(tmp_path, scenario)


# --------------------------------------------------------------- gossip ----

def test_gossip_block_propagation(tmp_path, keys):
    async def scenario(cluster):
        node_a, client_a = await cluster.add_node("a")
        node_b, client_b = await cluster.add_node("b")
        node_a.peers.add(cluster.url(1))
        res = await mine_via_api(client_a, keys["addr"])
        assert res == {"ok": True}
        for _ in range(100):
            if await node_b.state.get_next_block_id() == 2:
                break
            await asyncio.sleep(0.1)
        assert await node_b.state.get_next_block_id() == 2
        assert (await node_a.state.get_unspent_outputs_hash()
                == await node_b.state.get_unspent_outputs_hash())

    run_cluster(tmp_path, scenario)


def test_add_node_and_get_nodes(tmp_path, keys):
    async def scenario(cluster):
        node_a, client_a = await cluster.add_node("a")
        node_b, client_b = await cluster.add_node("b")
        res = await (await client_a.get(
            "/add_node", params={"url": cluster.url(1)})).json()
        assert res["ok"], res
        res = await (await client_a.get("/get_nodes")).json()
        assert cluster.url(1) in res["result"]
        res = await (await client_a.get(
            "/add_node", params={"url": cluster.url(1)})).json()
        assert not res["ok"]

    run_cluster(tmp_path, scenario)


# ----------------------------------------------------------------- sync ----

def test_sync_from_scratch(tmp_path, keys):
    async def scenario(cluster):
        node_a, client_a = await cluster.add_node("a")
        node_b, client_b = await cluster.add_node("b")
        for _ in range(3):
            assert (await mine_via_api(client_a, keys["addr"]))["ok"]
        res = await (await client_b.get(
            "/sync_blockchain", params={"node_url": cluster.url(0)})).json()
        assert res["ok"], res
        assert await node_b.state.get_next_block_id() == 4
        assert (await node_a.state.get_unspent_outputs_hash()
                == await node_b.state.get_unspent_outputs_hash())

    run_cluster(tmp_path, scenario)


def test_sync_multi_page_with_prefetch(tmp_path, keys):
    """Paged download with the speculative next-page fetch in flight:
    7 blocks at page size 2 -> 4 pages, every boundary crossed, and the
    final short page terminates the loop.  Fingerprints must match."""
    async def scenario(cluster):
        node_a, client_a = await cluster.add_node("a")
        node_b, client_b = await cluster.add_node("b")
        node_b.config.node.sync_page = 2
        for _ in range(7):
            assert (await mine_via_api(client_a, keys["addr"]))["ok"]
        res = await (await client_b.get(
            "/sync_blockchain", params={"node_url": cluster.url(0)})).json()
        assert res["ok"], res
        assert await node_b.state.get_next_block_id() == 8
        assert (await node_a.state.get_unspent_outputs_hash()
                == await node_b.state.get_unspent_outputs_hash())

    run_cluster(tmp_path, scenario)


def test_sync_fetch_pacing_floor(tmp_path, keys):
    """get_blocks fetches respect node.sync_fetch_interval even with the
    prefetch pipeline (the peer hard-limits /get_blocks to 40/min)."""
    async def scenario(cluster):
        import time as _t

        node_a, client_a = await cluster.add_node("a")
        node_b, client_b = await cluster.add_node("b")
        node_b.config.node.sync_page = 2
        node_b.config.node.sync_fetch_interval = 0.15
        for _ in range(5):
            assert (await mine_via_api(client_a, keys["addr"]))["ok"]
        t0 = _t.monotonic()
        res = await (await client_b.get(
            "/sync_blockchain", params={"node_url": cluster.url(0)})).json()
        elapsed = _t.monotonic() - t0
        assert res["ok"], res
        assert await node_b.state.get_next_block_id() == 6
        # 5 blocks / page 2 -> >=3 pages + the empty terminator = >=4
        # fetches; with a 0.15 s floor the 2nd..4th cost >=0.45 s total
        assert elapsed >= 0.45, elapsed

    run_cluster(tmp_path, scenario)


def test_sync_page_prefills_sig_verdicts(tmp_path, keys, monkeypatch):
    """Chain-sync batch ingest verifies the whole page's signatures in
    ONE dispatch; every per-block check must then be answered from the
    page verdicts (per-block dispatches would each pay their own
    host round trip).  Covers intra-page input resolution: the
    synced txs spend outputs created two blocks earlier in the same
    page."""
    async def scenario(cluster):
        node_a, client_a = await cluster.add_node("a")
        node_b, client_b = await cluster.add_node("b")
        await mine_via_api(client_a, keys["addr"])
        builder = WalletBuilder(node_a.state)
        tx = await builder.create_transaction(keys["d"], keys["addr2"], "2")
        await node_a.state.add_pending_transaction(tx)
        await mine_via_api(client_a, keys["addr"])
        # spend addr2's fresh output -> the sync page has an intra-page
        # input reference (block 3 spends block 2's tx output)
        builder2 = WalletBuilder(node_a.state)
        tx2 = await builder2.create_transaction(keys["d2"], keys["addr"], "1")
        await node_a.state.add_pending_transaction(tx2)
        await mine_via_api(client_a, keys["addr"])

        from upow_tpu.verify import block as block_mod
        from upow_tpu.verify.txverify import clear_sig_verdicts

        clear_sig_verdicts()  # drop verdicts cached by node A's intake
        # the test config resolves to the host path, where the prefill
        # is (correctly) skipped — force the device-node decision while
        # the actual batch still runs on host
        monkeypatch.setattr(node_b, "_prefill_worthwhile", lambda n: True)
        seen = []
        orig = block_mod.run_sig_checks_async

        async def spy(checks, **kw):
            pre = kw.get("precomputed")
            covered = pre is not None and all(c in pre for c in checks)
            seen.append((len(checks), pre, covered))
            return await orig(checks, **kw)

        monkeypatch.setattr(block_mod, "run_sig_checks_async", spy)
        res = await (await client_b.get(
            "/sync_blockchain", params={"node_url": cluster.url(0)})).json()
        assert res["ok"], res
        assert (await node_a.state.get_unspent_outputs_hash()
                == await node_b.state.get_unspent_outputs_hash())
        # every per-block signature check was answered by the page batch
        sig_calls = [s for s in seen if s[0]]
        assert sig_calls, "no signature checks ran during sync"
        for n, pre, covered in sig_calls:
            assert covered, "per-block check missed the page verdicts"

    run_cluster(tmp_path, scenario)


def test_peer_book_time_window_classes(tmp_path, monkeypatch):
    """PeerBook's three time classes (nodes_manager.py:97-160): active
    = messaged within 7 days (sampled for gossip), stale = heard from
    but beyond the window (NOT gossiped to, pruned after 90 days),
    never-seen = its own ≤10 sample; plus file persistence."""
    import time as _time

    from upow_tpu.config import NodeConfig
    from upow_tpu.node.peers import PeerBook

    now = [1_800_000_000.0]
    monkeypatch.setattr(_time, "time", lambda: now[0])

    cfg = NodeConfig()
    cfg.peers_file = str(tmp_path / "nodes.json")
    book = PeerBook(cfg)
    assert book.add("http://active.example:3006")
    assert book.add("stale.example:3006")  # scheme auto-prefixed
    assert book.add("http://unseen.example:3006/")  # trailing / stripped
    assert not book.add("http://unseen.example:3006")  # dedup

    book.update_last_message("http://active.example:3006")
    book.update_last_message("http://stale.example:3006")
    now[0] += 8 * 86400  # stale's message ages beyond the 7-day window
    book.update_last_message("http://active.example:3006")

    assert book.recent_nodes() == ["http://active.example:3006"]
    picks = book.propagate_nodes()
    assert "http://active.example:3006" in picks
    assert "http://unseen.example:3006" in picks
    assert "http://stale.example:3006" not in picks  # beyond the window

    # persistence: a fresh book on the same file sees the same classes
    book2 = PeerBook(cfg)
    assert set(book2.all_nodes()) == set(book.all_nodes())
    assert book2.recent_nodes() == ["http://active.example:3006"]

    # prune: 90 days of silence drops stale AND the never-seen entry
    # past its added age; the active peer survives via fresh messages
    now[0] += 83 * 86400
    book.update_last_message("http://active.example:3006")
    book.prune()
    assert book.all_nodes() == ["http://active.example:3006"]


def test_node_interface_unwraps_peer_errors():
    """A peer's error envelope (e.g. its 40/min rate-limit body) must
    surface as a readable error, not a KeyError on 'result'."""
    from upow_tpu.node.peers import NodeInterface

    assert NodeInterface._result({"ok": True, "result": [1]}) == [1]
    with pytest.raises(RuntimeError, match="Rate limit"):
        NodeInterface._result({"ok": False, "error": "Rate limit exceeded"})
    with pytest.raises(RuntimeError, match="peer error"):
        NodeInterface._result({})


def test_sync_retries_past_dead_peers(tmp_path, keys):
    """sync_blockchain with no named peer must work around dead peers in
    the book (connection errors raise out of fork detection) instead of
    giving up on the first unlucky random pick — the reference tries
    exactly one random peer per call (main.py:158-166)."""
    async def scenario(cluster):
        node_a, client_a = await cluster.add_node("a")
        node_b, client_b = await cluster.add_node("b")
        for _ in range(3):
            assert (await mine_via_api(client_a, keys["addr"]))["ok"]
        # two dead peers + the live one, with sampling pinned so the dead
        # peers are ALWAYS tried first (random order would skip the retry
        # path ~1/3 of runs and make this a flaky regression guard)
        dead = ["http://127.0.0.1:9", "http://127.0.0.1:10"]
        for url in dead:
            node_b.peers.add(url)
        node_b.peers.add(cluster.url(0))
        import upow_tpu.node.app as app_mod

        orig_sample = app_mod.random.sample
        app_mod.random.sample = lambda pop, k: dead + [cluster.url(0)]
        try:
            result = await node_b.sync_blockchain()
        finally:
            app_mod.random.sample = orig_sample
        assert result["ok"] is True, result
        assert result["peer"] == cluster.url(0)
        assert (await node_a.state.get_unspent_outputs_hash()
                == await node_b.state.get_unspent_outputs_hash())

    run_cluster(tmp_path, scenario)


def test_sync_with_transactions(tmp_path, keys):
    async def scenario(cluster):
        node_a, client_a = await cluster.add_node("a")
        node_b, client_b = await cluster.add_node("b")
        await mine_via_api(client_a, keys["addr"])
        builder = WalletBuilder(node_a.state)
        tx = await builder.create_transaction(keys["d"], keys["addr2"], "2")
        await node_a.state.add_pending_transaction(tx)
        await mine_via_api(client_a, keys["addr"])
        res = await (await client_b.get(
            "/sync_blockchain", params={"node_url": cluster.url(0)})).json()
        assert res["ok"], res
        assert (await node_b.state.get_address_balance(keys["addr2"])) == 2 * 10**8
        assert (await node_a.state.get_unspent_outputs_hash()
                == await node_b.state.get_unspent_outputs_hash())

    run_cluster(tmp_path, scenario)


def test_sync_with_device_txid_batch(tmp_path, keys, monkeypatch):
    """Identical-verdict: a page ingested with the device txid batch
    (sha256_batch_jnp seeding every tx's hash memo) accepts the same
    chain and fingerprint as host hashing (VERDICT r2 ask #5)."""

    async def scenario(cluster):
        node_a, client_a = await cluster.add_node("a")
        node_b, client_b = await cluster.add_node("b")
        node_b.config.device.txid_backend = "device"
        node_b.config.device.txid_min_batch = 2
        import upow_tpu.crypto.sha256 as sha_mod

        calls = []
        real = sha_mod.txid_batch

        def spy(payloads, **kw):
            out = real(payloads, **kw)
            calls.append((len(payloads), kw.get("backend")))
            return out

        monkeypatch.setattr(sha_mod, "txid_batch", spy)
        await mine_via_api(client_a, keys["addr"])
        builder = WalletBuilder(node_a.state)
        tx = await builder.create_transaction(keys["d"], keys["addr2"], "2")
        await node_a.state.add_pending_transaction(tx)
        await mine_via_api(client_a, keys["addr"])
        res = await (await client_b.get(
            "/sync_blockchain", params={"node_url": cluster.url(0)})).json()
        assert res["ok"], res
        assert calls and calls[0][1] == "device"  # batch path really ran
        assert (await node_b.state.get_address_balance(keys["addr2"])) \
            == 2 * 10**8
        assert (await node_a.state.get_unspent_outputs_hash()
                == await node_b.state.get_unspent_outputs_hash())
        # the seeded memos match independent hashing
        for h in await node_b.state.get_block_transaction_hashes(
                (await node_b.state.get_last_block())["hash"]):
            tx_b = await node_b.state.get_transaction(h)
            import hashlib

            assert tx_b.hash() == hashlib.sha256(
                bytes.fromhex(tx_b.hex())).hexdigest()

    run_cluster(tmp_path, scenario)


def test_sync_survives_faulty_device_txid(tmp_path, keys, monkeypatch):
    """ADVICE r3: a corrupted device digest that slips past the
    integrity sample seeds a wrong tx hash; the recomputed merkle then
    mismatches the header and the page is rejected — sync must fall
    back to host hashing for the retry instead of wedging on the faulty
    device (app.create_blocks merkle-mismatch retry)."""

    async def scenario(cluster):
        node_a, client_a = await cluster.add_node("a")
        node_b, client_b = await cluster.add_node("b")
        node_b.config.device.txid_backend = "device"
        node_b.config.device.txid_min_batch = 2
        import upow_tpu.crypto.sha256 as sha_mod

        await mine_via_api(client_a, keys["addr"])
        builder = WalletBuilder(node_a.state)
        tx = await builder.create_transaction(keys["d"], keys["addr2"], "2")
        await node_a.state.add_pending_transaction(tx)
        await mine_via_api(client_a, keys["addr"])
        target_payload = bytes.fromhex(tx.hex())

        calls = []
        real = sha_mod.txid_batch

        def faulty_device(payloads, **kw):
            out = real(payloads, backend="host")  # digests, right shapes
            calls.append(len(payloads))
            # one persistent bad lane: the send tx's digest is wrong on
            # EVERY device batch (the integrity sample can miss it; the
            # merkle check cannot)
            return [("0" * 64 if p == target_payload else d)
                    for p, d in zip(payloads, out)]

        monkeypatch.setattr(sha_mod, "txid_batch", faulty_device)
        res = await (await client_b.get(
            "/sync_blockchain", params={"node_url": cluster.url(0)})).json()
        assert res["ok"], res
        assert calls, "device txid path never ran"
        assert (await node_b.state.get_address_balance(keys["addr2"])) \
            == 2 * 10**8
        assert (await node_a.state.get_unspent_outputs_hash()
                == await node_b.state.get_unspent_outputs_hash())
        # no poisoned memo reached storage
        import hashlib

        for h in await node_b.state.get_block_transaction_hashes(
                (await node_b.state.get_last_block())["hash"]):
            tx_b = await node_b.state.get_transaction(h)
            # the STORED key equals the independently recomputed txid
            assert h == hashlib.sha256(
                bytes.fromhex(tx_b.hex())).hexdigest()

    run_cluster(tmp_path, scenario)


def test_sync_faulty_device_txid_content_absent_page(tmp_path, keys,
                                                     monkeypatch):
    """Same fault as above but the page entries carry NO 'content': the
    node rebuilds each header itself.  The rebuilt header must embed the
    raw-bytes merkle root, not the memo-derived one — otherwise
    check_block compares the corrupt device seed with itself and the
    block commits keyed under a wrong txid (review r4 finding)."""

    async def scenario(cluster):
        node_a, client_a = await cluster.add_node("a")
        node_b, client_b = await cluster.add_node("b")
        node_b.config.device.txid_backend = "device"
        node_b.config.device.txid_min_batch = 2
        import upow_tpu.crypto.sha256 as sha_mod

        await mine_via_api(client_a, keys["addr"])
        builder = WalletBuilder(node_a.state)
        tx = await builder.create_transaction(keys["d"], keys["addr2"], "2")
        await node_a.state.add_pending_transaction(tx)
        await mine_via_api(client_a, keys["addr"])
        target_payload = bytes.fromhex(tx.hex())

        real = sha_mod.txid_batch

        def faulty_device(payloads, **kw):
            out = real(payloads, backend="host")
            return [("0" * 64 if p == target_payload else d)
                    for p, d in zip(payloads, out)]

        monkeypatch.setattr(sha_mod, "txid_batch", faulty_device)
        page = await node_a.state.get_blocks(1, 500)
        for entry in page:
            entry["block"] = dict(entry["block"])
            entry["block"].pop("content", None)
        errors = []
        ok = await node_b.create_blocks(page, errors=errors)
        assert ok, errors
        assert (await node_a.state.get_unspent_outputs_hash()
                == await node_b.state.get_unspent_outputs_hash())
        import hashlib

        for h in await node_b.state.get_block_transaction_hashes(
                (await node_b.state.get_last_block())["hash"]):
            tx_b = await node_b.state.get_transaction(h)
            assert h == hashlib.sha256(
                bytes.fromhex(tx_b.hex())).hexdigest()

    run_cluster(tmp_path, scenario)


def test_fork_reorg_convergence(tmp_path, keys):
    """Partition: A and B mine divergent chains; B (shorter) syncs from A
    and reorgs onto A's chain (main.py:167-185's common-ancestor walk)."""

    async def scenario(cluster):
        node_a, client_a = await cluster.add_node("a")
        node_b, client_b = await cluster.add_node("b")
        # fork detection only engages past the reorg window (the reference
        # hardcodes id > 500, main.py:167; shrink the window to keep the
        # test chain short)
        node_a.config.node.sync_reorg_window = 4
        node_b.config.node.sync_reorg_window = 4
        for _ in range(5):  # common prefix longer than the window
            assert (await mine_via_api(client_a, keys["addr"]))["ok"]
        res = await (await client_b.get(
            "/sync_blockchain", params={"node_url": cluster.url(0)})).json()
        assert res["ok"], res
        # partition: A mines 2 more, B mines 1 (same genesis-key address —
        # the emission gate, manager.py:679-689 — but later timestamp, so
        # the chains fork)
        assert (await mine_via_api(client_a, keys["addr"]))["ok"]
        assert (await mine_via_api(client_a, keys["addr"]))["ok"]
        assert (await mine_via_api(client_b, keys["addr"]))["ok"]
        assert await node_a.state.get_next_block_id() == 8
        assert await node_b.state.get_next_block_id() == 7
        a_tip = (await node_a.state.get_last_block())["hash"]
        b_tip = (await node_b.state.get_last_block())["hash"]
        assert a_tip != b_tip  # genuinely diverged
        res = await (await client_b.get(
            "/sync_blockchain", params={"node_url": cluster.url(0)})).json()
        assert res["ok"], res
        assert await node_b.state.get_next_block_id() == 8
        assert (await node_b.state.get_last_block())["hash"] == a_tip
        assert (await node_a.state.get_unspent_outputs_hash()
                == await node_b.state.get_unspent_outputs_hash())

    run_cluster(tmp_path, scenario)


def test_push_block_gap_triggers_sync(tmp_path, keys):
    """A node receiving a too-new block with a Sender-Node header syncs
    from that sender (main.py:566-577)."""

    async def scenario(cluster):
        node_a, client_a = await cluster.add_node("a")
        node_b, client_b = await cluster.add_node("b")
        for _ in range(3):
            assert (await mine_via_api(client_a, keys["addr"]))["ok"]
        tip = await (await client_a.get(
            "/get_block", params={"block": "3"})).json()
        res = await (await client_b.post(
            "/push_block",
            json={"block_content": tip["result"]["block"]["content"],
                  "txs": [], "block_no": 3},
            headers={"Sender-Node": cluster.url(0)})).json()
        assert not res["ok"] and "sync" in res["error"]
        for _ in range(100):
            if await node_b.state.get_next_block_id() == 4:
                break
            await asyncio.sleep(0.1)
        assert await node_b.state.get_next_block_id() == 4

    run_cluster(tmp_path, scenario)


# ------------------------------------------------------------- websocket ---

def test_ws_new_block_broadcast(tmp_path, keys):
    async def scenario(cluster):
        node, client = await cluster.add_node("a")
        ws = await client.ws_connect("/ws")
        hello = json.loads((await ws.receive()).data)
        assert hello["type"] == "connection_established"
        await ws.send_str(json.dumps({"type": "subscribe_block"}))
        sub = json.loads((await ws.receive()).data)
        assert sub["type"] == "success"
        assert (await mine_via_api(client, keys["addr"]))["ok"]
        msg = json.loads((await asyncio.wait_for(ws.receive(), 10)).data)
        assert msg["type"] == "new_block"
        assert msg["data"]["block_no"] == 1
        await ws.send_str(json.dumps({"type": "ping"}))
        assert json.loads((await ws.receive()).data)["type"] == "pong"
        await ws.send_str(json.dumps({"type": "bogus"}))
        assert json.loads((await ws.receive()).data)["type"] == "error"
        await ws.close()

    run_cluster(tmp_path, scenario)


def test_ws_transaction_broadcast(tmp_path, keys):
    async def scenario(cluster):
        node, client = await cluster.add_node("a")
        await mine_via_api(client, keys["addr"])
        ws = await client.ws_connect("/ws")
        await ws.receive()  # connection_established
        await ws.send_str(json.dumps({"type": "subscribe_transaction"}))
        await ws.receive()  # success
        builder = WalletBuilder(node.state)
        tx = await builder.create_transaction(keys["d"], keys["addr2"], "1")
        res = await (await client.get("/push_tx",
                                      params={"tx_hex": tx.hex()})).json()
        assert res["ok"]
        msg = json.loads((await asyncio.wait_for(ws.receive(), 10)).data)
        assert msg["type"] == "new_transaction"
        assert msg["data"]["tx_hash"] == tx.hash()
        await ws.close()

    run_cluster(tmp_path, scenario)


def test_ws_limits(tmp_path, keys):
    """Reference socket limits (socket_config.py:6-43): per-IP connection
    cap, per-connection message rate limit, unsubscribe semantics —
    including unsubscribe-without-subscribe, and subscribe_transaction
    actually working (unreachable in the reference, which omits it from
    ALLOWED_MESSAGE_TYPES)."""
    async def scenario(cluster):
        node, client = await cluster.add_node("a")
        node.ws_hub.cfg.max_per_user = 2
        node.ws_hub.cfg.rate_limit_per_minute = 5

        ws1 = await client.ws_connect("/ws")
        await ws1.receive()
        ws2 = await client.ws_connect("/ws")
        await ws2.receive()
        # third connection from the same IP: rejected with 403
        import aiohttp

        with pytest.raises(aiohttp.WSServerHandshakeError):
            await client.ws_connect("/ws")

        # rate limit: 5 allowed per minute, the 6th gets RATE_LIMIT
        for _ in range(5):
            await ws1.send_str(json.dumps({"type": "ping"}))
            assert json.loads((await ws1.receive()).data)["type"] == "pong"
        await ws1.send_str(json.dumps({"type": "ping"}))
        err = json.loads((await ws1.receive()).data)
        assert err["type"] == "error"
        assert err["error_code"] == "RATE_LIMIT_EXCEEDED"

        # unsubscribe without subscribe -> NOT_SUBSCRIBED
        await ws2.send_str(json.dumps({"type": "unsubscribe_block"}))
        err = json.loads((await ws2.receive()).data)
        assert err["error_code"] == "NOT_SUBSCRIBED"
        # subscribe/unsubscribe transaction round-trip
        await ws2.send_str(json.dumps({"type": "subscribe_transaction"}))
        assert json.loads((await ws2.receive()).data)["type"] == "success"
        await ws2.send_str(json.dumps({"type": "unsubscribe_transaction"}))
        assert json.loads((await ws2.receive()).data)["type"] == "success"
        # malformed JSON -> INVALID_JSON, connection stays up
        await ws2.send_str("{nope")
        err = json.loads((await ws2.receive()).data)
        assert err["error_code"] == "INVALID_JSON"

        stats = node.ws_hub.get_stats()
        assert stats["total_connections"] == 2
        await ws1.close()
        await ws2.close()

    run_cluster(tmp_path, scenario)


def test_three_node_partition_heal(tmp_path, keys):
    """Three nodes with live gossip: C is partitioned away while A and B
    extend the chain (gossip keeps A/B converged in real time); C mines
    its own fork meanwhile.  When the partition heals, C syncs and all
    three reach identical UTXO fingerprints (VERDICT #9 / SURVEY §4)."""

    async def scenario(cluster):
        node_a, client_a = await cluster.add_node("a")
        node_b, client_b = await cluster.add_node("b")
        node_c, client_c = await cluster.add_node("c")
        for n in (node_a, node_b, node_c):
            n.config.node.sync_reorg_window = 4

        # full mesh peer books
        for i, n in enumerate((node_a, node_b, node_c)):
            for j in range(3):
                if j != i:
                    n.peers.add(cluster.url(j))

        async def converged(nodes, block_id, tries=100):
            for _ in range(tries):
                ids = [await n.state.get_next_block_id() for n in nodes]
                if all(x == block_id for x in ids):
                    return True
                await asyncio.sleep(0.1)
            return False

        # common prefix: mined on A, gossip carries it to B and C
        for _ in range(5):
            assert (await mine_via_api(client_a, keys["addr"]))["ok"]
        assert await converged((node_a, node_b, node_c), 6)

        # partition C: drop it from A/B's books and empty C's own
        for n in (node_a, node_b):
            n.peers.remove(cluster.url(2))
        node_c.peers.remove(cluster.url(0))
        node_c.peers.remove(cluster.url(1))

        # majority side extends by 2 (A mines, gossip reaches B);
        # C mines a 1-block fork of its own
        assert (await mine_via_api(client_a, keys["addr"]))["ok"]
        assert (await mine_via_api(client_b, keys["addr"]))["ok"]
        assert await converged((node_a, node_b), 8)
        assert (await mine_via_api(client_c, keys["addr"]))["ok"]
        assert await node_c.state.get_next_block_id() == 7
        a_tip = (await node_a.state.get_last_block())["hash"]
        assert (await node_c.state.get_last_block())["hash"] != a_tip

        # heal: C relearns a peer and syncs — reorgs onto the longer chain
        node_c.peers.add(cluster.url(0))
        res = await (await client_c.get(
            "/sync_blockchain", params={"node_url": cluster.url(0)})).json()
        assert res["ok"], res
        fingerprints = {
            await n.state.get_unspent_outputs_hash()
            for n in (node_a, node_b, node_c)
        }
        assert len(fingerprints) == 1
        assert (await node_c.state.get_last_block())["hash"] == a_tip

    run_cluster(tmp_path, scenario)


def test_miner_cli_against_node(tmp_path, keys):
    """The actual miner client (fetch get_mining_info → merkle over
    pending hashes → search → push_block) against a live node, including
    a pending transaction it must confirm (VERDICT weak #8: the MES from
    SURVEY §7.3, previously only exercised by hand)."""

    async def scenario(cluster):
        from upow_tpu.core import clock
        from upow_tpu.mine import miner as miner_cli

        node, client = await cluster.add_node("a")
        node_url = cluster.url(0) + "/"

        loop = asyncio.get_running_loop()

        def mine_once():
            return miner_cli.run(keys["addr"], node_url, "python",
                                 batch=1 << 14, ttl=300, once=True)

        # genesis block (free PoW), then fund a pending tx
        clock.advance(1)
        assert await loop.run_in_executor(None, mine_once) == 0
        # at difficulty 1.0 the losing worker may legally land a second
        # block before the first-finder reap: >= 2, not == 2
        assert await node.state.get_next_block_id() >= 2

        builder = WalletBuilder(node.state)
        tx = await builder.create_transaction(keys["d"], keys["addr2"], "1.5")
        resp = await client.get("/push_tx", params={"tx_hex": tx.hex()})
        assert (await resp.json())["ok"]

        clock.advance(1)
        assert await loop.run_in_executor(None, mine_once) == 0
        assert await node.state.get_next_block_id() == 3
        got = await node.state.get_transaction(tx.hash())
        assert got is not None
        bal = await node.state.get_address_balance(keys["addr2"])
        assert bal == int(Decimal("1.5") * 10**8)

    run_cluster(tmp_path, scenario)


def test_ipfilter_endpoint_slash_normalization(tmp_path):
    """block_endpoints entries match with or without a leading slash
    (docs/DEPLOY.md example must actually block)."""
    cfg_path = tmp_path / "ip_config.json"
    cfg_path.write_text(json.dumps({
        "whitelist": [], "blocklist": [],
        "block_endpoints": ["/send_to_address", "get_nodes"]}))
    from upow_tpu.node.ipfilter import IpFilter

    f = IpFilter(str(cfg_path))
    assert not f.allowed("9.9.9.9", endpoint="/send_to_address")
    assert not f.allowed("9.9.9.9", endpoint="/get_nodes")
    assert f.allowed("9.9.9.9", endpoint="/get_block")


def test_ipfilter_whitelist_is_exclusive(tmp_path):
    """Reference ip_manager.py:42-44 semantics: a NON-EMPTY whitelist
    admits only listed IPs (the blocklist is then irrelevant); without
    one the blocklist denies; endpoint blocks bind everyone — even
    whitelisted callers (main.py:306 has no bypass)."""
    from upow_tpu.node.ipfilter import IpFilter

    cfg_path = tmp_path / "ip_config.json"
    cfg_path.write_text(json.dumps({
        "whitelist": ["1.1.1.1"], "blocklist": ["2.2.2.2"],
        "block_endpoints": ["/get_nodes"]}))
    f = IpFilter(str(cfg_path))
    assert f.allowed("1.1.1.1")
    assert not f.allowed("3.3.3.3")  # not listed -> denied (exclusive)
    assert not f.allowed("2.2.2.2")
    # endpoint blocks apply even to the whitelisted IP
    assert not f.allowed("1.1.1.1", endpoint="/get_nodes")

    cfg_path.write_text(json.dumps({
        "whitelist": [], "blocklist": ["2.2.2.2"], "block_endpoints": []}))
    f = IpFilter(str(cfg_path))
    assert f.allowed("3.3.3.3")  # no whitelist -> default allow
    assert not f.allowed("2.2.2.2")  # blocklist active without whitelist


def test_rate_limits(tmp_path, keys):
    """slowapi-parity limits: GET / allows 3/minute then 429s; unlisted
    endpoints (push_block et al.) are never limited (main.py:267...)."""

    async def scenario(cluster):
        node, client = await cluster.add_node("a")
        for _ in range(3):
            assert (await client.get("/")).status == 200
        assert (await client.get("/")).status == 429
        # unlimited endpoint still fine
        for _ in range(6):
            assert (await client.get("/get_nodes")).status == 200

    run_cluster(tmp_path, scenario)


def test_miner_cli_reference_positionals(tmp_path, keys):
    """`python -m upow_tpu.mine.miner <addr> <workers> <node_url>` — the
    reference's positional CLI shape (miner.py:126-156) REALLY spawns
    worker subprocesses on disjoint shards; one of them mines the
    genesis block (real wall clock: the children cannot see the test's
    clock offset, and genesis needs no predecessor timestamp)."""

    async def scenario(cluster):
        from upow_tpu.core import clock
        from upow_tpu.mine import miner as miner_cli

        node, client = await cluster.add_node("a")
        node_url = cluster.url(0) + "/"
        clock.reset()  # children use the real clock; so must the node
        loop = asyncio.get_running_loop()

        def mine_once():
            return miner_cli.main([keys["addr"], "2", node_url,
                                   "--device", "python",
                                   "--batch", str(1 << 14), "--once"])

        assert await loop.run_in_executor(None, mine_once) == 0
        # at difficulty 1.0 the losing worker may legally land a second
        # block before the first-finder reap: >= 2, not == 2
        assert await node.state.get_next_block_id() >= 2
        # tpu fan-out is refused rather than letting N processes fight
        # over the single-client chip
        assert miner_cli.main([keys["addr"], "2", node_url,
                               "--device", "tpu", "--once"]) == 2

    run_cluster(tmp_path, scenario)


# ------------------------------------------------------- nodeless wallet ---

def test_nodeless_wallet_end_to_end(tmp_path, keys):
    """The HTTP-only wallet (reference nodeless_wallet.py): balance read,
    send built purely from get_address_info, push via push_tx, mined,
    and the consolidation path across multiple small outputs."""
    from upow_tpu.wallet.nodeless import NodelessWallet

    async def scenario(cluster):
        node, client = await cluster.add_node("nw")
        # fund the sender with two coinbases
        await mine_via_api(client, keys["addr"])
        await mine_via_api(client, keys["addr"])
        w = NodelessWallet(cluster.url(0))

        bal, pending = await w.get_balance(keys["addr"])
        assert bal == Decimal("12")  # two 6-coin rewards

        tx_hash = await w.send(keys["d"], keys["addr2"], Decimal("2.5"))
        pend = await (await client.get("/get_pending_transactions")).json()
        import hashlib as _h

        assert [
            _h.sha256(bytes.fromhex(t)).hexdigest() for t in pend["result"]
        ] == [tx_hash]
        await mine_via_api(client, keys["addr"])
        bal2, _ = await w.get_balance(keys["addr2"])
        assert bal2 == Decimal("2.5")

        # recipient now has 1 output; sender has several (change + reward):
        # consolidation merges them into one self-send
        consolidated = await w.consolidate_outputs(keys["d"])
        assert consolidated is not None
        await mine_via_api(client, keys["addr"])
        info = await w.get_address_info(keys["addr"])
        spendable = [o for o in info["spendable_outputs"]]
        # one merged output + the newest coinbase reward
        assert len(spendable) == 2

        # insufficient funds raises the reference's error message
        import pytest as _pytest

        with _pytest.raises(ValueError, match="enough funds"):
            await w.create_transaction(keys["d2"], keys["addr"],
                                       Decimal("1000000"))

    run_cluster(tmp_path, scenario)


# ------------------------------------------------------ randomized soak ----

def test_randomized_churn_soak(tmp_path, keys, monkeypatch):
    """Randomized three-node churn: each round, a random node mines (with
    a random wallet tx in flight half the time), occasionally a node is
    partitioned off to mine a private fork and then healed via sync.
    Invariants after every heal: one UTXO fingerprint across nodes, and a
    full replay of node A's chain reproduces its live tables.

    UPOW_SOAK_ROUNDS (default 6) scales the run for longer soaks.
    """
    import os
    import random as _random

    from upow_tpu.core import difficulty as _diff

    rng = _random.Random(20260730)
    rounds = int(os.environ.get("UPOW_SOAK_ROUNDS", "6"))
    # pin the retarget: the soak's orphaned-fork clock ticks make the
    # 100-block window ratio < 1, and sub-1.0 difficulty is UNMINABLE by
    # protocol (the reference's [-0:] whole-hash quirk, manager.py:148-151,
    # replicated and differential-tested in test_core_consensus).  The
    # retarget rule itself has dedicated boundary tests.
    monkeypatch.setattr(_diff, "next_difficulty",
                        lambda *_a, **_k: Decimal("1.0"))
    # lift the genesis-key emission gate's height cutoff: past block
    # 10000 only chains with active inodes may mine (manager.py:679-689
    # parity, tested on its own), and a >=10k-round soak chain crosses
    # that height with no registered inodes — by consensus design, not
    # as a soak finding
    from upow_tpu.verify import block as _block_mod

    monkeypatch.setattr(_block_mod, "LAST_BLOCK_FOR_GENESIS_KEY", 10 ** 9)

    async def scenario(cluster):
        from upow_tpu.state.pg import PgChainState
        from upow_tpu.state.pgdriver import MockPgDriver

        nodes, clients = [], []
        for name in ("a", "b", "c"):
            # node c runs the PostgreSQL backend (mock driver) — the
            # cluster churn must converge identically across backends
            state = PgChainState(driver=MockPgDriver()) if name == "c" \
                else None
            n, c = await cluster.add_node(name, state=state)
            # fork detection only runs when the chain is LONGER than the
            # reorg window (reference main.py:167) — keep it smaller than
            # the funding prefix below
            n.config.node.sync_reorg_window = 4
            n.rate_limiter.enabled = False  # soak load: not a client test
            nodes.append(n)
            clients.append(c)
        for i, n in enumerate(nodes):
            for j in range(3):
                if j != i:
                    n.peers.add(cluster.url(j))

        async def converge(idx_set, tries=120):
            for _ in range(tries):
                ids = [await nodes[i].state.get_next_block_id()
                       for i in idx_set]
                if len(set(ids)) == 1:
                    return ids[0]
                await asyncio.sleep(0.1)
            raise AssertionError(
                f"no convergence: {[(i, await nodes[i].state.get_next_block_id()) for i in idx_set]}")

        # funding prefix, longer than the reorg window
        for _ in range(6):
            res = await mine_via_api(clients[0], keys["addr"])
            assert res["ok"], res
        await converge({0, 1, 2})

        for rnd in range(rounds):
            miner_i = rng.randrange(3)
            if rng.random() < 0.5:
                # random spend into the mempool of the mining node
                builder = WalletBuilder(nodes[miner_i].state)
                try:
                    tx = await builder.create_transaction(
                        keys["d"], keys["addr2"],
                        Decimal(rng.randrange(1, 40)) / 10)
                    await nodes[miner_i].state.add_pending_transaction(tx)
                except ValueError:
                    pass  # no spendable outputs on this node's view yet
            res = await mine_via_api(clients[miner_i], keys["addr"])
            assert res["ok"], res
            await converge({0, 1, 2})

            if rng.random() < 0.4:
                # partition a random victim; it mines a private fork
                victim = rng.randrange(3)
                others = [i for i in range(3) if i != victim]
                for i in others:
                    nodes[i].peers.remove(cluster.url(victim))
                for i in others:
                    nodes[victim].peers.remove(cluster.url(i))
                for _ in range(rng.randrange(1, 3)):
                    # NB the genesis-key emission gate (manager.py:679-689):
                    # with no registered inodes only the genesis address may
                    # mine, so the fork differs by timestamp, not miner
                    res = await mine_via_api(clients[victim], keys["addr"])
                    assert res["ok"], res
                # majority extends further so the victim must reorg
                for _ in range(3):
                    res = await mine_via_api(clients[others[0]],
                                             keys["addr"])
                    assert res["ok"], res
                await converge(set(others))
                # heal
                for i in others:
                    nodes[i].peers.add(cluster.url(victim))
                    nodes[victim].peers.add(cluster.url(i))
                res = await (await clients[victim].get(
                    "/sync_blockchain",
                    params={"node_url": cluster.url(others[0])})).json()
                assert res["ok"], res
                await converge({0, 1, 2})

            fps = {await n.state.get_unspent_outputs_hash() for n in nodes}
            assert len(fps) == 1, f"fingerprint divergence in round {rnd}"

        # replay oracle on node A
        live = await nodes[0].state.get_unspent_outputs_hash()
        await nodes[0].state.rebuild_utxos()
        assert await nodes[0].state.get_unspent_outputs_hash() == live

    run_cluster(tmp_path, scenario)


def test_mining_info_ten_tx_template(tmp_path, keys):
    """get_mining_info hands miners at most 10 full txs but ALL pending
    hashes, with merkle_root over those first 10 (reference
    main.py:675-695 — the '10-tx template' quirk)."""

    async def scenario(cluster):
        from upow_tpu.core.merkle import merkle_root as _mr
        from upow_tpu.core.tx import tx_from_hex as _fromhex

        node, client = await cluster.add_node("a")
        for _ in range(12):
            await mine_via_api(client, keys["addr"])
        builder = WalletBuilder(node.state)
        hashes = set()
        for i in range(12):
            tx = await builder.create_transaction(
                keys["d"], keys["addr2"], Decimal(i + 1) / 10)
            res = await (await client.get(
                "/push_tx", params={"tx_hex": tx.hex()})).json()
            assert res["ok"], res
            hashes.add(tx.hash())
        info = (await (await client.get("/get_mining_info")).json())["result"]
        assert len(info["pending_transactions"]) == 10
        assert set(info["pending_transactions_hashes"]) == hashes
        assert len(info["pending_transactions_hashes"]) == 12
        first_ten = [_fromhex(t, check_signatures=False)
                     for t in info["pending_transactions"]]
        assert info["merkle_root"] == _mr(first_ten)

    run_cluster(tmp_path, scenario)


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _boot_node_process(cfg_path, port, log_path):
    """Launch `node.run --config` as a real child and poll until the API
    answers.  On death or timeout: kill the child and raise with the
    log tail (an orphan would hold the port and db for the whole run)."""
    import json as _json
    import subprocess
    import sys
    import time
    import urllib.request

    with open(log_path, "wb") as sink:  # child owns its fd copy
        proc = subprocess.Popen(
            [sys.executable, "-m", "upow_tpu.node.run", "--config",
             str(cfg_path)], stdout=sink, stderr=subprocess.STDOUT)
    deadline = time.time() + 60
    last_err = None
    while time.time() < deadline:
        if proc.poll() is not None:
            raise AssertionError(
                "node died on boot: "
                + log_path.read_bytes().decode(errors="replace")[-2000:])
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/get_mining_info",
                    timeout=2) as resp:
                _json.loads(resp.read())
            return proc
        except Exception as e:  # noqa: BLE001 - retry until deadline
            last_err = e
            time.sleep(0.5)
    proc.kill()
    raise AssertionError(
        f"node never came up ({last_err}): "
        + log_path.read_bytes().decode(errors="replace")[-2000:])


def test_node_survives_sigkill_and_resumes(tmp_path, keys):
    """Crash durability (SURVEY §5 checkpoint/resume): a file-backed
    node is SIGKILLed — no shutdown hooks, no flush — restarted on the
    same database, and must come back with the identical chain head AND
    UTXO fingerprint (both via the HTTP surface) and keep accepting
    blocks.  sqlite WAL plus the single-transaction accept make every
    accepted block durable the moment push_block returns ok."""
    import json as _json
    import signal
    import subprocess
    import time
    import urllib.request

    from decimal import Decimal

    from upow_tpu.core.header import BlockHeader
    from upow_tpu.core.merkle import miner_merkle_root
    from upow_tpu.mine.engine import MiningJob, mine as engine_mine

    port = _free_port()
    cfg = {
        "node": {
            "port": port,
            "db_path": str(tmp_path / "durable.db"),
            "seed_url": "",
            "peers_file": str(tmp_path / "nodes.json"),
            "ip_config_file": "",
        },
        "device": {"sig_backend": "host"},
        "log": {"path": "", "console": False},
    }
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(_json.dumps(cfg))

    def http(path, data=None):
        url = f"http://127.0.0.1:{port}{path}"
        req = urllib.request.Request(
            url, data=_json.dumps(data).encode() if data else None,
            headers={"Content-Type": "application/json"} if data else {})
        with urllib.request.urlopen(req, timeout=10) as resp:
            return _json.loads(resp.read())

    def boot(log_name):
        return _boot_node_process(cfg_path, port, tmp_path / log_name)

    last_ts = [0]

    def mine_one():
        while int(time.time()) <= last_ts[0]:
            time.sleep(0.2)
        mi = http("/get_mining_info")["result"]
        last = dict(mi["last_block"])
        prev = last.get("hash", GENESIS_PREV_HASH)
        ts = int(time.time())
        last_ts[0] = ts
        header = BlockHeader(
            previous_hash=prev, address=keys["addr"],
            merkle_root=miner_merkle_root([]), timestamp=ts,
            difficulty_x10=int(Decimal(str(mi["difficulty"])) * 10),
            nonce=0)
        if last.get("hash"):
            job = MiningJob(header.prefix_bytes(), prev,
                            Decimal(str(mi["difficulty"])))
            r = engine_mine(job, "native", batch=1 << 22, ttl=120)
            assert r.nonce is not None
            header.nonce = r.nonce
        out = http("/push_block", {
            "block_content": header.hex(), "txs": [],
            "block_no": last.get("id", 0) + 1})
        assert out["ok"], out

    proc = boot("node1.log")
    try:
        for _ in range(3):
            mine_one()
        head_before = http("/get_mining_info")["result"]["last_block"]
        fp_before = http("/")["unspent_outputs_hash"]
        assert head_before["id"] == 3
    finally:
        proc.send_signal(signal.SIGKILL)  # crash, not shutdown
        proc.wait(timeout=10)

    proc = boot("node2.log")
    try:
        head_after = http("/get_mining_info")["result"]["last_block"]
        assert head_after["hash"] == head_before["hash"], \
            (head_before, head_after)
        # the UTXO set survived the crash byte-identically
        assert http("/")["unspent_outputs_hash"] == fp_before
        mine_one()  # the resumed node keeps accepting
        assert http("/get_mining_info")["result"]["last_block"]["id"] == 4
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_launcher_boots_from_config_alone(tmp_path):
    """`python -m upow_tpu.node.run --config cfg.json` in a real child
    process: the node must come up from config alone (SURVEY §5 config
    axis), serve the API, and shut down cleanly on SIGTERM."""
    import json as _json
    import signal
    import subprocess
    import urllib.request

    port = _free_port()
    cfg = {
        "node": {
            "port": port,
            "db_path": str(tmp_path / "boot.db"),
            "seed_url": "",
            "peers_file": str(tmp_path / "nodes.json"),
            "ip_config_file": "",
        },
        "device": {"sig_backend": "host"},
        "log": {"path": str(tmp_path / "app.log"), "console": False},
    }
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(_json.dumps(cfg))

    proc = _boot_node_process(cfg_path, port, tmp_path / "child.log")
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/get_mining_info",
                timeout=10) as resp:
            body = _json.loads(resp.read())
        assert body["ok"] and "difficulty" in body["result"]
        # the rotating-file logger wrote where config said
        assert (tmp_path / "app.log").exists()
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise AssertionError("node did not exit on SIGTERM")


def test_metrics_endpoint(tmp_path, keys):
    """Prometheus text exposition (beyond-reference observability): chain
    height and mempool gauges move with the chain; span series appear
    after a block accept."""

    async def scenario(cluster):
        node, client = await cluster.add_node("a")
        res = await mine_via_api(client, keys["addr"])
        assert res.get("ok")

        builder = WalletBuilder(node.state)
        tx = await builder.create_transaction(
            keys["d"], keys["addr2"], Decimal("0.25"))
        resp = await client.post("/push_tx", json={"tx_hex": tx.hex()})
        assert (await resp.json())["ok"]

        resp = await client.get("/metrics")
        assert resp.status == 200
        assert resp.content_type == "text/plain"
        body = await resp.text()
        metrics = {}
        for line in body.splitlines():
            if line and not line.startswith("#"):
                name, _, value = line.partition(" ")
                # bucket lines may carry an OpenMetrics exemplar suffix:
                # "<value> # {trace_id=...} <exemplar_value>"
                metrics[name] = float(value.partition(" # ")[0])
        assert metrics["upow_block_height"] == 1
        assert metrics["upow_mempool_transactions"] == 1
        assert metrics["upow_node_syncing"] == 0
        assert "upow_ws_connections" in metrics
        # the push_tx intake above verified one signature -> cached
        assert metrics["upow_sig_cache_entries"] >= 1
        assert metrics["upow_sig_cache_misses_total"] >= 1
        # the block accept above registered timing spans
        assert any(k.startswith("upow_span_") and k.endswith("_count")
                   and v >= 1 for k, v in metrics.items())

    run_cluster(tmp_path, scenario)
