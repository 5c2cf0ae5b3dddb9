"""Load-generator integration: the load generator against a real
in-process node, the readpath scenario, and the /debug/profile endpoint.

Kept separate from test_loadgen.py because these boot nodes and build
funded chain fixtures (seconds, not milliseconds); the pure-logic
determinism tests shouldn't pay for that.
"""

import asyncio

import pytest

from upow_tpu import telemetry
from upow_tpu.loadgen.population import PopulationSpec


@pytest.fixture(autouse=True)
def clean_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()
    telemetry.configure()


@pytest.fixture(autouse=True)
def restore_difficulty():
    """chain_with_utxo_fanout pins START_DIFFICULTY process-globally."""
    from upow_tpu.core import clock, difficulty

    saved = difficulty.START_DIFFICULTY
    yield
    difficulty.START_DIFFICULTY = saved
    clock.reset()


def test_loadgen_against_node():
    """The smoke population drives every endpoint class through the
    real node with zero transport errors, and the node's own SLO
    histograms (middleware-fed) agree on the request counts."""
    from upow_tpu.loadgen.harness import run_against_node

    spec = PopulationSpec.smoke()
    summary = asyncio.run(run_against_node(spec))

    eps = summary["endpoints"]
    assert {"get_address_info", "get_mining_info", "push_tx",
            "ws"} <= set(eps)
    for ep, row in eps.items():
        assert row["errors"] == 0, (ep, row)
        assert row["p50_ms"] > 0 and row["p95_ms"] >= row["p50_ms"]
    assert eps["push_tx"]["requests"] == spec.push_bursts * spec.burst_size

    # server-side SLO histograms saw the same traffic
    server = summary["server_slo"]
    assert server["push_tx"]["requests"] == eps["push_tx"]["requests"]
    assert server["get_mining_info"]["p95_ms"] > 0

    # ws churn reached the hub and every socket was closed again
    ws = summary["ws_hub"]
    assert ws["connects_total"] == spec.n_ws * spec.ws_churn
    assert ws["disconnects_total"] == ws["connects_total"]
    assert ws["total_connections"] == 0


def _tiny_readpath():
    """CI-sized readpath: still covers all four differential stages
    and one invalidation window per pass, in a couple of seconds."""
    from upow_tpu.loadgen.readpath import ReadpathSpec

    return ReadpathSpec(n_wallets=4, n_requests=120, block_every=60,
                        n_fan=4, n_per=6, history_limit=5, blocks_limit=5)


def test_readpath_differential_and_refusal(monkeypatch):
    """The readpath scenario's built-in differential holds across
    accept -> forced reorg -> re-accept; and when a probe DOES diverge
    the run refuses to report latencies (headline zeroed, the
    gate-tripping convention)."""
    from upow_tpu.loadgen import readpath as rp

    result = asyncio.run(rp.run_readpath(_tiny_readpath()))
    assert result["differential"]["ok"]
    assert result["differential"]["checks"] == 4 * 13  # stages x probes
    assert {s["stage"] for s in result["differential"]["stages"]} == \
        {"initial", "post_block", "post_reorg", "post_reaccept"}
    assert result["speedup_p99"] > 0
    assert result["bypass"]["requests"] == result["cached"]["requests"]
    assert result["cached_pass"]["hit_ratio"] > 0.5

    # forced divergence: corrupt what the cache hands back so the
    # second cached fetch of every probe disagrees with the bypass
    orig = rp._fetch
    flip = {"n": 0}

    async def corrupting(client, path, params, bypass):
        status, body, dt = await orig(client, path, params, bypass)
        if not bypass:
            flip["n"] += 1
            if flip["n"] % 2 == 0:
                body = body + b" "
        return status, body, dt

    monkeypatch.setattr(rp, "_fetch", corrupting)
    poisoned = asyncio.run(rp.run_readpath(_tiny_readpath()))
    assert poisoned["differential"]["ok"] is False
    assert poisoned["speedup_p99"] == 0.0
    assert "bypass" not in poisoned and "cached" not in poisoned
    stage0 = poisoned["differential"]["stages"][0]
    assert stage0["mismatches"]  # the evidence rides in the artifact


def test_node_metrics_exports_slo_series(tmp_path):
    """/metrics carries the middleware-fed SLO histogram for a route
    that was actually hit, and the full page validates."""
    from aiohttp.test_utils import TestClient, TestServer

    from upow_tpu.config import Config
    from upow_tpu.node.app import Node
    from upow_tpu.telemetry import exposition

    async def scenario():
        cfg = Config()
        cfg.node.db_path = ""
        cfg.node.seed_url = ""
        cfg.node.peers_file = str(tmp_path / "nodes.json")
        cfg.node.ip_config_file = ""
        cfg.log.path = ""
        cfg.log.console = False
        node = Node(cfg)
        server = TestServer(node.app)
        await server.start_server()
        client = TestClient(server)
        node.started = True
        try:
            for _ in range(3):
                await client.get("/get_mining_info")
            resp = await client.get("/metrics")
            text = await resp.text()
        finally:
            await client.close()
            await server.close()
            await node.close()
        return text

    text = asyncio.run(scenario())
    assert exposition.validate(text) == []
    assert "upow_slo_http_get_mining_info_latency_seconds_bucket" in text
    count_line = next(
        ln for ln in text.splitlines()
        if ln.startswith("upow_slo_http_get_mining_info_latency_seconds_count"))
    assert float(count_line.rsplit(" ", 1)[1]) >= 3
    # preregistered-but-unhit endpoints export all-zero series too
    assert "upow_slo_http_push_tx_latency_seconds_count 0" in text
    # /metrics itself is excluded from nothing — but /debug and /ws are
    assert "upow_slo_http_debug" not in text


def test_debug_profile_endpoint(tmp_path):
    """The opt-in /debug/profile endpoint: 404 when disabled, start/
    status/stop lifecycle when enabled, 400 on unknown actions."""
    from aiohttp.test_utils import TestClient, TestServer

    from upow_tpu import profiling
    from upow_tpu.config import Config
    from upow_tpu.node.app import Node

    def make_cfg(enabled):
        cfg = Config()
        cfg.node.db_path = ""
        cfg.node.seed_url = ""
        cfg.node.peers_file = str(tmp_path / "nodes.json")
        cfg.node.ip_config_file = ""
        cfg.log.path = ""
        cfg.log.console = False
        cfg.profile.enabled = enabled
        cfg.profile.trace_dir = str(tmp_path / "traces")
        return cfg

    async def scenario(enabled):
        node = Node(make_cfg(enabled))
        server = TestServer(node.app)
        await server.start_server()
        client = TestClient(server)
        node.started = True
        out = {}
        try:
            out["disabled"] = (await client.get("/debug/profile")).status
            if enabled:
                res = await client.get("/debug/profile",
                                       params={"action": "status"})
                out["status"] = await res.json()
                res = await client.get("/debug/profile",
                                       params={"action": "bogus"})
                out["bogus"] = res.status
                res = await client.get("/debug/profile",
                                       params={"action": "start"})
                out["start"] = await res.json()
                res = await client.get("/debug/profile",
                                       params={"action": "stop"})
                out["stop"] = await res.json()
        finally:
            await client.close()
            await server.close()
            await node.close()
            profiling.reset()
        return out

    off = asyncio.run(scenario(enabled=False))
    assert off["disabled"] == 404

    on = asyncio.run(scenario(enabled=True))
    assert on["disabled"] == 200
    assert on["status"]["ok"] and on["status"]["result"] == {
        "active": False}
    assert on["bogus"] == 400
    if on["start"]["ok"]:  # CPU backends may refuse to trace; both fine
        assert on["stop"]["ok"]
    else:
        assert "error" in on["start"]["result"]
