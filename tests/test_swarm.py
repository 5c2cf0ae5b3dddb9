"""Swarm simulator tests: link matrix, loopback transport, scenario
runs, and the artifact determinism contract (ISSUE 8 acceptance).

Scenario tests call :func:`run_scenario` — the same entry the CLI and
CI matrix use — so what's pinned here is the shipped artifact, not a
test-only code path.  Everything below runs in well under a minute;
the 50-node and long-partition variants are ``@pytest.mark.slow``.
"""

import asyncio

import pytest

from upow_tpu.config import NodeConfig
from upow_tpu.node.peers import PeerBook
from upow_tpu.resilience import faultinject
from upow_tpu.resilience.faultinject import FaultInjected
from upow_tpu.swarm import (LinkDown, LinkMatrix, LinkPolicy, Swarm,
                            run_scenario)
from upow_tpu.swarm.scenarios import _wallet, deterministic_world

A, B, C = "http://10.0.0.1:1", "http://10.0.0.2:1", "http://10.0.0.3:1"


def _matrix(seed=0, **kw) -> LinkMatrix:
    m = LinkMatrix(seed, **kw)
    for url in (A, B, C):
        m.register(url)
    return m


# ------------------------------------------------------------- links ----

def test_partition_blocks_cross_traffic_and_heals():
    async def main():
        m = _matrix()
        await m.transfer(A, B)                      # full connectivity
        m.partition([[A], [B, C]])
        with pytest.raises(LinkDown) as e:
            await m.transfer(A, B)
        assert e.value.reason == "partitioned"
        await m.transfer(B, C)                      # same group flows
        # unlisted endpoints (the driver) always bypass shaping —
        # bypassed transfers aren't counted either
        await m.transfer("http://driver.local", A)
        m.heal()
        await m.transfer(A, B)
        assert m.stats()["blocked"] == 1
        assert m.stats()["delivered"] == 3

    asyncio.run(main())


def test_isolation_cuts_every_link_of_one_url():
    async def main():
        m = _matrix()
        m.isolate(A)
        for src, dst in ((A, B), (B, A), (C, A)):
            with pytest.raises(LinkDown):
                await m.transfer(src, dst)
        await m.transfer(B, C)
        m.restore(A)
        await m.transfer(A, B)

    asyncio.run(main())


def test_drop_draws_are_per_link_and_seed_deterministic():
    async def outcomes(seed):
        m = _matrix(seed, default=LinkPolicy(drop=0.5))
        out = []
        for _ in range(20):
            try:
                await m.transfer(A, B)
                out.append(1)
            except LinkDown:
                out.append(0)
        return out

    async def main():
        first = await outcomes(123)
        assert first == await outcomes(123)     # same seed, same schedule
        assert first != await outcomes(321)     # a different fault world
        assert 0 < sum(first) < 20              # p=0.5 actually drops

    asyncio.run(main())


def test_swarm_link_fault_site_fires():
    """swarm.link is a registered fault site: an installed spec kills
    simulated link traffic exactly like rpc.* kills real HTTP."""
    async def main():
        m = _matrix()
        faultinject.install("swarm.link:error:key=10.0.0.2", seed=1)
        try:
            with pytest.raises(FaultInjected):
                await m.transfer(A, B)          # key matches dst
            await m.transfer(C, A)              # other links untouched
        finally:
            faultinject.uninstall()

    asyncio.run(main())


# ------------------------------------------------- peer health ranking ----

def test_ranked_orders_by_state_then_score_then_url():
    """Satellite: pin the tie-break.  usable-closed peers sort by
    descending health, equal scores tie-break on URL, open circuits go
    last — the exact ordering sync_blockchain and propagate share."""
    cfg = NodeConfig()
    cfg.peers_file = ""
    cfg.seed_url = ""
    book = PeerBook(cfg)
    urls = ["http://b:1", "http://a:1", "http://d:1", "http://c:1"]
    for u in urls:
        book.add(u)
    book.breakers.record_failure("http://c:1")          # score 0.7
    for _ in range(5):
        book.breakers.record_failure("http://d:1")      # tripped open
    assert book.ranked(urls) == [
        "http://a:1", "http://b:1",     # untouched 1.0s: URL tie-break
        "http://c:1",                   # degraded but usable
        "http://d:1",                   # open circuit: last resort
    ]


def test_propagate_nodes_is_health_ranked():
    """Satellite: propagate_nodes() must order its sample exactly like
    ranked() — gossip fan-out consistent with sync candidate order."""
    import random

    cfg = NodeConfig()
    cfg.peers_file = ""
    cfg.seed_url = ""
    book = PeerBook(cfg)
    urls = [f"http://peer{i}:1" for i in range(8)]
    for u in urls:
        book.add(u)
    book.breakers.record_failure("http://peer0:1")
    book.breakers.record_failure("http://peer0:1")
    for _ in range(5):
        book.breakers.record_failure("http://peer5:1")
    random.seed(4)
    picks = book.propagate_nodes()
    assert picks, "unseen peers must still be gossiped to"
    assert picks == book.ranked(picks)          # already in ranked order
    assert "http://peer5:1" not in picks        # open circuit: no gossip
    assert picks[-1] == "http://peer0:1"        # degraded peer last


# ---------------------------------------------------------- transport ----

def test_loopback_dispatch_real_middleware():
    """A driver GET runs the destination node's full aiohttp stack; a
    peer-RPC through LoopbackInterface carries breaker accounting."""
    async def main():
        swarm = Swarm(2, seed=0)
        await swarm.start()
        try:
            res = await swarm.get(0, "/")
            assert res["ok"] and "unspent_outputs_hash" in res
            res = await swarm.get(0, "get_nodes")
            assert swarm.urls[1] in res["result"]
            # a partitioned peer RPC records a breaker failure
            swarm.matrix.partition([[swarm.urls[0]], [swarm.urls[1]]])
            iface = swarm.nodes[0].iface_factory(
                swarm.urls[1], swarm.nodes[0].config.node,
                resilience=swarm.nodes[0].resilience)
            with pytest.raises(ConnectionError):
                await iface.get("get_nodes")
            snap = swarm.nodes[0].breakers.snapshot()
            assert snap[swarm.urls[1]]["consecutive_failures"] > 0
        finally:
            await swarm.close()

    with deterministic_world(0):
        asyncio.run(main())


def test_debug_breakers_endpoint():
    """Satellite: /debug/breakers serves the per-peer snapshot."""
    async def main():
        swarm = Swarm(2, seed=0)
        await swarm.start()
        try:
            swarm.matrix.partition([[swarm.urls[0]], [swarm.urls[1]]])
            iface = swarm.nodes[0].iface_factory(
                swarm.urls[1], swarm.nodes[0].config.node,
                resilience=swarm.nodes[0].resilience)
            with pytest.raises(ConnectionError):
                await iface.get("get_nodes")
            res = await swarm.get(0, "debug/breakers")
            assert res["ok"]
            peers = res["result"]["peers"]
            assert peers[swarm.urls[1]]["consecutive_failures"] > 0
            assert set(peers[swarm.urls[1]]) == {
                "state", "score", "consecutive_failures", "flips"}
            assert "closed" in res["result"]["state_counts"]
        finally:
            await swarm.close()

    with deterministic_world(0):
        asyncio.run(main())


# ---------------------------------------------------------- scenarios ----

def test_partition_heal_scenario():
    """ISSUE 8 acceptance: divergent halves converge after heal, and
    the reorg/breaker evidence shares one swarm-spanning trace id."""
    art = run_scenario("partition_heal", seed=5)
    core = art["core"]
    assert core["diverged_during_partition"]
    assert core["converged_after_heal"]
    assert core["final_height"] == 7
    assert core["losers_reorged"]
    # ISSUE 9 tie-in: the losers' hot-state caches served the stale
    # partition balance before heal and the winner's bytes after — the
    # reorg hook, not the revalidation backstop, invalidated them
    # (swarm_config disables foreign revalidation outright)
    assert core["loser_caches_invalidated"]
    assert core["reorgs_share_heal_trace"]
    assert core["trace_spans_nodes"]
    assert core["breakers_flipped_during_partition"]
    # gate-shaped SLO summary rides along for the observatory pipeline
    assert any(k.startswith("swarm.partition_heal.node")
               for k in art["slo"]["endpoints"])


def test_eclipse_scenario_recovers_via_health_ranking():
    """ISSUE 8 acceptance: the victim's health-ranked peer selection
    resurfaces the honest peer once the adversary clique is unmasked."""
    core = run_scenario("eclipse", seed=5)["core"]
    assert core["eclipsed"]
    assert core["adversary_served_calls"]
    assert core["recovered"]
    assert core["honest_ranked_first"]
    assert core["adversaries_scored_below_honest"]


def test_ws_churn_scenario_sheds_only_the_stalled_client():
    core = run_scenario("ws_churn", seed=5)["core"]
    assert core["live_client_delivered"] == 8     # laggard cost nothing
    assert core["dropped_messages"] == 3          # 8 sent, 4 queued, 1 in flight
    assert core["slow_client_delivered"] == 5     # newest survive
    assert core["metrics_export_dropped"]         # upow_ws_dropped_messages


def test_spam_scenario_pools_stay_clean():
    core = run_scenario("spam", seed=5)["core"]
    assert core["spam_accepted"] == 0
    assert core["pools_clean"]
    assert core["tx_confirmed_everywhere"]
    assert core["converged"]


def test_artifact_fingerprint_determinism():
    """ISSUE 8 acceptance: same seed ⇒ byte-identical fingerprint;
    different seed ⇒ different chain, different fingerprint."""
    first = run_scenario("spam", seed=9)
    again = run_scenario("spam", seed=9)
    other = run_scenario("spam", seed=10)
    assert first["fingerprint"] == again["fingerprint"]
    assert first["core"] == again["core"]
    assert first["fingerprint"] != other["fingerprint"]
    assert first["core"]["final_tip"] != other["core"]["final_tip"]


def test_wallets_are_seed_deterministic():
    assert _wallet(7, "x") == _wallet(7, "x")
    assert _wallet(7, "x") != _wallet(8, "x")
    assert _wallet(7, "x") != _wallet(7, "y")


# ------------------------------------------------------ CLI exit code ----

@pytest.mark.parametrize("core_ok_", [True, False])
def test_swarm_cli_exit_code_and_artifact(monkeypatch, tmp_path, core_ok_):
    """``make swarm``: exit 1 when any core boolean came back false; the
    ``--out`` artifact is written whole (tmp + fsync + rename, the
    snapshot layout's writer) and nothing is left beside it."""
    import json

    from upow_tpu.swarm import __main__ as cli

    canned = {"kind": "swarm_scenario", "scenario": "partition_heal",
              "nodes": 3, "seed": 5, "fingerprint": "ab" * 32,
              "core": {"converged_after_heal": core_ok_, "height": 9},
              "observed": {"elapsed_s": 0.25},
              "slo": {"endpoints": {"swarm.partition_heal.node0":
                                    {"p50_ms": 1.5}}}}
    monkeypatch.setattr(cli, "run_scenario",
                        lambda name, nodes, seed: dict(canned, seed=seed))
    out = tmp_path / "swarm.json"
    rc = cli.main(["--scenario", "partition_heal", "--seed", "5",
                   "--out", str(out)])
    assert rc == (0 if core_ok_ else 1)
    assert json.loads(out.read_text()) == canned
    assert [f.name for f in tmp_path.iterdir()] == ["swarm.json"]


# --------------------------------------------------------------- slow ----

@pytest.mark.slow
def test_partition_heal_50_nodes():
    """Upper end of the 10-50 node envelope from the issue."""
    core = run_scenario("partition_heal", nodes=50, seed=3)["core"]
    assert core["converged_after_heal"]
    assert core["losers_reorged"]
    assert core["trace_spans_nodes"]


@pytest.mark.slow
def test_reorg_storm_long_partition():
    """A wider swarm riding repeated partition/heal cycles."""
    core = run_scenario("reorg_storm", nodes=12, seed=3)["core"]
    assert core["all_converged"]
    assert core["reorged_every_cycle"]
