"""Fleet observatory (ISSUE 13): instance-scoped registries, the
cross-node scraper, propagation percentiles, the trace stitcher, the
flight recorder and the geo-soak scenario.

Scenario-level tests call :func:`run_scenario` — the same entry the
CLI and CI use; unit tests exercise the fleet modules on synthetic
snapshots so failure messages point at the right layer.
"""

import asyncio
import json
import math
import re

from upow_tpu.fleet import propagation, recorder, scrape, stitch
from upow_tpu.loadgen import gate
from upow_tpu.resilience import faultinject
from upow_tpu.swarm.harness import Swarm
from upow_tpu.swarm.scenarios import (_wallet, deterministic_world,
                                      run_scenario)
from upow_tpu.telemetry import exposition


# ------------------------------------------------- scoped registries ----

def _counter_value(metrics_text: str, family: str) -> float:
    for ln in metrics_text.splitlines():
        if ln.startswith(family + " "):
            return float(ln.split()[1])
    return 0.0


def test_scoped_registries_disjoint_across_nodes():
    """Satellite 1: three in-loop nodes keep disjoint SLO counters —
    node i serves i+1 requests and its own /metrics reports exactly
    that, not the fleet total (the regression this scoping prevents)."""
    family = "upow_slo_http_get_supply_info_requests_total"

    async def main():
        swarm = await Swarm(3, seed=0).start()
        try:
            for i in range(3):
                for _ in range(i + 1):
                    res = await swarm.get(i, "get_supply_info")
                    assert res["ok"]
            snapshot = await scrape.scrape(swarm)
            for i in range(3):
                text = snapshot["nodes"][f"node{i}"]["metrics_text"]
                assert _counter_value(text, family) == i + 1, \
                    f"node{i} served {i + 1} requests"
        finally:
            await swarm.close()

    with deterministic_world(0):
        asyncio.run(main())


# ------------------------------------------------------- propagation ----

def test_propagation_report_quantiles():
    """First-seen joins per hash; spread = first to ceil(0.9n)-th node;
    the driver ring never counts as a node."""
    events = {
        "driver": [{"kind": "block_seen", "hash": "aa", "ts": 0.0}],
        "node0": [{"kind": "block_seen", "hash": "aa", "ts": 10.0}],
        "node1": [{"kind": "block_seen", "hash": "aa", "ts": 10.1}],
        "node2": [{"kind": "block_seen", "hash": "aa", "ts": 10.4}],
    }
    rep = propagation.report(events, n_nodes=3, coverage=0.9)
    blocks = rep["blocks"]
    assert blocks["hashes"] == 1 and blocks["covered"] == 1
    # need = ceil(0.9 * 3) = 3 nodes -> spread 10.4 - 10.0 = 400ms
    assert abs(blocks["p50_ms"] - 400.0) < 1e-6
    assert abs(blocks["p99_ms"] - 400.0) < 1e-6

    # an uncovered hash (1 of 3 nodes) contributes nothing
    events["node0"].append({"kind": "block_seen", "hash": "bb", "ts": 11.0})
    rep = propagation.report(events, n_nodes=3, coverage=0.9)
    assert rep["blocks"]["hashes"] == 2
    assert rep["blocks"]["covered"] == 1


def test_propagation_tx_spread_needs_two_seers():
    events = {
        "node0": [{"kind": "tx_seen", "hash": "t1", "ts": 5.0}],
        "node1": [{"kind": "tx_seen", "hash": "t1", "ts": 5.25}],
        "node2": [{"kind": "tx_seen", "hash": "lonely", "ts": 9.0}],
    }
    rep = propagation.report(events, n_nodes=3)
    txs = rep["txs"]
    assert txs["covered"] == 1          # "lonely" had a single seer
    assert abs(txs["p50_ms"] - 250.0) < 1e-6


def test_propagation_empty_is_nan_not_crash():
    rep = propagation.report({"node0": []}, n_nodes=1)
    assert rep["blocks"]["hashes"] == 0
    assert math.isnan(rep["blocks"]["p50_ms"])
    assert propagation.gate_rows(rep) == {}


# ----------------------------------------------------------- stitcher ----

def _root(node_ts, name, tid, duration_ms=2.0):
    return {"trace_id": tid, "name": name, "start_ts": node_ts,
            "duration_ms": duration_ms, "spans": []}


def test_stitch_joins_one_trace_across_nodes():
    tid = "aabbccdd"
    traces = {
        "driver": {"recent": [_root(1.000, "fleet.push_tx", tid)]},
        "node0": {"recent": [_root(1.002, "GET /push_tx", tid)]},
        "node1": {"recent": [_root(1.010, "POST /push_tx", tid),
                             _root(5.0, "GET /", "other")]},
        "node2": {"recent": [_root(1.025, "POST /push_tx", tid)]},
    }
    fleet = stitch.stitch(traces)
    assert set(fleet) == {tid, "other"}
    t = fleet[tid]
    assert t["nodes"] == ["driver", "node0", "node1", "node2"]
    assert t["node_count"] == 4
    assert [h["node"] for h in t["hops"]] == t["nodes"]
    # start-to-start edge latencies between consecutive node changes
    lat = {(e["from"], e["to"]): e["latency_ms"]
           for e in t["hop_latencies_ms"]}
    assert abs(lat[("node0", "node1")] - 8.0) < 1e-6
    assert abs(lat[("node1", "node2")] - 15.0) < 1e-6
    # first start (1.000) to last end (1.025 + 2ms)
    assert abs(t["duration_ms"] - 27.0) < 1e-6
    assert stitch.stitch_one(traces, "missing") is None


# ----------------------------------------------------- flight recorder ----

def test_trigger_reason_precedence():
    fault = [{"kind": "fault_injected", "site": "rpc"}]
    slow = {"swarm.x.node0": {"p99_ms": 900.0}}
    assert recorder.trigger_reason(False, fault) == "core_assertion_failed"
    assert recorder.trigger_reason(True, fault) == "fault_injected"
    breach = recorder.trigger_reason(True, [], slo_rows=slow,
                                     p99_budget_ms=500.0)
    assert breach == "slo_breach:swarm.x.node0:p99_ms=900.0"
    assert recorder.trigger_reason(True, [], slo_rows=slow,
                                   p99_budget_ms=2000.0) is None


def test_flight_recorder_dump_on_injected_fault():
    """An injected link fault marks the run: the black box lands in the
    artifact even though every core assertion still held (retries
    absorbed the fault)."""
    # key "3006->" matches node->anything transfers only, never the
    # driver's own requests; one 1ms latency blip is harmless to the
    # scenario but emits the fault_injected event run_scenario scans
    faultinject.install("swarm.link:latency:times=1,delay=0.001,key=3006->")
    try:
        art = run_scenario("spam", seed=5)
    finally:
        faultinject.uninstall()
    assert all(v for v in art["core"].values() if isinstance(v, bool))
    box = art["flight_recorder"]
    assert box["reason"] == "fault_injected"
    assert box["marks"] >= 2            # start + final at minimum
    assert box["nodes"], "per-node frames recorded"
    frame = next(iter(box["nodes"].values()))[-1]
    assert set(frame) >= {"label", "ts", "counter_deltas", "events",
                          "open_traces"}


def test_no_flight_recorder_on_clean_run():
    art = run_scenario("spam", seed=5)
    assert "flight_recorder" not in art


# ------------------------------------------------------------ geo-soak ----

def test_geo_soak_scenario_and_determinism():
    """ISSUE 13 acceptance: gossip-carried blocks cover >=90% of nodes
    with measured propagation quantiles, the traced push_tx stitches
    across >=3 nodes, churn + partition heal converge — and the same
    seed reproduces the core fingerprint byte-identically."""
    art = run_scenario("geo_soak", seed=5)
    core = art["core"]
    assert core["bootstrap_converged"]
    assert core["waves_all_propagated"]
    assert core["gossip_reached_all_but_victim"]
    assert core["churn_victim_caught_up"]
    assert core["partition_diverged"]
    assert core["healed_converged"]
    assert core["tx_reached_90pct_nodes"]
    assert core["push_tx_trace_crossed_3_nodes"]
    assert core["blocks_covered_90pct"]
    assert core["final_converged"]
    assert sorted(set(core["continents"].values())) == ["am", "ap", "eu"]

    prop = art["observed"]["propagation"]
    assert prop["blocks"]["covered"] >= 11
    assert prop["blocks"]["p50_ms"] > 0
    assert prop["blocks"]["p95_ms"] >= prop["blocks"]["p50_ms"]
    stitched = art["observed"]["stitched_push_tx"]
    nodes = [x for x in stitched["nodes"] if x != "driver"]
    assert len(nodes) >= 3
    assert stitched["hop_latencies_ms"], "cross-node edges measured"
    assert any(k.startswith("swarm.geo_soak.node")
               for k in art["slo"]["endpoints"])
    assert "flight_recorder" not in art, "clean run keeps no black box"

    again = run_scenario("geo_soak", seed=5)
    assert again["fingerprint"] == art["fingerprint"]
    assert again["core"] == core


def test_geo_soak_fleet_rows_shape():
    from upow_tpu.fleet.geosoak import fleet_rows

    art = run_scenario("geo_soak", seed=11)
    rows = fleet_rows(art)
    k = rows["kernels"]
    assert k["fleet_core_ok"]["value"] == 1.0
    assert k["fleet_core_ok"]["direction"] == "higher"
    for name in ("fleet_block_prop_p50_ms", "fleet_block_prop_p95_ms",
                 "fleet_tx_prop_p50_ms", "fleet_tx_prop_p95_ms"):
        assert k[name]["direction"] == "lower"
        assert k[name]["value"] >= 0.0
    assert any(ep.startswith("fleet.geo_soak.node")
               for ep in rows["slo_endpoints"])
    assert "fleet.geo_soak.block_prop" in rows["slo_endpoints"]
    # a failed core bool zeroes the enforced kernel
    broken = {**art, "core": {**art["core"], "healed_converged": False}}
    assert fleet_rows(broken)["kernels"]["fleet_core_ok"]["value"] == 0.0


# ----------------------------------------------- fleet exposition gate ----

def test_render_fleet_validates_and_crafted_violations():
    """Satellite 3: the merged upow_fleet_* rendering passes the
    exposition validator; corrupting it is caught."""
    async def main():
        swarm = await Swarm(3, seed=0).start()
        try:
            _, addr = _wallet(0, "fleet_render")
            assert (await swarm.mine(0, addr))["ok"]
            await swarm.wait_converged()
            await swarm.settle()
            return await scrape.scrape(swarm)
        finally:
            await swarm.close()

    with deterministic_world(0):
        snapshot = asyncio.run(main())

    text = scrape.render_fleet(snapshot)
    assert exposition.validate(text) == []
    for family in ("upow_fleet_nodes", "upow_fleet_height_spread",
                   "upow_fleet_block_propagation_p95_ms",
                   "upow_fleet_block_propagation_seconds_bucket",
                   "upow_fleet_tx_propagation_seconds_bucket"):
        assert family in text, family

    # crafted violation: an illegal sample name
    assert exposition.validate(text + '9bad_name 1\n')
    # crafted violation: regressing cumulative bucket counts
    broken = re.sub(
        r'upow_fleet_block_propagation_seconds_bucket\{le="\+Inf"\} \d+',
        'upow_fleet_block_propagation_seconds_bucket{le="+Inf"} 0',
        text)
    assert exposition.validate(broken)


def test_render_fleet_empty_snapshot():
    text = scrape.render_fleet({"nodes": {}})
    assert exposition.validate(text) == []
    assert "upow_fleet_nodes 0" in text


# ------------------------------------------------------- gate --trend ----

def test_gate_trend_skips_driver_lines_and_tracks_direction(tmp_path):
    """Satellite 6: --trend reads only perf_observatory lines and
    reports direction-aware per-metric trends."""
    lines = [
        {"ts": 1, "kind": "driver", "round": 1, "loc": 10},
        {"kind": "perf_observatory",
         "slo": {"push_tx": {"req_s": 100.0, "p95_ms": 20.0}},
         "kernels": {"fleet_core_ok": 1.0, "verify_python": 100.0}},
        "not json at all",
        {"kind": "perf_observatory",
         "slo": {"push_tx": {"req_s": 150.0, "p95_ms": 30.0}},
         "kernels": {"fleet_core_ok": 1.0, "verify_python": 50.0}},
    ]
    path = tmp_path / "PROGRESS.jsonl"
    path.write_text("".join(
        (ln if isinstance(ln, str) else json.dumps(ln)) + "\n"
        for ln in lines))

    report = gate.trend_report(str(path))
    assert report["observatory_lines"] == 2
    rows = {r["metric"]: r for r in report["metrics"]}
    assert "kernel.loc" not in rows     # driver line skipped
    assert rows["slo.push_tx.req_s"]["trend"] == "improving"
    assert rows["slo.push_tx.p95_ms"]["trend"] == "regressing"
    assert rows["slo.push_tx.p95_ms"]["direction"] == "lower"
    assert rows["kernel.verify_python"]["trend"] == "regressing"
    assert rows["kernel.fleet_core_ok"]["trend"] == "flat"
    # regressions sort first; trend mode never fails the build
    assert report["metrics"][0]["trend"] == "regressing"
    assert gate.main(["--trend", str(path)]) == 0


# ------------------------------------------------------- log rotation ----

def test_rotate_keep_tail_preserves_complete_lines(tmp_path):
    """Satellite 2: the size cap keeps the newest half, aligned to a
    line boundary, and is a no-op under the cap."""
    import bench  # repo root: the bench event log's rotation

    p = tmp_path / "grow.log"
    p.write_text("".join(f"line {i:06d} {'x' * 40}\n"
                         for i in range(4000)))
    before = p.stat().st_size
    bench._rotate_keep_tail(str(p), max_bytes=before + 1)
    assert p.stat().st_size == before   # under cap: untouched

    bench._rotate_keep_tail(str(p), max_bytes=10_000)
    assert p.stat().st_size <= 5_000
    kept = p.read_text().splitlines()
    assert kept[0].startswith("line ")      # no partial first line
    assert kept[-1] == f"line 003999 {'x' * 40}"


def test_bench_event_log_rotates(tmp_path, monkeypatch):
    import bench

    events = tmp_path / ".bench_events.jsonl"
    monkeypatch.setattr(bench, "_BENCH_EVENTS", str(events))
    monkeypatch.setattr(bench, "_BENCH_EVENTS_MAX", 4096)
    for i in range(200):
        bench._record_bench_event("rotation_probe", n=i, pad="y" * 64)
    assert events.stat().st_size <= 4096 + 200
    tail = events.read_text().splitlines()
    assert all(json.loads(ln)["kind"] == "rotation_probe" for ln in tail)
    assert json.loads(tail[-1])["n"] == 199
