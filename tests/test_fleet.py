"""Fleet observatory (ISSUE 13): instance-scoped registries, the
cross-node scraper, propagation percentiles, the trace stitcher, the
flight recorder and the geo-soak scenario.

Scenario-level tests call :func:`run_scenario` — the same entry the
CLI and CI use; unit tests exercise the fleet modules on synthetic
snapshots so failure messages point at the right layer.
"""

import asyncio
import copy
import json
import math
import re

import pytest

from upow_tpu.fleet import propagation, recorder, scrape, stitch
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


# ------------------------------------------------------ CLI exit code ----

@pytest.fixture(scope="module")
def geo_artifact():
    return run_scenario("geo_soak", seed=7)


@pytest.mark.parametrize("case", ["clean", "core_false", "alert_fired",
                                  "trace_short", "fingerprint_moved"])
def test_fleet_cli_exit_code_holds_the_core(geo_artifact, monkeypatch,
                                            tmp_path, capsys, case):
    """``make fleet`` is ``python -m upow_tpu.fleet --check-determinism
    --trace --out``: its exit code is what holds ``fleet_core_ok`` and
    ``watchtower_clean_ok`` (no gate on a committed baseline)."""
    from upow_tpu.fleet import __main__ as cli

    first = copy.deepcopy(geo_artifact)
    again = copy.deepcopy(geo_artifact)
    if case == "core_false":
        first["core"]["healed_converged"] = False
    elif case == "alert_fired":
        first["core"]["watchtower_zero_alerts"] = False
    elif case == "trace_short":
        first["observed"]["stitched_push_tx"]["node_count"] = 2
    elif case == "fingerprint_moved":
        again["fingerprint"] = "0" * 64
    runs = iter([first, again])
    monkeypatch.setattr(cli, "run_geo_artifact",
                        lambda nodes, seed: next(runs))
    out = tmp_path / "fleet.json"
    rc = cli.main(["--check-determinism", "--trace", "--out", str(out)])
    assert rc == (0 if case == "clean" else 1)
    # the artifact is written whole either way, and nothing is left over
    assert json.loads(out.read_text())["fingerprint"] == first["fingerprint"]
    assert [f.name for f in tmp_path.iterdir()] == ["fleet.json"]
    rows = json.loads(capsys.readouterr().out.splitlines()[-1])["kernels"]
    assert rows["fleet_core_ok"] == \
        (0.0 if case in ("core_false", "alert_fired") else 1.0)
    assert rows["watchtower_clean_ok"] == \
        (0.0 if case == "alert_fired" else 1.0)


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


# ------------------------------------------------------- log rotation ----

def test_rotate_keep_tail_preserves_complete_lines(tmp_path):
    """Satellite 2: the size cap keeps the newest half, aligned to a
    line boundary, and is a no-op under the cap."""
    from upow_tpu.watchtower import benchlog

    p = tmp_path / "grow.log"
    p.write_text("".join(f"line {i:06d} {'x' * 40}\n"
                         for i in range(4000)))
    before = p.stat().st_size
    benchlog._rotate_keep_tail(str(p), max_bytes=before + 1)
    assert p.stat().st_size == before   # under cap: untouched

    benchlog._rotate_keep_tail(str(p), max_bytes=10_000)
    assert p.stat().st_size <= 5_000
    kept = p.read_text().splitlines()
    assert kept[0].startswith("line ")      # no partial first line
    assert kept[-1] == f"line 003999 {'x' * 40}"


def test_alert_event_log_rotates(tmp_path, monkeypatch):
    from types import SimpleNamespace

    from upow_tpu.watchtower import benchlog

    events = tmp_path / ".bench_events.jsonl"
    monkeypatch.setattr(benchlog, "MAX_BYTES", 4096)
    rule = SimpleNamespace(name="rotation_probe", severity="page")
    for i in range(200):
        benchlog.record(str(events), SimpleNamespace(
            rule=rule, key="k" * 64, value=i, exemplars=[]))
    assert events.stat().st_size <= 4096 + 400
    tail = [json.loads(ln) for ln in events.read_text().splitlines()]
    assert all(e["kind"] == "alert_fired"
               and e["rule"] == "rotation_probe" for e in tail)
    assert tail[-1]["value"] == 199
