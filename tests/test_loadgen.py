"""Load-generator tests: schedule determinism, SLO histogram
exposition, and debug-endpoint limit hardening.

The in-process-node integration lives in ``test_loadgen_node`` — the
pure pieces here run without booting anything, so the determinism
claims are tested exactly where they're made (mock backend, pure
latency function of the seed).
"""

import asyncio

import pytest

from test_node import Cluster, easy_difficulty  # noqa: F401
from upow_tpu import telemetry
from upow_tpu.loadgen.population import (PopulationSpec, build_schedule,
                                         schedule_fingerprint)
from upow_tpu.loadgen.runner import MockBackend, run_mock, run_schedule
from upow_tpu.telemetry import exposition, metrics, slo


@pytest.fixture(autouse=True)
def clean_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()
    telemetry.configure()


# ------------------------------------------------------ determinism ----

def test_schedule_deterministic():
    """Same seed -> byte-identical schedule; different seed differs."""
    a = build_schedule(PopulationSpec.smoke())
    b = build_schedule(PopulationSpec.smoke())
    assert schedule_fingerprint(a) == schedule_fingerprint(b)
    assert [e.at for e in a] == sorted(e.at for e in a)
    c = build_schedule(PopulationSpec.smoke(seed=0xDEAD))
    assert schedule_fingerprint(a) != schedule_fingerprint(c)


def test_schedule_covers_all_actor_streams():
    kinds = {e.kind for e in build_schedule(PopulationSpec.smoke())}
    assert {"balance", "mining_info", "push_tx",
            "ws_connect", "ws_ping", "ws_close"} <= kinds


def test_push_bursts_share_timestamp():
    """Burst members land at an identical instant — that simultaneity
    is what drives the intake's micro-batch coalescing."""
    spec = PopulationSpec.smoke()
    events = build_schedule(spec)
    bursts = {}
    for e in events:
        if e.kind == "push_tx":
            bursts.setdefault(e.at, []).append(e)
    assert len(bursts) == spec.push_bursts
    assert all(len(v) == spec.burst_size for v in bursts.values())


def test_mock_summary_deterministic():
    """Same seed -> identical summary (modulo wall clock), twice."""
    s1 = run_mock(PopulationSpec.smoke(), record_slo=False)
    s2 = run_mock(PopulationSpec.smoke(), record_slo=False)
    s1.pop("wall_s"), s2.pop("wall_s")
    assert s1 == s2
    assert s1["endpoints"]["push_tx"]["requests"] == 16


def test_zipf_read_skew():
    """Rank 0 must absorb more reads than any deep-tail rank."""
    spec = PopulationSpec(duration=4.0, n_readers=8)
    hits = {}
    for e in build_schedule(spec):
        w = e.param("wallet")
        if w is not None:
            hits[w] = hits.get(w, 0) + 1
    assert hits.get(0, 0) > hits.get(spec.n_wallets - 1, 0)
    assert hits.get(0, 0) >= max(hits.values()) * 0.5


def test_runner_survives_executor_crash():
    """An executor exception becomes a synthetic 599, not an abort."""
    events = build_schedule(PopulationSpec.smoke())

    async def boom(ev):
        raise RuntimeError("injected")

    results = asyncio.run(run_schedule(events, boom))
    assert len(results) == len(events)
    assert all(r.status == 599 and not r.ok for r in results)


# ------------------------------------------- slo histograms /metrics ----

def test_slo_exposition_valid():
    """The SLO histograms render to valid exposition text with the
    cumulative +Inf invariant intact."""
    run_mock(PopulationSpec.smoke(), record_slo=True)
    e = exposition.Exposition()
    for name, h in metrics.histograms().items():
        e.histogram(name, h["bounds"], h["counts"], h["count"], h["sum"])
    text = e.render()
    assert "upow_slo_http_push_tx_latency_seconds_bucket" in text
    assert exposition.validate(text) == []
    # +Inf cumulative == _count for the push_tx series
    lines = [ln for ln in text.splitlines() if "push_tx" in ln]
    inf = next(ln for ln in lines if 'le="+Inf"' in ln)
    count = next(ln for ln in lines if ln.startswith(
        "upow_slo_http_push_tx_latency_seconds_count"))
    assert inf.rsplit(" ", 1)[1] == count.rsplit(" ", 1)[1] != "0"


def test_slo_summary_quantiles():
    for _ in range(90):
        slo.observe_request("/x", 0.003)
    for _ in range(10):
        slo.observe_request("/x", 0.2, status=502)
    row = slo.summary()["x"]
    assert row["requests"] == 100 and row["errors"] == 10
    assert 2.0 <= row["p50_ms"] <= 5.0
    assert row["p95_ms"] > row["p50_ms"]
    assert 100.0 <= row["p99_ms"] <= 250.0


def test_slo_quantile_edge_cases():
    assert slo.quantile({"bounds": (1,), "counts": (0, 0),
                         "count": 0, "sum": 0.0}, 0.5) is None
    # everything in the +Inf overflow clamps to the top finite bound
    est = slo.quantile({"bounds": (0.001, 0.01), "counts": (0, 0, 7),
                        "count": 7, "sum": 3.0}, 0.5)
    assert est == 0.01


def test_mock_backend_feeds_slo_registry():
    asyncio.run(MockBackend(seed=7)(
        build_schedule(PopulationSpec.smoke())[0]))
    assert any(n.startswith("slo.http.") for n in metrics.histograms())


# ------------------------------------------- debug-endpoint limits ----

def test_debug_limit_hardening(tmp_path):
    """Negative limits clamp to 0, oversized clamp to the cap, and
    non-integers are a 400 — never a 500."""
    async def scenario(cluster):
        _node, client = await cluster.add_node("a")
        for i in range(5):
            telemetry.event("breaker", peer=f"p{i}", state="open")

        res = await client.get("/debug/events", params={"limit": "-3"})
        assert res.status == 200
        assert len((await res.json())["result"]) == 5  # clamped to "all"

        res = await client.get("/debug/events", params={"limit": "2"})
        assert len((await res.json())["result"]) == 2

        res = await client.get("/debug/events",
                               params={"limit": "99999999999"})
        assert res.status == 200  # clamped to the cap, served

        # empty string means "not provided" (default), not an error
        res = await client.get("/debug/events", params={"limit": ""})
        assert res.status == 200

        for bad in ("abc", "1.5", "2x"):
            res = await client.get("/debug/events", params={"limit": bad})
            assert res.status == 400, bad
            body = await res.json()
            assert body["ok"] is False and "integer" in body["error"]

        res = await client.get("/debug/traces", params={"limit": "abc"})
        assert res.status == 400
        res = await client.get("/debug/traces", params={"limit": "-1"})
        assert res.status == 200

    async def main():
        cluster = Cluster(tmp_path)
        try:
            await scenario(cluster)
        finally:
            await cluster.close()

    asyncio.run(main())


# ------------------------------------------------- profiler session ----

def test_profile_status_lifecycle(tmp_path):
    from upow_tpu import profiling

    profiling.reset()
    assert profiling.status()["active"] is False
    res = profiling.start(str(tmp_path / "traces"), max_seconds=60.0)
    try:
        if "error" in res:  # backend can't trace: status must stay clean
            assert profiling.status()["active"] is False
            return
        assert profiling.status()["active"] is True
        again = profiling.start(str(tmp_path / "traces2"))
        assert "error" in again  # one capture at a time
    finally:
        profiling.stop()
    assert profiling.status()["active"] is False


def test_config_profile_env(monkeypatch):
    from upow_tpu.config import Config

    monkeypatch.setenv("UPOW_PROFILE_ENABLED", "1")
    monkeypatch.setenv("UPOW_PROFILE_MAX_CAPTURE_SECONDS", "7.5")
    cfg = Config.load(path=None)
    assert cfg.profile.enabled is True
    assert cfg.profile.max_capture_seconds == 7.5
