"""Pipelined verify engine tests (ISSUE 7): the shared dispatch front's
coalescing, the serial-vs-pipelined differential (byte-identical
verdicts + the >=5x acceptance), canary-gated device verdict caching,
stage telemetry preregistration, and the gate's explicit per-metric
direction override.
"""

import asyncio
import json

import pytest

from upow_tpu import telemetry
from upow_tpu.benchutil import pipeline_verify_fixture, verify_pipeline_bench
from upow_tpu.loadgen import gate
from upow_tpu.telemetry import metrics
from upow_tpu.verify import txverify
from upow_tpu.verify.dispatch import get_front


@pytest.fixture(autouse=True)
def clean_state():
    telemetry.reset()
    telemetry.configure()
    txverify.clear_sig_verdicts()
    yield
    txverify.clear_sig_verdicts()
    telemetry.reset()
    telemetry.configure()


def _host_compute(checks):
    """Reference verdicts through the single-sig host path (raw digest,
    hex-form fallback) — the semantics every batched path must match."""
    return [bool(txverify._host_verify_digest(c[0], c[2], c[3])
                 or txverify._host_verify_digest(c[1], c[2], c[3]))
            for c in checks]


# ------------------------------------------------- differential ----

def test_pipelined_verdicts_byte_identical_and_5x():
    """The ISSUE acceptance: >=1k mixed valid/invalid checks, pipelined
    accept/reject verdicts identical to the serial path, >=5x rate."""
    r = verify_pipeline_bench(seconds=0.05)
    assert r["differential_txs"] >= 1000
    assert r["n_invalid"] > 0  # the mix actually exercises rejects
    assert r["verdicts_equal"]
    assert r["speedup"] >= 5


# ------------------------------------------------ dispatch front ----

def test_front_coalesces_compatible_submissions():
    """Concurrent same-key submissions share ONE dispatch and each get
    exactly their own verdict slice back."""
    checks = pipeline_verify_fixture(32, n_unique=8, invalid_every=5)
    expected = _host_compute(checks)

    async def run():
        front = get_front()
        d0, s0 = front.dispatches, front.submissions
        outs = await asyncio.gather(*[
            front.submit(checks[i:i + 8], backend="host", source="test")
            for i in range(0, 32, 8)])
        return front.dispatches - d0, front.submissions - s0, outs

    dispatches, submissions, outs = asyncio.run(run())
    assert submissions == 4
    assert dispatches == 1
    assert [v for out in outs for v in out] == expected
    assert metrics.counters()["pipeline.front.source.test"] == 4


def test_front_incompatible_keys_dispatch_separately():
    checks = pipeline_verify_fixture(16, n_unique=8, invalid_every=0)

    async def run():
        front = get_front()
        d0 = front.dispatches
        outs = await asyncio.gather(
            front.submit(checks[:8], backend="host", pad_block=128),
            front.submit(checks[8:], backend="host", pad_block=64))
        return front.dispatches - d0, outs

    dispatches, outs = asyncio.run(run())
    assert dispatches == 2
    assert all(all(out) for out in outs)


def test_front_empty_submission_short_circuits():
    async def run():
        front = get_front()
        d0 = front.dispatches
        out = await front.submit([], backend="host")
        return out, front.dispatches - d0

    out, dispatches = asyncio.run(run())
    assert out == [] and dispatches == 0


def test_configure_preregisters_pipeline_families():
    """Stage + front metric families exist before any block flows."""
    assert "pipeline.front.submissions" in metrics.counters()
    assert "pipeline.front.dispatches" in metrics.counters()
    hists = metrics.histograms()
    assert "pipeline.front.coalesced" in hists
    for stage in ("block_decode", "block_sig_wait"):
        assert f"pipeline.{stage}.seconds" in hists
        assert f"pipeline.{stage}.occupancy" in hists


# ------------------------------------------- canary cache gating ----

def _patch_device_dispatch(monkeypatch, corrupt_canary):
    """Route cache misses down the 'device' path but serve the actual
    dispatch host-side, optionally reporting the known-bad canary as
    valid (a silently-miscomputing device)."""
    calls = []

    def fake_uncached(checks, backend="auto", pad_block=128,
                      device_timeout=240.0, use_cache=True,
                      precomputed=None, mesh_devices=1):
        assert use_cache is False and backend == "device"
        calls.append(len(checks))
        out = _host_compute(checks)
        if corrupt_canary:
            out[-1] = True  # the appended known-bad canary comes back ok
        return out

    monkeypatch.setattr(txverify, "_resolve_backend",
                        lambda backend, n: "device")
    monkeypatch.setattr(txverify, "run_sig_checks", fake_uncached)
    return calls


def test_canary_pass_admits_device_verdicts_to_cache(monkeypatch):
    checks = pipeline_verify_fixture(12, n_unique=12, invalid_every=5)
    expected = _host_compute(checks)
    real = txverify.run_sig_checks
    calls = _patch_device_dispatch(monkeypatch, corrupt_canary=False)

    assert real(checks, backend="auto") == expected
    assert calls == [len(checks) + 2]  # canary pair rode along
    assert txverify.sig_verdict_stats()["size"] == len(checks)
    assert metrics.counters()["verify.canary_pass"] == 1
    # second pass: pure cache hits, no second dispatch
    assert real(checks, backend="auto") == expected
    assert len(calls) == 1


def test_canary_fail_blocks_device_verdict_caching(monkeypatch):
    checks = pipeline_verify_fixture(12, n_unique=12, invalid_every=5)
    expected = _host_compute(checks)
    real = txverify.run_sig_checks
    calls = _patch_device_dispatch(monkeypatch, corrupt_canary=True)

    # verdicts for the caller's checks are still served (and correct —
    # only the canary was corrupted), but nothing may enter the cache
    assert real(checks, backend="auto") == expected
    assert txverify.sig_verdict_stats()["size"] == 0
    assert metrics.counters()["verify.canary_fail"] == 1
    # the tainted batch is re-dispatched, not replayed from cache
    assert real(checks, backend="auto") == expected
    assert len(calls) == 2


def test_host_verdicts_cached_without_canary():
    checks = pipeline_verify_fixture(12, n_unique=12, invalid_every=5)
    expected = _host_compute(checks)
    assert txverify.run_sig_checks(checks, backend="host") == expected
    stats = txverify.sig_verdict_stats()
    assert stats["size"] == len(checks)
    assert txverify.run_sig_checks(checks, backend="host") == expected
    assert txverify.sig_verdict_stats()["hits"] >= len(checks)
    # (exported at zero once a node has configured telemetry)
    assert not metrics.counters().get("verify.canary_pass")


def test_canary_pair_is_good_then_bad():
    good, bad = txverify._canary_checks()
    assert _host_compute([good, bad]) == [True, False]


# ------------------------------------- gate direction override ----

def _write(tmp_path, name, doc):
    path = tmp_path / name
    path.write_text(json.dumps(doc))
    return str(path)


def test_gate_collects_artifact_directions(tmp_path):
    doc = {"kernels": {
        "verify_pipeline_speedup": {"value": 440.0, "unit": "x",
                                    "direction": "higher"},
        "warm_seconds": {"value": 2.0, "unit": "s",
                         "direction": "higher"},
        "verify_python": {"value": 500.0, "unit": "sigs/s"},
        "bogus": {"value": 1.0, "direction": "sideways"}}}
    directions = {}
    flat = gate.load_metrics(_write(tmp_path, "a.json", doc), directions)
    assert flat["kernel.verify_pipeline_speedup"] == 440.0
    # malformed/absent direction fields keep name inference
    assert directions == {"kernel.verify_pipeline_speedup": "higher",
                          "kernel.warm_seconds": "higher"}


def test_gate_direction_override_flips_inference(tmp_path, capsys):
    """'warm_seconds' infers lower-is-better; the artifact's explicit
    higher-is-better wins, so a big drop is now a regression."""
    def art(v):
        return {"kernels": {"warm_seconds": {
            "value": v, "unit": "s", "direction": "higher"}}}

    base = _write(tmp_path, "base.json", art(10.0))
    cur = _write(tmp_path, "cur.json", art(4.0))
    assert gate.main(["--against", base, "--current", cur]) == 1
    report = json.loads(capsys.readouterr().out)
    (row,) = report["verdicts"]
    assert row["regressed"] and row["direction"] == "higher"
    assert row["direction_source"] == "artifact"

    # without the override the same drop would have passed
    def art_plain(v):
        return {"kernels": {"warm_seconds": {"value": v, "unit": "s"}}}
    base = _write(tmp_path, "base2.json", art_plain(10.0))
    cur = _write(tmp_path, "cur2.json", art_plain(4.0))
    assert gate.main(["--against", base, "--current", cur]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["verdicts"][0]["direction_source"] == "inferred"


def test_gate_override_on_bench_suite_lines(tmp_path, capsys):
    """Direction override also applies to bench_suite JSON-line streams
    (e.g. an error-rate named like a throughput metric)."""
    def stream(v):
        return json.dumps({"metric": "retry_rate", "value": v,
                           "unit": "1/s", "direction": "lower"})

    base = tmp_path / "base.jsonl"
    base.write_text(stream(1.0) + "\n")
    cur = tmp_path / "cur.jsonl"
    cur.write_text(stream(5.0) + "\n")
    # inference would call the 5x increase an improvement (throughput
    # name); the explicit lower direction fails it
    assert gate.main(["--against", str(base),
                      "--current", str(cur)]) == 1
    capsys.readouterr()
