"""Pipelined verify engine tests (ISSUE 7): the shared dispatch front's
coalescing, the serial-vs-pipelined differential (byte-identical
verdicts), canary-gated device verdict caching, and stage telemetry
preregistration.
"""

import asyncio

import pytest

from upow_tpu import telemetry
from upow_tpu.loadgen.fixtures import pipeline_verify_fixture
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
    accept/reject verdicts identical to the serial path.

    * serial — one cache-bypassed ``run_sig_checks`` call per tx (the
      reference's profile: every hop re-verifies every signature, one
      tx at a time).
    * pipelined — micro-batched submissions coalesced through the
      shared dispatch front with the verdict cache live: one cold
      populate pass, then a warm pass answered from the cache.  The
      cold pass computes every verdict through the identical host
      path, so the cache can never answer something the serial path
      would not.

    (The name keeps its "5x": that was a CPU rate ratio over 0.05 s; a
    rate is the chip benchmark's to report, the byte identity is the
    test.)"""
    n_txs, microbatch = 1024, 128
    checks = pipeline_verify_fixture(n_txs)

    serial: list = []
    for c in checks:
        serial.extend(txverify.run_sig_checks([c], backend="host",
                                              use_cache=False))

    async def one_pass():
        front = get_front()
        outs = await asyncio.gather(*[
            front.submit(checks[i:i + microbatch], backend="host",
                         source="bench")
            for i in range(0, n_txs, microbatch)])
        return [v for out in outs for v in out]

    async def pipelined():
        txverify.clear_sig_verdicts()
        cold = await one_pass()
        return cold, await one_pass()

    cold, warm = asyncio.run(pipelined())
    assert len(serial) == n_txs
    assert 0 < sum(1 for v in serial if not v) < n_txs  # rejects AND accepts
    assert serial == _host_compute(checks)
    assert serial == cold == warm


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
