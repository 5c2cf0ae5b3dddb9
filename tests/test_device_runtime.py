"""Device-runtime service tests (ISSUE 10): cross-source coalescing
differential, weighted fairness under a saturating miner flood, the
degrade choke point (flip mid-flight drains queued work to the host,
byte-identical), the arm-failure path (every subsystem served on CPU,
no deadlock), and the ``device.runtime`` fault site.
"""

import threading
import time

import pytest

from upow_tpu import telemetry
from upow_tpu.loadgen.fixtures import pipeline_verify_fixture
from upow_tpu.config import DeviceRuntimeConfig
from upow_tpu.device import runtime as rt_mod
from upow_tpu.device.runtime import DeviceRuntime
from upow_tpu.resilience import faultinject
from upow_tpu.resilience.degrade import DegradeManager
from upow_tpu.telemetry import metrics
from upow_tpu.verify import txverify


@pytest.fixture(autouse=True)
def clean_state():
    telemetry.reset()
    telemetry.configure()
    txverify.clear_sig_verdicts()
    yield
    txverify.clear_sig_verdicts()
    telemetry.reset()
    telemetry.configure()


@pytest.fixture
def rt():
    runtime = DeviceRuntime()
    yield runtime
    runtime.close()


_HANG = ("backend init still inside jax.devices() after 3s (native hang; "
         "no Python exception to show)")


def _fake_probe(monkeypatch, platform, status="ok", error=None):
    """The one seam: the process's probe.  Forgets the record this
    process already holds and installs a counting fake; returns the
    list of timeouts it was called with."""
    calls = []

    def probe(timeout):
        calls.append(timeout)
        return {"status": status, "platform": platform, "seconds": 0.0,
                "error": error, "traceback_fingerprint": None}

    monkeypatch.setattr(rt_mod, "_PROBE", None)
    monkeypatch.setattr(rt_mod, "probe_platform", probe)
    return calls


@pytest.fixture
def fresh_singleton():
    """get_runtime() builds a new service for this test, and the next
    test does not inherit it."""
    rt_mod.reset_runtime()
    yield
    rt_mod.reset_runtime()


def _host_compute(checks):
    """Reference verdicts through the single-sig host path — the
    semantics every runtime-coalesced dispatch must match."""
    return [bool(txverify._host_verify_digest(c[0], c[2], c[3])
                 or txverify._host_verify_digest(c[1], c[2], c[3]))
            for c in checks]


# ---------------------------------------------- coalescing differential ----

def test_32_source_coalescing_differential(rt):
    """32 subsystems submit compatible sig batches concurrently; the
    runtime serves them in ONE dispatch and every source gets exactly
    the serial host path's verdicts back."""
    checks = pipeline_verify_fixture(64, n_unique=16, invalid_every=5)
    slices = [checks[i * 2: i * 2 + 2] for i in range(32)]
    expected = [_host_compute(s) for s in slices]

    with rt.hold():
        futs = [
            rt.submit_sig_checks(s, backend="host", device_timeout=30.0,
                                 source="src%02d" % i)
            for i, s in enumerate(slices)
        ]
        # all 32 queued while held: nothing dispatched yet
        assert rt.dispatches == 0
        assert rt.submissions == 32
    got = [f.result(timeout=60.0) for f in futs]

    assert got == expected
    assert rt.dispatches == 1  # 32 submissions -> one shared dispatch
    st = rt.stats()
    assert len(st["per_source"]) == 32
    assert all(v == 1 for v in st["per_source"].values())


def test_incompatible_keys_do_not_coalesce(rt):
    """Different dispatch keys (pad_block) stay in separate dispatches —
    coalescing must never change WHAT is computed."""
    checks = pipeline_verify_fixture(8, n_unique=4, invalid_every=3)
    with rt.hold():
        f1 = rt.submit_sig_checks(checks[:4], backend="host",
                                  pad_block=128, source="a")
        f2 = rt.submit_sig_checks(checks[4:], backend="host",
                                  pad_block=64, source="b")
    assert f1.result(60.0) == _host_compute(checks[:4])
    assert f2.result(60.0) == _host_compute(checks[4:])
    assert rt.dispatches == 2


def test_max_coalesce_caps_group_size():
    cfg = DeviceRuntimeConfig(max_coalesce=4)
    rt = DeviceRuntime(cfg)
    try:
        checks = pipeline_verify_fixture(16, n_unique=8, invalid_every=4)
        with rt.hold():
            futs = [rt.submit_sig_checks([c], backend="host",
                                         source="s%d" % i)
                    for i, c in enumerate(checks)]
        got = [f.result(60.0) for f in futs]
        assert got == [[v] for v in _host_compute(checks)]
        assert rt.dispatches == 4  # 16 submissions / cap 4
    finally:
        rt.close()


# ------------------------------------------------------------- fairness ----

def test_miner_flood_cannot_starve_block_verify(rt):
    """A saturating 'mine' stream (weight 1) queued ahead of a burst of
    'block' items (weight 4): the block items are served near the front
    and their queue wait stays bounded while the flood drains."""
    served = []

    def work(tag):
        def fn():
            served.append(tag)
            time.sleep(0.002)
        return fn

    n_mine, n_block = 120, 5
    with rt.hold():
        mine_futs = [rt.submit_call(work("mine"), kernel="pow",
                                    source="mine") for _ in range(n_mine)]
        block_futs = [rt.submit_call(work("block"), kernel="verify",
                                     source="block") for _ in range(n_block)]
    for f in block_futs + mine_futs:
        f.result(timeout=60.0)

    # all five block items served within the first handful of slots even
    # though 120 miner items were queued first
    block_pos = [i for i, tag in enumerate(served) if tag == "block"]
    assert len(block_pos) == n_block
    assert max(block_pos) < 12, served[:16]

    waits = rt.stats()["queue_waits"]
    # the flood's tail waits for the whole drain; block verify does not
    assert max(waits["block"]) < max(waits["mine"]) / 4


def test_idle_source_cannot_bank_credit(rt):
    """A source waking from idle starts at the current virtual time —
    idleness is not a stored entitlement to a monopoly burst."""
    with rt.hold():
        for _ in range(10):
            rt.submit_call(lambda: None, source="mine")
    [f.result(30.0) for f in [rt.submit_call(lambda: "x", source="mine")]]
    # vtime has advanced; a brand-new source starts AT it, not at zero
    with rt._cv:
        vtime = rt._vtime
    assert vtime > 0
    with rt.hold():
        fut = rt.submit_call(lambda: "y", source="late")
        with rt._cv:
            assert rt._passes["late"] >= vtime
    assert fut.result(30.0) == "y"


# ------------------------------------------------------ degrade choke ----

def test_degrade_flip_mid_flight_drains_host_byte_identical(rt, monkeypatch):
    """Items queued BEFORE a degrade flip execute AFTER it on the host
    path (backend resolution happens at pop time, not submit time) and
    the verdicts are byte-identical to the serial host path."""
    mgr = DegradeManager(failure_limit=1, cooldown=3600.0)
    monkeypatch.setattr(txverify, "DEGRADE", mgr)
    checks = pipeline_verify_fixture(24, n_unique=8, invalid_every=4)
    expected = _host_compute(checks)

    state_at_execute = []
    real = txverify.run_sig_checks

    def spy(cks, **kw):
        state_at_execute.append(txverify.DEGRADE.state)
        return real(cks, **kw)

    monkeypatch.setattr(txverify, "run_sig_checks", spy)

    with rt.hold():
        fut = rt.submit_sig_checks(checks, backend="auto",
                                   device_timeout=30.0, source="block")
        # the flip happens while the batch is still queued
        mgr.record_failure(RuntimeError("device went sick"))
        assert mgr.state == "degraded"
    assert fut.result(60.0) == expected
    # the dispatch ran after the flip and saw the degraded state (the
    # cache layer re-enters run_sig_checks for misses, hence >= 1 call)
    assert state_at_execute and set(state_at_execute) == {"degraded"}


def test_degrade_runtime_fault_site_drains_host(rt, monkeypatch):
    """An injected device.runtime fault records a degrade failure and
    re-runs the group on the host — callers get byte-identical verdicts
    and never see the fault."""
    mgr = DegradeManager(failure_limit=1, cooldown=3600.0)
    monkeypatch.setattr(txverify, "DEGRADE", mgr)
    checks = pipeline_verify_fixture(16, n_unique=8, invalid_every=3)
    expected = _host_compute(checks)
    faultinject.install("device.runtime:error:times=1", seed=1337)
    try:
        fut = rt.submit_sig_checks(checks, backend="host",
                                   device_timeout=30.0, source="mempool")
        assert fut.result(60.0) == expected
    finally:
        faultinject.uninstall()
    assert mgr.state == "degraded"
    assert mgr.snapshot()["consecutive_failures"] == 1
    assert metrics.counters().get("runtime.faults", 0) == 1


def test_fault_site_on_boxed_call_surfaces_as_status(rt):
    """submit_call's boxed mode turns an injected dispatch fault into
    the ('err', exc) status tuple — the caller's own degrade policy
    decides, exactly like the pre-runtime boxed_call contract."""
    faultinject.install("device.runtime:error:times=1", seed=7)
    try:
        status, value = rt.run_boxed(lambda: 42, timeout=10.0,
                                     kernel="probe", source="bench")
    finally:
        faultinject.uninstall()
    assert status == "err"
    assert isinstance(value, faultinject.FaultInjected)
    # the injector is spent: the next dispatch is clean
    assert rt.run_boxed(lambda: 42, timeout=10.0) == ("ok", 42)


# ----------------------------------------------------- arm failure ----

def test_arm_failure_serves_every_subsystem_on_cpu(monkeypatch):
    """A probe that hangs/fails arms the runtime WITHOUT a backend:
    platform() is None, devices() is [], and sig/call submissions from
    every source still complete on host paths without deadlock."""
    _fake_probe(monkeypatch, None, "timeout", _HANG)
    monkeypatch.setattr(txverify, "DEGRADE",
                        DegradeManager(failure_limit=3, cooldown=3600.0))
    rt = DeviceRuntime(DeviceRuntimeConfig(arm_timeout=5.0))
    try:
        assert rt.platform() is None
        assert rt.devices() == []
        arm = rt.stats()["arm"]
        assert arm["armed"] and arm["platform"] is None
        assert arm["arm_failure_reason"] == _HANG
        assert arm["probe_status"] == "timeout"

        checks = pipeline_verify_fixture(12, n_unique=6, invalid_every=4)
        expected = _host_compute(checks)
        futs = [rt.submit_sig_checks(checks, backend="auto",
                                     device_timeout=10.0, source=s)
                for s in ("block", "mempool", "verify")]
        calls = [rt.submit_call(lambda i=i: i * i, source=s)
                 for i, s in enumerate(("mine", "index", "bench"))]
        for f in futs:
            assert f.result(timeout=30.0) == expected
        assert [c.result(timeout=30.0) for c in calls] == [0, 1, 4]
    finally:
        rt.close()


def test_arm_failure_reason_in_structured_info(monkeypatch):
    calls = _fake_probe(monkeypatch, None, "err",
                        "RuntimeError('PJRT: no device')")
    rt = DeviceRuntime(DeviceRuntimeConfig(arm_timeout=3.0))
    try:
        info = rt.arm(attempt="test-attempt")
        assert calls == [3.0]
        assert info["platform"] is None
        assert info["attempt"] == "test-attempt"
        assert info["arm_failure_reason"] == "RuntimeError('PJRT: no device')"
        assert info["probe_status"] == "err"
        assert info["device_count"] == 0
    finally:
        rt.close()


# ---------------------------------------------------- the one probe ----

def _raise(exc):
    raise exc


@pytest.mark.parametrize("case", ["ok-tpu", "ok-cpu", "timeout", "err"])
def test_probe_platform_record(monkeypatch, case):
    """The probe reports ``jax.devices()[0].platform`` as JAX names it
    (every backend-routing comparison is written against "tpu"), and
    keeps the failure when there is none to report."""
    boom = None
    if case == "err":
        try:
            _raise(RuntimeError("PJRT INTERNAL: boom"))
        except RuntimeError as e:
            boom = e
    status, value = {"ok-tpu": ("ok", "tpu"), "ok-cpu": ("ok", "cpu"),
                     "timeout": ("timeout", None),
                     "err": ("err", boom)}[case]
    monkeypatch.setattr(rt_mod, "boxed_call",
                        lambda fn, timeout: (status, value))
    rec = rt_mod.probe_platform(7.0)
    assert set(rec) == {"status", "platform", "seconds", "error",
                        "traceback_fingerprint"}
    assert rec["status"] == status
    assert rec["platform"] == (value if status == "ok" else None)
    if case == "timeout":
        assert "after 7s" in rec["error"] and "native hang" in rec["error"]
    elif case == "err":
        assert rec["error"] == repr(boom)
        assert "PJRT INTERNAL: boom" in rec["error"]
        assert rec["traceback_fingerprint"] == \
            rt_mod.traceback_fingerprint(boom)
        assert len(rec["traceback_fingerprint"]) == 12
    else:
        assert rec["error"] is None
        assert rec["traceback_fingerprint"] is None


def test_one_probe_a_process(monkeypatch, fresh_singleton):
    """Whoever arms first probes; every later arm — another
    DeviceRuntime, the device index, the sig path's backend gate, the
    mesh miner — reads that record: a hung backend costs a process ONE
    timeout."""
    from upow_tpu.mine.mesh_engine import MeshEngine
    from upow_tpu.state import ChainState

    calls = _fake_probe(monkeypatch, "cpu")
    monkeypatch.setattr(txverify, "DEGRADE",
                        DegradeManager(failure_limit=3, cooldown=3600.0))
    first = DeviceRuntime(DeviceRuntimeConfig(arm_timeout=4.0))
    second = DeviceRuntime()
    try:
        assert first.arm()["platform"] == "cpu"
        assert second.arm()["platform"] == "cpu"
        assert second.platform() == "cpu"
    finally:
        first.close()
        second.close()
    state = ChainState()
    try:
        state.enable_device_index()
        assert state.resident_indexes() is not None
    finally:
        state.close()
    assert txverify._device_usable() is False       # "cpu": host path
    assert txverify.DEGRADE.state == "ok"
    eng = MeshEngine(mesh_devices=2, batch_per_device=64)
    assert eng.arm()["armed"]
    assert rt_mod.get_runtime().stats()["arm"]["platform"] == "cpu"
    assert calls == [4.0]


def test_arm_order_cache_probe_then_devices(monkeypatch):
    """Inside arm(): the compile cache is enabled before the backend
    exists, then the one probe, then ``jax.devices()`` for kind and
    count — once each (the arm is 10-19 s of every cell's ``setup_s``;
    a second probe or enumeration would show there)."""
    import jax

    from upow_tpu import compile_cache

    order = []
    real_devices = jax.devices

    def probe(timeout):
        order.append("probe")
        return {"status": "ok", "platform": "cpu", "seconds": 0.0,
                "error": None, "traceback_fingerprint": None}

    monkeypatch.setattr(rt_mod, "_PROBE", None)
    monkeypatch.setattr(rt_mod, "probe_platform", probe)
    monkeypatch.setattr(compile_cache, "enable",
                        lambda: order.append("cache") or "/cache/dir")
    monkeypatch.setattr(jax, "devices",
                        lambda *a: order.append("devices") or real_devices())
    rt = DeviceRuntime()
    try:
        info = rt.arm(attempt="order")
        again = rt.arm()
    finally:
        rt.close()
    assert order == ["cache", "probe", "devices"]
    assert again == info
    assert info["compile_cache_dir"] == "/cache/dir"
    assert info["device_count"] == len(real_devices())
    assert rt_mod.device_line(info) == (
        "device: platform=cpu kind=%s count=%d compile_cache=/cache/dir"
        % (info["device_kind"], info["device_count"]))


@pytest.mark.parametrize("site", ["storage", "pg", "txverify"])
def test_no_platform_keeps_the_host_paths(monkeypatch, fresh_singleton,
                                          caplog, site):
    """No platform: the device index stays off and the sig path is
    poisoned onto the host, with the warnings operators grep for."""
    import logging

    _fake_probe(monkeypatch, None, "timeout", _HANG)
    monkeypatch.setattr(txverify, "DEGRADE",
                        DegradeManager(failure_limit=3, cooldown=3600.0))
    caplog.set_level(logging.WARNING)
    if site == "txverify":
        assert txverify._device_usable() is False
        assert txverify.DEGRADE.state == "poisoned"
        assert not txverify.device_verify_allowed()
        assert ("jax backend init hung/failed; signature verification "
                "pinned to the host path for this process") in caplog.text
        return
    if site == "storage":
        from upow_tpu.state import ChainState

        state = ChainState()
    else:
        from upow_tpu.state.pg import PgChainState
        from upow_tpu.state.pgdriver import MockPgDriver

        state = PgChainState(driver=MockPgDriver())
    try:
        state.enable_device_index()
        assert state.resident_indexes() is None
        assert state.index_stats() is None
    finally:
        state.close()
    assert ("jax backend init hung/failed; device UTXO index disabled"
            in caplog.text)


def test_platform_asked_from_the_drainer_thread(fresh_singleton):
    """A sig batch resolves its backend at pop time, on the drainer's
    own thread, by asking the runtime that thread belongs to: the arm
    happened before the first item was served, so it does not wait on
    itself."""
    rt = rt_mod.get_runtime()
    checks = pipeline_verify_fixture(6, n_unique=3, invalid_every=4)
    fut = rt.submit_sig_checks(checks, backend="auto", device_timeout=10.0,
                               source="block")
    assert fut.result(timeout=60.0) == _host_compute(checks)
    seen = rt.submit_call(
        lambda: (threading.current_thread().name, rt.platform()))
    assert seen.result(timeout=10.0) == ("upow-device-runtime", "cpu")


# ------------------------------------------------------ co-residency ----

def test_coresidency_differential_and_coalescing():
    """Miner + block verify + mempool intake on ONE runtime
    (loadgen/coresidency.py at smoke size): every concurrent verdict
    slice byte-identical to the serial host reference and to the
    one-dispatch-per-batch pass, in fewer dispatches."""
    from upow_tpu.loadgen.coresidency import CoresidencySpec, run_coresidency

    r = run_coresidency(CoresidencySpec.smoke())
    assert r["differential"]["ok"], r["differential"]
    assert r["differential"]["checks"] > 0
    assert r["differential"]["mismatches"] == 0
    assert r["dispatch_reduction"] > 1
    assert r["concurrent"]["sig_dispatches"] < r["serial"]["dispatches"]


# -------------------------------------------------- service plumbing ----

def test_run_boxed_matches_boxed_call_contract(rt):
    assert rt.run_boxed(lambda: "v", timeout=10.0) == ("ok", "v")
    status, exc = rt.run_boxed(
        lambda: (_ for _ in ()).throw(ValueError("boom")), timeout=10.0)
    assert status == "err" and isinstance(exc, ValueError)
    assert rt.run_boxed(lambda: time.sleep(5), timeout=0.1) \
        == ("timeout", None)


def test_inline_execution_from_drainer_thread(rt):
    """A dispatch nested inside a dispatch executes inline — queueing
    it would deadlock the single drainer thread."""
    def outer():
        inner = rt.submit_call(lambda: "nested", source="verify")
        return inner.result(timeout=1.0)

    fut = rt.submit_call(outer, source="block")
    assert fut.result(timeout=30.0) == "nested"


def test_dispatch_runs_in_submitter_context(rt):
    """Degrade/fault events emitted inside a dispatch must carry the
    submitter's trace ID: the drainer enters the submitter's captured
    contextvars for both call items and coalesced sig groups
    (regression: tests/test_chaos.py asserts device events have ids)."""
    import contextvars

    var = contextvars.ContextVar("rt_test_trace", default=None)
    var.set("submitter-context")

    fut = rt.submit_call(lambda: var.get(), source="verify")
    assert fut.result(timeout=30.0) == "submitter-context"

    boxed = rt.submit_call(lambda: var.get(), source="verify",
                           timeout=10.0)
    assert boxed.result(timeout=30.0) == ("ok", "submitter-context")

    seen = []
    real = txverify.run_sig_checks

    def spy(checks, **kw):
        seen.append(var.get())
        return real(checks, **kw)

    checks = pipeline_verify_fixture(4, n_unique=4, invalid_every=3)
    try:
        txverify.run_sig_checks = spy
        rt.submit_sig_checks(checks, backend="host",
                             source="block").result(timeout=60.0)
    finally:
        txverify.run_sig_checks = real
    assert seen and seen[0] == "submitter-context"


def test_empty_checks_resolve_immediately(rt):
    assert rt.submit_sig_checks([]).result(timeout=1.0) == []


def test_close_fails_pending_and_rejects_new(rt):
    with rt.hold():
        fut = rt.submit_call(lambda: 1, source="bench")
        rt.close()
    with pytest.raises(RuntimeError):
        fut.result(timeout=5.0)
    with pytest.raises(RuntimeError):
        rt.submit_call(lambda: 2)


def test_queue_overflow_rejects():
    rt = DeviceRuntime(DeviceRuntimeConfig(queue_max=3))
    try:
        with rt.hold():
            for _ in range(3):
                rt.submit_call(lambda: None, source="bench")
            with pytest.raises(RuntimeError):
                rt.submit_call(lambda: None, source="bench")
    finally:
        rt.close()


def test_runtime_telemetry_families_exported(rt):
    checks = pipeline_verify_fixture(8, n_unique=4, invalid_every=3)
    rt.submit_sig_checks(checks, backend="host",
                         source="block").result(60.0)
    counters = metrics.counters()
    assert counters.get("runtime.submissions", 0) >= 1
    assert counters.get("runtime.dispatches", 0) >= 1
    assert counters.get("runtime.source.block", 0) >= 1
    hists = metrics.histograms()
    assert "runtime.queue_depth" in hists
    assert "runtime.coalesced" in hists
    assert "runtime.queue_wait.block" in hists


def _runtime_families():
    """Everything a dispatch leaves under ``runtime.*`` and
    ``kernel.device_runtime.*``, zeros left out; a histogram as
    (per-bucket counts, sum)."""
    counters = {k: v for k, v in metrics.counters().items()
                if k.startswith(("runtime.", "kernel.device_runtime."))
                and v}
    hists = {k: (h["counts"], h["sum"])
             for k, h in metrics.histograms().items()
             if k.startswith(("runtime.", "kernel.device_runtime."))
             and h["count"]}
    return counters, hists


@pytest.mark.parametrize("kind", ["call", "sig"])
def test_a_dispatch_leaves_in_the_runtime_families_what_it_always_left(
        rt, kind):
    """The families docs/OBSERVABILITY.md gives a node's operator, value
    for value as before ISSUE 42 made a drain one registry update: a
    ``call`` item (a miner's round) counts as a batch of one lane in
    one, a signature group as its checks padded to ``pad_block``."""
    from upow_tpu.telemetry.device import (DISPATCH_BUCKETS,
                                           OCCUPANCY_BUCKETS,
                                           RUNTIME_COALESCE_BUCKETS,
                                           RUNTIME_QUEUE_DEPTH_BUCKETS)

    def one_in(bounds, index, n=1):
        counts = [0] * (len(bounds) + 1)
        counts[index] = n
        return counts

    if kind == "call":
        assert rt.submit_call(lambda: 7, kernel="sha256_search_mesh",
                              source="mine").result(30.0) == 7
        source, submissions, real, padded = "mine", 1, 1, 1
    else:
        checks = pipeline_verify_fixture(8, n_unique=4, invalid_every=3)
        with rt.hold():      # two submitters share the one dispatch
            futs = [rt.submit_sig_checks(checks[:5], backend="host",
                                         source="block"),
                    rt.submit_sig_checks(checks[5:], backend="host",
                                         source="block")]
        assert [len(f.result(60.0)) for f in futs] == [5, 3]
        source, submissions, real, padded = "block", 2, 8, 128
    # the drainer records after it resolves the future
    deadline = time.time() + 10.0
    while not metrics.counters().get("runtime.dispatches") \
            and time.time() < deadline:
        time.sleep(0.01)
    counters, hists = _runtime_families()
    assert counters == {
        "runtime.submissions": submissions,
        "runtime.source.%s" % source: submissions,
        "runtime.dispatches": 1,
        "kernel.device_runtime.lanes_real": real,
        "kernel.device_runtime.lanes_padded": padded}
    wait_counts, wait_sum = hists.pop("runtime.queue_wait.%s" % source)
    took_counts, took_sum = hists.pop(
        "kernel.device_runtime.dispatch_seconds")
    assert sum(wait_counts) == sum(took_counts) == 1
    assert 0.0 <= wait_sum < 30.0 and 0.0 < took_sum < 60.0
    for counts, value in ((wait_counts, wait_sum), (took_counts, took_sum)):
        at = counts.index(1)    # the first bound the value does not pass
        assert at == len(DISPATCH_BUCKETS) or value <= DISPATCH_BUCKETS[at]
        assert at == 0 or value > DISPATCH_BUCKETS[at - 1]
    assert hists == {
        "runtime.coalesced": (
            one_in(RUNTIME_COALESCE_BUCKETS, submissions - 1),
            submissions),
        # two queued at the pop that drained both
        "runtime.queue_depth": (
            one_in(RUNTIME_QUEUE_DEPTH_BUCKETS, submissions - 1),
            submissions),
        "kernel.device_runtime.occupancy": (
            one_in(OCCUPANCY_BUCKETS, 5 if kind == "call" else 0),
            real / padded)}


@pytest.mark.parametrize("value,index", [
    (0.0, 0), (0.1, 0), (0.100001, 1), (0.5, 2), (1.0, 5), (1.5, 6),
    (-3.0, 0)])
def test_observe_finds_the_first_bound_the_value_does_not_pass(value,
                                                               index):
    from upow_tpu.telemetry.device import OCCUPANCY_BUCKETS

    metrics.observe("test.bounds", value, buckets=OCCUPANCY_BUCKETS)
    h = metrics.histograms()["test.bounds"]
    assert h["counts"].index(1) == index and h["sum"] == value


def test_an_update_is_its_incs_and_observations_under_one_lock():
    metrics.update((("test.a", 2), ("test.b", 1), ("test.a", 3)),
                   (("test.h", 0.3, (0.25, 0.5)), ("test.h", 9.0, None)))
    counters = metrics.counters()
    assert (counters["test.a"], counters["test.b"]) == (5, 1)
    h = metrics.histograms()["test.h"]
    # bounds are fixed by the first observation, as with observe()
    assert h["bounds"] == (0.25, 0.5) and h["counts"] == [0, 1, 1]
    assert h["count"] == 2 and h["sum"] == 9.3


def test_weights_config_parsing():
    cfg = DeviceRuntimeConfig(weights="block=4, mine = 1,bad")
    w = cfg.parsed_weights()
    assert w["block"] == 4 and w["mine"] == 1 and "bad" not in w


def test_concurrent_submitters_thread_safe(rt):
    """Many threads hammering submit while the drainer runs: every
    future resolves with its own result."""
    results = {}

    def submitter(i):
        fut = rt.submit_call(lambda i=i: i * 3, source="s%d" % (i % 4))
        results[i] = fut.result(timeout=30.0)

    threads = [threading.Thread(target=submitter, args=(i,))
               for i in range(48)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert results == {i: i * 3 for i in range(48)}
