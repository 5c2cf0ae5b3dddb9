"""The one span system: telemetry spans as profiler host events, the
miner's job-rooted spans and counters, the compile listener's durations
and the miner process's profiler hook.  Registries are process-wide and
other tests feed them, so every count is a difference."""

import glob
import hashlib
import importlib.util
import os
import random
import re
import signal
import subprocess
import sys

import pytest

from upow_tpu import telemetry
from upow_tpu.mine import engine, miner
from upow_tpu.mine.engine import MiningJob, mine
from upow_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
rng = random.Random(2525)


def _job(difficulty="9", prev=None) -> MiningJob:
    from upow_tpu.core import curve, point_to_string

    prev = prev or bytes(rng.randrange(256) for _ in range(32)).hex()
    _, pub = curve.keygen(rng=rng.randrange(1, 1 << 200))
    return MiningJob.from_header_fields(
        previous_hash=prev, address=point_to_string(pub),
        merkle_root=hashlib.sha256(b"").hexdigest(),
        timestamp=1_753_791_000, difficulty=difficulty)


def _counter(name: str) -> int:
    return telemetry.counters().get(name, 0)


def _nodes(tree: dict) -> int:
    return 1 + sum(_nodes(c) for c in tree.get("spans", []))


# ------------------------------------------------ the profiler bridge --

class FakeAnnotation:
    log: list = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        FakeAnnotation.log.append(("open", self.name, dict(self.kw)))
        return self

    def __exit__(self, *exc):
        FakeAnnotation.log.append(("close", self.name, dict(self.kw)))
        return False


@pytest.fixture
def fake_annotations(monkeypatch):
    FakeAnnotation.log = []
    monkeypatch.setattr(tracing, "_annotation_cls", FakeAnnotation)
    return FakeAnnotation.log


def test_spans_open_and_close_annotations_lifo_with_trace_id(
        fake_annotations):
    with telemetry.request_trace("req", block=7) as root:
        with telemetry.span("outer", kernel="k", blob=object()):
            with telemetry.span("inner", light=True):
                pass
        child = telemetry.child_span(root, "explicit", n=3)
        telemetry.finish_child(child)
    tid = root.trace_id
    assert [(what, name) for what, name, _kw in fake_annotations] == [
        ("open", "req"), ("open", "outer"), ("open", "inner"),
        ("close", "inner"), ("close", "outer"),
        ("open", "explicit"), ("close", "explicit"), ("close", "req")]
    by_name = {name: kw for what, name, kw in fake_annotations
               if what == "open"}
    assert all(kw["trace"] == tid for kw in by_name.values())
    assert by_name["req"]["block"] == 7
    # small fields only: an object is no keyword of a profiler event
    assert by_name["outer"] == {"kernel": "k", "trace": tid}
    assert by_name["explicit"]["n"] == 3


def test_light_span_feeds_the_aggregate_and_leaves_the_tree_alone(
        fake_annotations):
    before = telemetry.stats().get("light.round", {}).get("count", 0)
    with telemetry.request_trace("req") as root:
        for _ in range(600):    # more than the 512-span budget of a root
            with telemetry.span("light.round", light=True) as node:
                assert node is None
        with telemetry.span("job.level") as node:
            assert node is not None
    assert telemetry.stats()["light.round"]["count"] == before + 600
    assert [c.name for c in root.children] == ["job.level"]
    assert sum(1 for what, name, _kw in fake_annotations
               if (what, name) == ("open", "light.round")) == 600


def test_add_span_is_no_profiler_event(fake_annotations):
    with telemetry.request_trace("req") as root:
        telemetry.add_span(root, "already.over", 1.0, 2.0)
    assert [c.name for c in root.children] == ["already.over"]
    assert "already.over" not in {name for _w, name, _kw in fake_annotations}


def test_a_failing_annotation_class_never_breaks_the_span(monkeypatch):
    class Broken:
        def __init__(self, name, **kw):
            raise RuntimeError("profiler gone")

    monkeypatch.setattr(tracing, "_annotation_cls", Broken)
    with telemetry.request_trace("req") as root:
        with telemetry.span("still.timed") as node:
            pass
    assert node.done and root.children == [node]


class Unheard:
    """A profiler class as jax's: ``is_enabled`` says whether a session
    runs, whichever thread started it."""
    session = False
    built: list = []

    @staticmethod
    def is_enabled():
        return Unheard.session

    def __init__(self, name, **kw):
        Unheard.built.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_an_event_is_built_only_while_a_session_can_hear_it(monkeypatch):
    import threading

    Unheard.session, Unheard.built = False, []
    monkeypatch.setattr(tracing, "_annotation_cls", Unheard)

    def spans():
        with telemetry.request_trace("req"):
            with telemetry.span("tree"):
                with telemetry.span("light", light=True):
                    pass

    spans()
    assert Unheard.built == []
    # the launcher's thread starts the session and tells nobody
    starter = threading.Thread(
        target=lambda: setattr(Unheard, "session", True))
    starter.start()
    starter.join(timeout=10.0)
    spans()
    assert Unheard.built == ["req", "tree", "light"]
    Unheard.session = False
    spans()
    assert len(Unheard.built) == 3


def _no_profiler(monkeypatch):
    monkeypatch.setattr(tracing, "_annotation_cls", None)
    monkeypatch.setitem(sys.modules, "jax", None)    # jax never loaded


def _failing(where):
    class Failing:
        def __init__(self, name, **kw):
            if where == "init":
                raise RuntimeError("profiler gone")

        def __enter__(self):
            if where == "enter":
                raise RuntimeError("profiler gone")
            return self

        def __exit__(self, *exc):
            raise RuntimeError("profiler gone")

    if where == "is_enabled":
        def is_enabled():
            raise RuntimeError("profiler gone")

        Failing.is_enabled = staticmethod(is_enabled)
    return lambda monkeypatch: monkeypatch.setattr(
        tracing, "_annotation_cls", Failing)


@pytest.mark.parametrize("profiler", [
    _no_profiler, _failing("init"), _failing("enter"), _failing("exit"),
    _failing("is_enabled")],
    ids=["none", "init", "enter", "exit", "is_enabled"])
def test_a_light_span_times_whatever_the_profiler_does(monkeypatch,
                                                       profiler):
    profiler(monkeypatch)
    before = telemetry.stats().get("light.timed", {"count": 0,
                                                   "total_s": 0.0})
    with telemetry.span("light.timed", light=True, kernel="k") as node:
        tracing.time.sleep(0.002)
    assert node is None
    after = telemetry.stats()["light.timed"]
    assert after["count"] == before["count"] + 1
    assert after["total_s"] - before["total_s"] >= 0.002
    # and the caller's own exception is the caller's
    with pytest.raises(KeyError):
        with telemetry.span("light.timed", light=True):
            raise KeyError("theirs")
    assert telemetry.stats()["light.timed"]["count"] == before["count"] + 2


def test_telemetry_spans_do_not_import_jax():
    code = ("import sys\n"
            "from upow_tpu import telemetry\n"
            "with telemetry.request_trace('r'):\n"
            "    with telemetry.span('a'):\n"
            "        with telemetry.span('b', light=True):\n"
            "            pass\n"
            "assert telemetry.stats()['b']['count'] == 1\n"
            "print('jax' in sys.modules)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "False"


def test_only_tracing_names_the_profilers_annotation_class():
    hits = []
    for path in glob.glob(os.path.join(REPO, "upow_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            if "TraceAnnotation" in f.read():
                hits.append(os.path.relpath(path, REPO))
    assert hits == [os.path.join("upow_tpu", "telemetry", "tracing.py")]


# ------------------------------------------- the miner's spans, counted --

def test_a_job_counts_its_rounds_and_keeps_its_tree_small():
    rounds, batch = 9, 512
    before = {n: _counter(n) for n in
              ("mine.rounds", "mine.nonces", "runtime.source.mine")}
    agg = telemetry.stats()
    with telemetry.request_trace("mine.job") as root:
        result = mine(_job("9"), "jnp", batch=batch,
                      stride_end=rounds * batch - 100)
    assert result.nonce is None
    assert result.hashes_tried == rounds * batch - 100
    assert _counter("mine.rounds") - before["mine.rounds"] == rounds
    assert _counter("mine.nonces") - before["mine.nonces"] == \
        rounds * batch - 100
    assert _counter("runtime.source.mine") \
        - before["runtime.source.mine"] == rounds
    tree = [t for t in telemetry.traces()["recent"]
            if t["trace_id"] == root.trace_id][0]
    assert _nodes(tree) <= 12
    assert {c["name"] for c in tree["spans"]} == {"mine.prepare",
                                                   "mine.first_issue"}

    def grew(name):
        return telemetry.stats()[name]["count"] \
            - agg.get(name, {}).get("count", 0)

    assert grew("mine.first_issue") == 1
    assert grew("mine.round.issue") == rounds - 1
    assert grew("mine.round.wait") == rounds
    assert grew("runtime.call") == rounds


@pytest.fixture
def no_mesh_engine_left():
    from upow_tpu.mine.mesh_engine import reset_mesh_engine

    yield
    reset_mesh_engine()


@pytest.mark.parametrize("length,tails", [(3 * 256 + 40, 1), (3 * 256, 0)])
@pytest.mark.parametrize("backend", ["jnp", "mesh"])
def test_a_masked_round_issues_under_mine_round_tail(
        fake_annotations, no_mesh_engine_left, backend, length, tails):
    """``mine.round.tail`` opens inside the ``mine.round.issue`` of a
    round shorter than the program, once a job, and never over a range
    that ends on a whole round: on the static-target engine and on the
    resident one over one device (``--device tpu``) alike."""
    agg = telemetry.stats().get("mine.round.tail", {}).get("count", 0)
    with telemetry.request_trace("mine.job") as root:
        result = mine(_job("9"), backend, batch=256, stride_end=length,
                      mesh_devices=1)
    assert result.hashes_tried == length
    assert telemetry.stats().get("mine.round.tail", {}).get("count", 0) \
        - agg == tails
    names = [(what, name) for what, name, _kw in fake_annotations
             if name in ("mine.round.issue", "mine.round.tail")]
    assert names.count(("open", "mine.round.tail")) == tails
    if tails:
        at = names.index(("open", "mine.round.tail"))
        assert names[at - 1:at + 3] == [
            ("open", "mine.round.issue"), ("open", "mine.round.tail"),
            ("close", "mine.round.tail"), ("close", "mine.round.issue")]
        assert at + 3 == len(names)          # the job's last issue
    # a light span: the job's tree is left to the job-level spans
    tree = [t for t in telemetry.traces()["recent"]
            if t["trace_id"] == root.trace_id][0]
    assert {c["name"] for c in tree["spans"]} == {"mine.prepare",
                                                   "mine.first_issue"}


ROUND = [("open", "mine.round.plan"), ("close", "mine.round.plan"),
         ("open", "mine.round.submit"), ("open", "runtime.call"),
         ("close", "runtime.call"), ("close", "mine.round.submit")]


@pytest.mark.parametrize("around", ["mine.first_issue", "mine.round.issue",
                                    "mine.round.tail"])
def test_a_mesh_round_is_planned_then_submitted_inside_its_issue(
        span_events, no_mesh_engine_left, around):
    """``MeshEngine.dispatch`` cuts the host's issue in two where the
    work is: ``mine.round.plan`` then ``mine.round.submit`` on the
    miner's thread, ``runtime.call`` on the device owner's from inside
    the submit to before its end; light spans all, in a job's first
    round, a whole round and a masked one alike."""
    import threading

    agg = telemetry.stats()
    rounds = 4
    with telemetry.request_trace("mine.job") as root:
        result = mine(_job("9"), "mesh", batch=256,
                      stride_end=(rounds - 1) * 256 + 40, mesh_devices=1)
    assert result.hashes_tried == (rounds - 1) * 256 + 40
    log = span_events
    me = threading.current_thread().name
    # every enclosing span of this kind holds exactly one round's six
    opened = [i for i, ev in enumerate(log) if ev[:2] == ("open", around)]
    assert len(opened) == {"mine.first_issue": 1, "mine.round.issue":
                           rounds - 1, "mine.round.tail": 1}[around]
    for at in opened:
        inside = log[at + 1:log.index(("close", around, me), at)]
        if around == "mine.round.issue" and \
                inside[0][:2] == ("open", "mine.round.tail"):
            inside = inside[1:-1]
        assert [ev[:2] for ev in inside] == ROUND
        threads = {name: thread for _w, name, thread in inside}
        assert threads["mine.round.plan"] == me
        assert threads["mine.round.submit"] == me
        assert threads["runtime.call"] == "upow-device-runtime"
    assert log[opened[0]][2] == me

    def grew(name):
        return telemetry.stats()[name]["count"] \
            - agg.get(name, {}).get("count", 0)

    # the flat aggregate has them, the job's tree does not
    assert grew("mine.round.plan") == grew("mine.round.submit") == rounds
    tree = [t for t in telemetry.traces()["recent"]
            if t["trace_id"] == root.trace_id][0]
    assert {c["name"]: c.get("spans") for c in tree["spans"]} == {
        "mine.prepare": None, "mine.first_issue": None}


def test_a_new_tip_is_one_compile_key_miss_and_the_same_tip_none():
    name = "kernel.sha256_search.compile_cache_misses"
    tip_a, tip_b = ("%064x" % 0xA11CE), ("%064x" % 0xB0B)
    mine(_job("9", prev=tip_a), "jnp", batch=256, stride_end=256)
    seen = _counter(name)
    mine(_job("9", prev=tip_a), "jnp", batch=256, stride_end=256)
    assert _counter(name) == seen
    mine(_job("9", prev=tip_b), "jnp", batch=256, stride_end=256)
    assert _counter(name) == seen + 1


def test_mine_honours_a_patched_make_dispatcher(monkeypatch):
    import numpy as np

    from upow_tpu.crypto.sha256 import SENTINEL

    calls = []

    def make(job, backend, mesh_devices=0, batch=None):
        def dispatch(start, count):
            calls.append((start, count))
            return np.uint32(SENTINEL)
        return dispatch

    monkeypatch.setattr(engine, "_make_dispatcher", make)
    result = mine(_job("9"), "jnp", batch=100, stride_end=250)
    assert calls == [(0, 100), (100, 100), (200, 50)]
    assert result.nonce is None and result.hashes_tried == 250


def test_search_programs_are_still_found_by_pow_search():
    from upow_tpu.crypto import sha256
    from upow_tpu.parallel import mesh

    for fn in (sha256._pow_search_jnp, sha256._pow_search_pallas,
               mesh._pow_search_mesh_resident):
        assert "pow_search" in fn.__name__
        assert "pow_search" in fn.__wrapped__.__name__


def test_a_capture_holds_the_miners_spans_on_the_profilers_clock(tmp_path):
    import jax

    mine(_job("9"), "jnp", batch=256, stride_end=256)   # compile outside
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with telemetry.request_trace("mine.job"):
            mine(_job("9"), "jnp", batch=256, stride_end=5 * 256)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert found
    data = jax.profiler.ProfileData.from_file(found[0])
    lines_of: dict = {}
    for plane in data.planes:
        for n, line in enumerate(plane.lines):
            for ev in line.events:
                lines_of.setdefault(ev.name, set()).add((plane.name, n))
    for name in ("mine.job", "mine.prepare", "mine.first_issue",
                 "mine.round.issue", "mine.round.wait", "runtime.call"):
        assert name in lines_of, sorted(lines_of)[:40]
    # the device owner's thread is not the miner's
    assert lines_of["runtime.call"].isdisjoint(lines_of["mine.round.wait"])
    assert all(plane.startswith("/host:")
               for plane, _n in lines_of["mine.round.wait"])


# ------------------------- ISSUE 42's four metrics, from their data files --

@pytest.fixture(scope="module")
def bench_manifest():
    """``benchmarks/harness/manifest.py``, found as the benchmark finds
    it (its readers import ``harness`` from ``benchmarks/``)."""
    bench = os.path.join(REPO, "benchmarks")
    sys.path.insert(0, bench)
    try:
        from harness import manifest, xplane  # noqa: F401  (readers' import)

        yield manifest
    finally:
        sys.path.remove(bench)


def _hand_made_records(with_the_new_spans=True):
    """A 20 s window on one device.  The device runs all of it but
    [20, 20.12): a round's answer reached the host 120 ms late, and the
    miner's thread sat in ``mine.round.wait`` over [19.99, 20.125).
    Three rounds' issues beside it: plan 0.2 / 0.3 / 0.4 ms, submit
    1.0 / 1.1 / 1.3 ms, the device owner's call 0.8 / 0.9 / 1.2 ms
    inside each submit."""
    s = 1e9

    def rec(plane, line, name, start_s, dur_s):
        return {"plane": plane, "line": line, "name": name,
                "start_ns": start_s * s, "dur_ns": dur_s * s}

    host, dev = "/host:CPU", "/device:TPU:0"
    out = [rec(host, "python3", "perfbench.window", 10.0, 20.0),
           rec(dev, "XLA Ops", "%search.1", 10.0, 10.0),
           rec(dev, "XLA Ops", "%search.1", 20.12, 9.88),
           rec(dev, "XLA Modules", "jit__pow_search_x(1)", 10.0, 10.0),
           rec(host, "python3", "mine.round.wait", 19.99, 0.135),
           rec(host, "python3", "mine.round.wait", 12.0, 0.007)]
    for at, plan, submit, call in ((13.0, 2e-4, 1.0e-3, 0.8e-3),
                                   (14.0, 3e-4, 1.1e-3, 0.9e-3),
                                   (15.0, 4e-4, 1.3e-3, 1.2e-3)):
        out.append(rec(host, "python3", "mine.round.issue", at,
                       plan + submit + 5e-6))
        out.append(rec(host, "drainer", "runtime.call", at + plan + 5e-5,
                       call))
        if with_the_new_spans:
            out.append(rec(host, "python3", "mine.round.plan", at, plan))
            out.append(rec(host, "python3", "mine.round.submit",
                           at + plan, submit))
    return out


@pytest.mark.parametrize("name,layer,reads,parent_reads", [
    ("round_plan_ms.mine", "miner CLI and engine", 0.3, None),
    ("round_submit_ms.mine", "device owner", 1.1, None),
    ("round_call_ms.mine", "device owner", 0.9, 0.9),
    ("idle_round_wait_share.mine", "device", 0.6, 0.6)])
def test_a_new_metrics_data_file_reads_hand_made_records(
        bench_manifest, name, layer, reads, parent_reads):
    mf = bench_manifest.load_manifest()
    entry = [m for m in mf["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and mf["per_layer"].index(entry[0]) >= 18
    entry = entry[0]
    search = next(m for m in mf["end_to_end"] if m["name"] == "search_mhs")
    assert entry["workloads"] == [
        w["name"] for w in mf["workloads"]
        if bench_manifest.reports(search, w["name"], mf)]
    assert (entry["layer"], entry["moves"], entry["source"],
            entry["better"]) == (layer, "search_mhs", "program_span",
                                 "lower")
    # the cell finds the file by the metric's name, the reader by the file
    specs = {e["name"]: spec for e, spec in
             bench_manifest.layer_metrics_for(mf, "mine-roll-4chip")}
    spec = specs[name]
    assert all(spec[k] == entry[k] for k in ("layer", "unit", "moves",
                                             "source"))
    assert spec["reader"] in ("span_stat", "idle_by_span")  # no new reader
    reader = bench_manifest.load_module("readers", spec["reader"])
    got = reader.read({"records": _hand_made_records()}, spec)
    assert got == pytest.approx(reads)
    # a program without the two spans (the parent): nothing to read for
    # them, and no exception; the other two read a span it already opens
    old = reader.read({"records": _hand_made_records(False)}, spec)
    assert old == (None if parent_reads is None
                   else pytest.approx(parent_reads))


# ------------------------------------------------ the compile listener --

def test_a_fresh_jit_is_one_compile_and_its_second_call_none():
    import jax
    import jax.numpy as jnp

    from upow_tpu import compile_cache

    compile_cache.listen()
    x = jnp.arange(8, dtype=jnp.uint32)
    x.block_until_ready()

    @jax.jit
    def fresh_program_for_this_test(v):
        return v * 3 + 1

    before = _counter("compile.count")
    backend = telemetry.histograms().get(
        "compile.backend_seconds", {"count": 0})["count"]
    with telemetry.request_trace("compile.test") as root:
        fresh_program_for_this_test(x).block_until_ready()
    assert _counter("compile.count") == before + 1
    fresh_program_for_this_test(x).block_until_ready()
    assert _counter("compile.count") == before + 1
    hists = telemetry.histograms()
    assert hists["compile.backend_seconds"]["count"] == backend + 1
    assert hists["compile.trace_seconds"]["count"] >= 1
    assert hists["compile.lower_seconds"]["count"] >= 1
    record = telemetry.events.snapshot(kind="compile")[-1]
    assert "fresh_program_for_this_test" in record["fun_name"]
    assert record["trace_id"] == root.trace_id
    assert record["backend_s"] > 0 and record["trace_s"] > 0


# ------------------------------------------------------ the miner's CLI --

def _minerlog():
    spec = importlib.util.spec_from_file_location(
        "benchmarks_minerlog",
        os.path.join(REPO, "benchmarks", "harness", "minerlog.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _scripted_node(monkeypatch, difficulty, pushes):
    from upow_tpu.core import curve, point_to_string

    _, pub = curve.keygen(rng=77)
    info = {"difficulty": difficulty,
            "last_block": {"hash": "%064x" % 0xFEED, "id": 41},
            "pending_transactions_hashes": ["%064x" % 5, "%064x" % 6]}
    monkeypatch.setattr(miner, "fetch_mining_info", lambda node: dict(info))

    def push(node, content, txs, block_no):
        pushes.append((content, txs, block_no))
        return {"ok": True}

    monkeypatch.setattr(miner, "push_block", push)
    return point_to_string(pub)


@pytest.mark.parametrize("difficulty,ttl,kinds,rc", [
    (1.0, 90.0, ["start", "job", None, "found", None, "mined"], 0),
    (9.0, 0.0, ["start", "job", None, "round", "expired"], 1),
])
def test_the_miners_lines_are_the_ones_the_benchmark_parses(
        monkeypatch, capsys, difficulty, ttl, kinds, rc):
    monkeypatch.delenv("UPOW_PROFILE_ENABLED", raising=False)
    pushes = []
    address = _scripted_node(monkeypatch, difficulty, pushes)
    before = {n: _counter(n) for n in
              ("mine.jobs", "mine.jobs_found", "mine.jobs_expired")}
    assert miner.run(address, "http://x/", "jnp", 4096, ttl,
                     once=True) == rc
    out = capsys.readouterr().out.splitlines()
    log = _minerlog()
    parsed = [log.parse_line(text) for text in out if text]
    got = [None if rec is None else rec["kind"] for rec in parsed]
    # a hit may take a few rounds: fold the progress lines before it
    if rc == 0:
        got = [k for k in got if k != "round"]
    assert got == kinds, out
    assert out[0] == ("upow_tpu miner: backend=jnp shard=0/1 "
                      "nonces=[0, 4294967296) node=http://x/")
    assert out[1] == (f"difficulty: {difficulty}  block: 42  "
                      "confirming 2 transactions")
    # the benchmark's older drivers pass over the line they do not know
    assert re.fullmatch(r"header: timestamp=\d+ behind=0 window=1 repeat=0"
                        r" held=0 age=0\.0", out[2]), out[2]
    assert _counter("mine.jobs") == before["mine.jobs"] + 1
    if rc == 0:
        assert out[-3:] == ["{'ok': True}", "BLOCK MINED", ""]
        assert len(pushes) == 1 and pushes[0][2] == 42
        assert _counter("mine.jobs_found") == before["mine.jobs_found"] + 1
    else:
        assert out[-1] == "template expired after 4096 hashes; refreshing"
        assert _counter("mine.jobs_expired") \
            == before["mine.jobs_expired"] + 1
    job = telemetry.traces()["recent"][-1]
    assert job["name"] == "mine.job"
    assert job["fields"]["end"] == ("found" if rc == 0 else "expired")
    assert job["fields"]["tip"] == ("%064x" % 0xFEED)[-12:]
    # the range's length at the default shard: the sentinel left out
    assert job["fields"]["nonces"] == (1 << 32) - 1
    names = [c["name"] for c in job["spans"]]
    assert names[:4] == ["mine.fetch", "mine.build_job", "mine.prepare",
                         "mine.first_issue"]
    assert ("mine.push" in names) == (rc == 0)


def test_a_failed_fetch_is_counted_and_closes_its_span_with_the_error(
        monkeypatch, capsys):
    """``--once``: the failed try and the next are two ``mine.fetch``
    spans of the one job that waits for its template."""
    calls = []

    def fetch(node):
        calls.append(node)
        if len(calls) == 1:
            raise OSError("connection refused")
        raise KeyboardInterrupt     # ends the loop at the second try

    monkeypatch.setattr(miner, "fetch_mining_info", fetch)
    monkeypatch.setattr(miner.time, "sleep", lambda s: None)
    before = _counter("mine.fetch_errors")
    with pytest.raises(KeyboardInterrupt):
        miner.run("addr", "http://x/", "python", 64, 1.0, once=True)
    assert _counter("mine.fetch_errors") == before + 1
    assert "node unreachable: connection refused; retrying" in \
        capsys.readouterr().err
    job = telemetry.traces()["recent"][-1]
    assert job["error"] == "KeyboardInterrupt" and "end" not in job["fields"]
    failed, cut = job["spans"]
    assert failed["name"] == cut["name"] == "mine.fetch"
    assert failed["error"] == "OSError"
    assert failed["fields"] == {"ok": False, "error": "connection refused"}
    assert cut["error"] == "KeyboardInterrupt"


@pytest.fixture
def restored_signals():
    sigs = (signal.SIGUSR1, signal.SIGUSR2, signal.SIGTERM)
    saved = {s: signal.getsignal(s) for s in sigs}
    yield
    for s, handler in saved.items():
        signal.signal(s, handler)


def _main_with_a_stubbed_run(monkeypatch, rc=0):
    monkeypatch.setenv("UPOW_MINER_CHILD", "1")
    monkeypatch.setattr(miner, "_start_device", lambda device: 0)
    monkeypatch.setattr(miner, "run", lambda *a, **kw: rc)
    return miner.main(["addr", "--device", "python", "--once"])


def test_main_leaves_an_installed_handler_alone_and_says_goodbye(
        monkeypatch, capsys, restored_signals):
    def theirs(*_a):
        pass

    signal.signal(signal.SIGUSR1, theirs)
    signal.signal(signal.SIGUSR2, signal.SIG_DFL)
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    monkeypatch.setenv("UPOW_PROFILE_ENABLED", "1")
    assert _main_with_a_stubbed_run(monkeypatch) == 0
    assert signal.getsignal(signal.SIGUSR1) is theirs
    assert signal.getsignal(signal.SIGUSR2) not in (signal.SIG_DFL, theirs)
    with pytest.raises(SystemExit) as term:
        signal.getsignal(signal.SIGTERM)()
    assert term.value.code == 0
    out = capsys.readouterr().out.splitlines()
    assert out[-2].startswith("memory: peak_bytes=")
    log = _minerlog()
    assert log.parse_line(out[-2])["kind"] == "memory"
    import json

    flat = json.loads(out[-1][len("telemetry: "):])
    assert set(flat) == {"spans", "counters", "rounds_in_flight"}
    assert flat["rounds_in_flight"] == engine.rounds_in_flight()


def test_main_prints_and_installs_nothing_with_the_switch_off(
        monkeypatch, capsys, restored_signals):
    for s in (signal.SIGUSR1, signal.SIGUSR2, signal.SIGTERM):
        signal.signal(s, signal.SIG_DFL)
    monkeypatch.delenv("UPOW_PROFILE_ENABLED", raising=False)
    assert _main_with_a_stubbed_run(monkeypatch, rc=1) == 1
    for s in (signal.SIGUSR1, signal.SIGUSR2, signal.SIGTERM):
        assert signal.getsignal(s) is signal.SIG_DFL
    assert capsys.readouterr().out == ""
