"""The whole of a run, driven past the look for a chip: a stand-in for
the miner child (``fake_miner.py``) says it is on a TPU, and the driver,
the stub node, the reference and ``correct`` do the rest.  Sound, the
run is correct; with one answer altered where it is produced, or the
configuration's guarantee broken where it is checked (the control),
``correct`` comes out false."""

import io
import json
import os
import re
import sys
import time
from contextlib import redirect_stdout
from types import SimpleNamespace

import pytest

import run as bench_run

FAKE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "fake_miner.py")
TINY = {"warm_difficulties": [2.0], "after_difficulties": [2.5],
        "difficulty": 11.0, "round_nonces": 4096,
        "arm_timeout_s": 30, "warm_timeout_s": 60, "miner_args": []}


def drive(fault="", control=None, seed=7, platform="tpu", trace=0,
          traffic=None, child_args=()):
    from upow_tpu.core import curve
    from upow_tpu.core.codecs import point_to_string, string_to_bytes

    _d, pub = curve.keygen(rng=0x5EED0000 + seed)
    address_hex = string_to_bytes(point_to_string(pub)).hex()
    argv = [sys.executable, FAKE, address_hex, "--node", "{node}",
            "--batch", "4096", "--range", "65536", "--platform", platform]
    if fault:
        argv += ["--fault", fault]
    argv += list(child_args)
    args = ["--workload", "mine-sweep-1chip", "--seed", str(seed),
            "--seconds", "1.5", "--trace", str(trace)]
    if control:
        args += ["--control", control]
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(args, faults={
            "child_argv": argv, "traffic": dict(TINY, **(traffic or {}))})
    lines = out.getvalue().strip().splitlines()
    return rc, lines


def test_a_sound_run_is_correct_and_prints_the_contracts_line(capsys):
    rc, lines = drive()
    assert rc == 0, lines[-5:]
    result = json.loads(lines[-1])
    # the contract's keys, then the harness's own two, the checks last
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "stop", "checks"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    assert set(result["metrics"]) == {"search_mhs", "setup_s"}
    assert result["metrics"]["search_mhs"]["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    # a number of 0 or more, not the key alone: what the driver refuses
    peak = result["device"]["memory_peak_bytes"]
    assert type(peak) is int and peak == 4096
    # each number compared is printed beside its limit
    checks = [ln for ln in lines if ln.startswith("[check] ")]
    assert len(checks) >= 7 and all("(limit " in ln for ln in checks)
    # a block mined before and one after the window, each under the reference
    assert sum("_nonce_minus_reference_lowest: 0 (limit 0) ok" in ln
               for ln in checks) == 2
    # and again as the last lines on stderr and last in the result
    said = capsys.readouterr().err.strip().splitlines()
    assert [c["name"] for c in result["checks"]] == \
        [ln.split()[1].rstrip(":") for ln in said[-len(checks):]] == \
        [ln.split()[1].rstrip(":") for ln in checks]
    assert all(c["ok"] for c in result["checks"])
    # how the child went down: asked, answered, stopped, not killed
    stops = [ln for ln in lines if ln.startswith("[stop] ")]
    assert len(stops) == 1 and "WARNING" not in stops[0]
    assert result["stop"] == dict(
        result["stop"], memory_request_answered=1, memory_lines=2,
        memory_peak_bytes=4096, child_rc=0, killed=0, exceptions_ignored=0)
    assert 0 <= result["stop"]["memory_answer_s"] < 5
    assert 0 <= result["stop"]["stop_s"] < 5
    for key, value in result["stop"].items():
        assert f" {key}={value:.4f}" in stops[0] if isinstance(value, float) \
            else f" {key}={value} " in stops[0] + " ", (key, stops[0])


@pytest.mark.parametrize("fault,check", [
    ("second_hit", "warm_2.0_nonce_minus_reference_lowest"),
    ("short_sweep", "expired_jobs_short_of_their_nonce_range"),
    ("bad_nonce", "pushed_blocks_refused_by_reference"),
])
def test_an_answer_altered_where_it_is_produced_is_not_correct(fault,
                                                               check):
    rc, lines = drive(fault=fault)
    assert rc == 0, lines[-5:]
    assert json.loads(lines[-1])["correct"] is False
    failed = [ln for ln in lines if ln.startswith("[check] ")
              and "FAILED" in ln]
    assert any(check in ln for ln in failed), failed


def test_the_control_comes_out_not_correct():
    """The guarantee 'a pushed block meets the served target', broken
    where it is checked: the stub judges two hex chars tighter."""
    rc, lines = drive(control="tighten_target")
    assert rc == 0, lines[-5:]
    assert json.loads(lines[-1])["correct"] is False
    assert any("pushed_blocks_refused_by_reference" in ln and "FAILED" in ln
               for ln in lines)


def test_no_chip_no_result():
    """A child that is not on a TPU: non-zero, and no result line."""
    rc, lines = drive(platform="cpu")
    assert rc != 0
    assert lines[-1].startswith("FAILED:")
    with pytest.raises(ValueError):
        json.loads(lines[-1])


# ---- the chip's peak memory: asked for, then the child is stopped ----

@pytest.mark.parametrize("child_args,rc,told", [
    # never answers and says no line at exit either
    (["--memory", "never"], 1,
     ["memory_request_answered=0 ", "memory_answer_s=1.0", "memory_lines=0 ",
      "child_rc=0 ",
      "killed=0 ", "exceptions_ignored=0;",
      "the child said of its memory: nothing"]),
    (["--memory", "null"], 1,
     ["memory_request_answered=1 ", "memory_lines=2 ",
      "memory_peak_bytes=None ", "['peak_bytes=null', 'peak_bytes=null']"]),
    (["--memory", "unreadable"], 1,
     ["memory_request_answered=1 ", "memory_lines=2 ",
      "['RuntimeError: backend gone', 'RuntimeError: backend gone']"]),
    # the answer written behind an unfinished line of another thread
    (["--memory", "inline"], 0, ["memory_request_answered=1 ",
                                 "memory_lines=2 ", "killed=0 "]),
    # SIGTERM ignored: sound on the early answer, and the kill is said
    (["--ignore-term"], 0, ["WARNING: the child had to be killed: ",
                            "memory_request_answered=1 ", "memory_lines=1 ",
                            "child_rc=-9 ", "stop_s=1.", "killed=1 "]),
])
def test_the_ways_a_reading_of_the_peak_memory_goes_wrong(
        monkeypatch, child_args, rc, told):
    seen = _watch_the_driver(monkeypatch, MEMORY_WAIT_S=1.0, STOP_WAIT_S=1.0)
    got, lines = drive(child_args=child_args)
    assert got == rc, lines[-5:]
    stops = [ln for ln in lines if ln.startswith("[stop] ")]
    assert len(stops) == 1
    if rc:
        # no result line, and the cause in numbers on the last line
        assert not seen
        assert lines[-1].startswith("FAILED: no reading of the chip's peak "
                                    "memory, so no result line: ")
        with pytest.raises(ValueError):
            json.loads(lines[-1])
        assert "its last lines: " in lines[-1]
        where = lines[-1]
    else:
        result = json.loads(lines[-1])
        assert result["correct"] is True
        assert result["device"]["memory_peak_bytes"] == 4096
        where = stops[0]
    for text in told:
        assert text in where, (text, where)
    assert ("WARNING" in stops[0]) == ("--ignore-term" in child_args)


def test_a_rehearsal_on_the_cpu_may_read_null():
    """The CPU keeps no such statistic: there, and only there, a run
    without the number still prints its (never correct) result."""
    from harness.manifest import load_module

    driver = load_module("drivers", "mine_sweep")

    class Miner:
        lines = [(1.0, "memory: peak_bytes=null")]
        killed, stop_s = False, 0.01

        def signal(self, _sig):
            pass

        def wait_for(self, _predicate, _timeout, _what, seen=0):
            return 1.0, "memory: peak_bytes=null"

        def stop(self, timeout):
            return 0

        def tail(self, _n):
            return "memory: peak_bytes=null"

    said = []
    rc, events, stop = driver._stop_child(
        SimpleNamespace(say=said.append, rehearse=True), Miner())
    assert (rc, stop["memory_peak_bytes"], len(said)) == (0, None, 1)
    with pytest.raises(driver.BenchError, match="memory_lines=1 "):
        driver._stop_child(
            SimpleNamespace(say=said.append, rehearse=False), Miner())


# ---- a traced run: how long its window is, and the stop ----

def _watch_the_driver(monkeypatch, **waits):
    """Every run's driver gets ``waits`` for its constants (a test does
    not wait minutes); returns the list of what each run handed back."""
    from harness import manifest

    seen = []
    load = manifest.load_module

    def load_and_watch(kind, name, *rest):
        module = load(kind, name, *rest)
        if kind == "drivers":
            run = module.run

            def run_and_keep(ctx):
                seen.append(run(ctx))
                return seen[-1]

            module.run = run_and_keep
            for key, value in waits.items():
                assert hasattr(module, key)
                setattr(module, key, value)
        return module

    monkeypatch.setattr(manifest, "load_module", load_and_watch)
    return seen


@pytest.fixture
def driven(monkeypatch):
    """What the driver handed back, kept for the test to look at.  There
    is no profiler here: the trace a traced run finds is one window span,
    and the wait for the stop is two seconds."""
    from harness import xplane

    monkeypatch.setattr(xplane, "find_trace", lambda _d: "x.xplane.pb")
    monkeypatch.setattr(xplane, "extract", lambda _p: [
        {"plane": "/host:CPU", "line": "python3",
         "name": "perfbench.window", "start_ns": 0.0, "dur_ns": 1e9}])
    return _watch_the_driver(monkeypatch, STOP_TRACE_WAIT_S=2.0)


@pytest.mark.parametrize("trace,traced_window_s,window_s", [
    (1, 0.5, 0.5),     # traced, and the traffic says shorter: shorter
    (0, 0.5, 1.5),     # the same traffic untraced: --seconds
    (1, 4.0, 1.5),     # never longer than --seconds
    (1, None, 1.5),    # a traffic without the key: --seconds, traced
    (0, None, 1.5),    # or not
])
def test_only_a_traced_run_takes_the_traffics_traced_window(
        driven, trace, traced_window_s, window_s):
    rc, lines = drive(trace=trace, traffic={} if traced_window_s is None
                      else {"traced_window_s": traced_window_s})
    assert rc == 0, lines[-5:]
    observed = driven[0]["observed"]
    w0, w1 = observed["window"]
    assert w1 - w0 == pytest.approx(window_s)
    assert any(ln.startswith(f"[window] {window_s:.1f}s: ") for ln in lines)
    stops = [e["unix"] for e in observed["events"]
             if e["kind"] == "trace" and e["what"] == "stopped"]
    answered = [ln for ln in lines
                if ln.startswith("[trace] stop_trace answered in ")]
    if not trace:
        assert not stops and not answered
        return
    # the stop is signalled where the window closes, and is timed
    assert len(stops) == 1 and 0 <= stops[0] - w1 < 0.5
    assert len(answered) == 1
    assert answered[0].endswith(" s of the 2.0 s the driver waits")
    # and the rounds held against the device are the shorter window's
    assert any(ln.startswith("[check] traced_rounds_claimed_minus_on_device")
               for ln in lines)


def test_a_stop_that_never_answers_names_the_rounds_and_the_key(driven):
    rc, lines = drive(trace=1, traffic={"traced_window_s": 0.5},
                      child_args=["--mute-stop"])
    assert rc == 1 and not driven
    said = re.match(r"FAILED: stop_trace did not answer in the 2.0 s the "
                    r"driver waits: the traced window of 0.5s held (\d+) "
                    r"rounds", lines[-1])
    assert said and int(said.group(1)) > 10, lines[-1]
    assert "'traced_window_s' in benchmarks/traffic/mine-sweep.json" \
        in lines[-1]


@pytest.mark.parametrize("share,warned", [(0.2, False), (0.6, True)])
def test_a_stop_over_half_the_wait_is_said_as_a_warning(share, warned):
    from harness.manifest import load_module

    driver = load_module("drivers", "mine_sweep")
    said = []

    class Miner:
        def signal(self, _sig):
            pass

        def wait_for(self, _predicate, timeout, _what):
            return time.time() + share * timeout, "trace: stopped unix=1.0"

    driver._stop_trace(SimpleNamespace(say=said.append), Miner(), 0.0, 10.0)
    took = share * driver.STOP_TRACE_WAIT_S
    assert len(said) == 1 and re.match(
        rf"\[trace\] {'WARNING: ' if warned else ''}stop_trace answered in "
        rf"{took:.1f} s of the 120 s the driver waits", said[0]), said
    assert ("lower 'traced_window_s'" in said[0]) is warned


# ---- a traced run: the rounds the lines claim against the device's ----

def _traced(claimed_rounds, device_rounds, monkeypatch):
    """The driver's check on a hand-made trace of ``device_rounds``
    rounds of 10 ms and lines that claim ``claimed_rounds`` in it."""
    from types import SimpleNamespace

    from harness import xplane
    from harness.manifest import load_module

    driver = load_module("drivers", "mine_sweep")
    recs = [{"plane": "/host:CPU", "line": "python3",
             "name": "perfbench.window", "start_ns": 0.0, "dur_ns": 10e9}]
    recs += [{"plane": "/device:TPU:0", "line": "XLA Modules",
              "name": "jit__pow_search_pallas(1)",
              "start_ns": 1e9 + i * 10e6, "dur_ns": 9e6}
             for i in range(device_rounds)]
    monkeypatch.setattr(xplane, "find_trace", lambda _d: "x.xplane.pb")
    monkeypatch.setattr(xplane, "extract", lambda _p: recs)
    events = [{"kind": "trace", "what": "started", "unix": 1000.0},
              {"kind": "trace", "what": "stopped", "unix": 1010.0}]
    jobs = [{"rounds": [(1001.0 + i * 0.01, 4096)
                        for i in range(claimed_rounds)]}]
    said = []
    ctx = SimpleNamespace(traffic={"search_program": "pow_search",
                                   "round_nonces": 4096})
    driver._check_traced_rounds(
        ctx, lambda name, value, limit, ok, note="": said.append(
            (name, value, ok)), events, jobs, "trace-dir")
    return said


def test_rounds_claimed_and_rounds_on_the_device_agree(monkeypatch):
    assert _traced(640, 640, monkeypatch) == \
        [("traced_rounds_claimed_minus_on_device", 0, True)]
    assert _traced(641, 640, monkeypatch)[0][2] is True   # an edge round


def test_rounds_claimed_and_never_run_are_not_correct(monkeypatch):
    # one round in sixteen claimed and not sent (launch/faults.py)
    assert _traced(640, 600, monkeypatch) == \
        [("traced_rounds_claimed_minus_on_device", 40, False)]
    # what the control read on four chips in a 10 s window (PERF.md)
    assert _traced(136, 129, monkeypatch)[0] == \
        ("traced_rounds_claimed_minus_on_device", 7, False)
    # and a trace in which the program never ran
    assert _traced(0, 0, monkeypatch)[0][2] is False
