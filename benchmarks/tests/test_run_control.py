"""The whole of a run, driven past the look for a chip: a stand-in for
the miner child (``fake_miner.py``) says it is on a TPU, and the driver,
the stub node, the reference and ``correct`` do the rest.  Sound, the
run is correct; with one answer altered where it is produced, or the
configuration's guarantee broken where it is checked (the control),
``correct`` comes out false."""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

import run as bench_run

FAKE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "fake_miner.py")
TINY = {"warm_difficulties": [2.0], "after_difficulties": [2.5],
        "difficulty": 11.0, "round_nonces": 4096,
        "arm_timeout_s": 30, "warm_timeout_s": 60, "miner_args": []}


def drive(fault="", control=None, seed=7, platform="tpu"):
    from upow_tpu.core import curve
    from upow_tpu.core.codecs import point_to_string, string_to_bytes

    _d, pub = curve.keygen(rng=0x5EED0000 + seed)
    address_hex = string_to_bytes(point_to_string(pub)).hex()
    argv = [sys.executable, FAKE, address_hex, "--node", "{node}",
            "--batch", "4096", "--range", "65536", "--platform", platform]
    if fault:
        argv += ["--fault", fault]
    args = ["--workload", "mine-sweep-1chip", "--seed", str(seed),
            "--seconds", "1.5", "--trace", "0"]
    if control:
        args += ["--control", control]
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(args, faults={"child_argv": argv,
                                          "traffic": TINY})
    lines = out.getvalue().strip().splitlines()
    return rc, lines


def test_a_sound_run_is_correct_and_prints_the_contracts_line():
    rc, lines = drive()
    assert rc == 0, lines[-5:]
    result = json.loads(lines[-1])
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    assert set(result["metrics"]) == {"search_mhs", "setup_s"}
    assert result["metrics"]["search_mhs"]["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    # each number compared is printed beside its limit
    checks = [ln for ln in lines if ln.startswith("[check] ")]
    assert len(checks) >= 7 and all("(limit " in ln for ln in checks)
    # a block mined before and one after the window, each under the reference
    assert sum("_nonce_minus_reference_lowest: 0 (limit 0) ok" in ln
               for ln in checks) == 2


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
