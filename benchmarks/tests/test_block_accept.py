"""The whole of a block-accept run, driven past the look for a chip: a
stand-in for the node child (``fake_node.py``) says it is on a TPU, and
the driver, the fixture, the reference and ``correct`` do the rest.
Sound, the run is correct; with the timed path broken underneath, or a
control switched on, ``correct`` comes out false; a child that dies is a
failed run with no result line."""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

import run as bench_run

FAKE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "fake_node.py")
TINY = {"fan_out": 4, "per_output": 4, "min_block_txs": 16,
        "valid_blocks": 5, "forge_within": 3, "start_timeout_s": 30,
        "first_dispatch_timeout_s": 30, "push_timeout_s": 30}


def drive(fault="-", control=None, seed=7, seconds="3", trace=0,
          traffic=None, child_args=()):
    argv = [sys.executable, FAKE, "--port", "{port}", "--db", "{db}",
            "--fault", fault if fault != "-" else "{fault}",
            "--sig-backend", "{sig_backend}", *child_args]
    args = ["--workload", "accept-2mb-cold", "--seed", str(seed),
            "--seconds", seconds, "--trace", str(trace)]
    if control:
        args += ["--control", control]
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(args, faults={
            "child_argv": argv, "traffic": dict(TINY, **(traffic or {}))})
    return rc, out.getvalue().strip().splitlines()


def _failed(lines):
    return [ln.split()[1].rstrip(":") for ln in lines
            if ln.startswith("[check] ") and "FAILED" in ln]


def test_a_sound_run_is_correct_and_spends_the_fixture(capsys):
    rc, lines = drive()
    assert rc == 0, lines[-5:]
    result = json.loads(lines[-1])
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "stop", "checks"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 7          # 5 valid and 2 forged
    assert set(result["metrics"]) == {"accept_tx_per_s", "accept_s_p50",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                                "count": 1, "memory_peak_bytes": 4096}
    by_name = {c["name"]: c for c in result["checks"]}
    assert by_name["fixture_exhausted"]["value"] == 1
    assert by_name["valid_blocks_acknowledged"]["value"] == 5
    assert by_name["durable_height"]["value"] == 9
    assert by_name["p256_lanes_real_in_window"]["value"] >= 5 * 16
    # both forged pushes refused, the tip read back after each
    refused = [ln for ln in lines if ln.startswith("[push ")
               and "ok=False" in ln]
    assert len(refused) == 2 and "forged_sig" in "".join(refused) \
        and "forged_spend" in "".join(refused)
    # each number compared beside its limit, again last on stderr
    said = capsys.readouterr().err.strip().splitlines()
    assert [ln.split()[1].rstrip(":") for ln in said[-len(by_name):]] == \
        list(by_name)
    assert any(ln.startswith("[fixture] ") and "OpenSSL" in ln
               for ln in lines)
    assert any(ln.startswith("[reference] ") for ln in lines)


def test_a_window_that_closes_first_leaves_blocks_unspent():
    rc, lines = drive(seconds="0.001")
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is True
    assert result["attempted"] == 1
    by_name = {c["name"]: c for c in result["checks"]}
    assert by_name["fixture_exhausted"]["value"] == 0
    assert by_name["durable_height"]["value"] == 5


@pytest.mark.parametrize("control,check", [
    ("host_verify", "p256_lanes_real_in_window"),
    ("unverified", "verdicts_differing_from_reference"),
    ("forged_unmarked", "verdicts_differing_from_reference"),
])
def test_each_control_comes_out_not_correct(control, check):
    rc, lines = drive(control=control)
    assert rc == 0, lines[-5:]
    assert json.loads(lines[-1])["correct"] is False
    assert check in _failed(lines), _failed(lines)


@pytest.mark.parametrize("fault,child_args,check", [
    ("host_fell", (), "resilience_device_fallback"),
    ("forget_last", ("--forget-at", "9"), "durable_height"),
    ("-", ("--armed", "cpu"), "device_platform"),
])
def test_the_timed_path_broken_underneath_is_not_correct(fault, child_args,
                                                         check):
    rc, lines = drive(fault=fault, child_args=child_args)
    assert rc == 0, lines[-5:]
    assert json.loads(lines[-1])["correct"] is False
    assert check in _failed(lines), _failed(lines)


@pytest.mark.parametrize("fault,child_args", [
    ("die", ()), ("-", ("--platform", "cpu")), ("-", ("--memory", "null")),
])
def test_a_child_that_dies_or_has_no_chip_is_a_failed_run(fault, child_args):
    rc, lines = drive(fault=fault, child_args=child_args)
    assert rc == 1
    assert lines[-1].startswith("FAILED: ")
    assert not any(ln.startswith("{") for ln in lines)


def test_the_driver_names_its_controls_and_the_hook_has_the_fault():
    from harness import manifest

    driver = manifest.load_module("drivers", "block_accept")
    assert set(driver.CONTROLS) == {"host_verify", "unverified",
                                    "forged_unmarked"}
    sys.path.insert(0, os.path.join(manifest.BENCH, "launch"))
    try:
        import node_faults
    finally:
        sys.path.pop(0)
    assert driver.CONTROLS["unverified"]["child_fault"] in node_faults.FAULTS
    with pytest.raises(SystemExit):
        node_faults.apply("no_such_fault")
