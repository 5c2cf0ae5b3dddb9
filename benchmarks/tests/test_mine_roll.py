"""The cell ``mine-roll-4chip``: its files are found by name; the plain
reference ``harness/rollref.py`` knows a repeated header and the two
edges of the node's rule; the stub serves a tip with a timestamp of its
own and refuses a block stamped outside the rule; and the driver's three
checks read a sound miner as correct and each way of going wrong as not
(``fake_roll_miner.py`` stands in for the child, as ``fake_miner.py``
does for ``mine_sweep``)."""

import io
import json
import os
import sys
import time
from contextlib import redirect_stdout

import pytest

import run as bench_run
from harness import manifest, powref, rollref

CELL = "mine-roll-4chip"
FAKE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "fake_roll_miner.py")
#: jobs of 16 rounds of 2 ms: some thirty a second, so the second job
#: of the window already has to roll
TINY = {"warm_difficulties": [2.0, 2.5], "after_difficulties": [],
        "difficulty": 11.0, "round_nonces": 4096, "tip_age_s": 60,
        "arm_timeout_s": 30, "warm_timeout_s": 60, "miner_args": []}


def _identity(seed):
    """(address, its bytes): the driver's own, from the seed."""
    return manifest.load_module("drivers", "mine_sweep")._miner_identity(seed)


def drive(stamp="roll", seed=7, traffic=None, extra=()):
    argv = [sys.executable, FAKE, _identity(seed)[1].hex(), "--node",
            "{node}", "--batch", "4096", "--range", "65536",
            "--stamp", stamp]
    faults = {"child_argv": argv}
    if traffic is not None:
        faults["traffic"] = dict(TINY, **traffic)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(["--workload", CELL, "--seed", str(seed),
                             "--seconds", "1.5", "--trace", "0",
                             *extra], faults=faults)
    lines = out.getvalue().strip().splitlines()
    assert rc == 0, lines[-5:]
    result = json.loads(lines[-1])
    return result, {c["name"]: c for c in result["checks"]}, lines


# ---- the files ----

def test_the_cell_finds_its_files():
    mf = manifest.load_manifest()
    cell = manifest.find_cell(mf, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("miner-host", "mine-roll-pod", 4)
    config = manifest.load_config(mf, cell)
    pod = manifest.load_config(mf, manifest.find_cell(mf, "mine-sweep-4chip"))
    assert set(config["children"]) == set(config["rehearse_children"]) \
        == {"4"}
    assert config["children"]["4"] == pod["children"]["4"]
    assert config["architecture"] is None
    assert set(config["reduced"]) == {"pod_chips", "node",
                                      "competing_miners"}
    assert len(config["guarantees"]) == 5
    assert {"tip_age_s", "sentinel"} <= set(config["assumed"])
    entry = next(c for c in mf["configs"] if c["name"] == "miner-host")
    assert entry["reduced"] == list(config["reduced"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    # the traffic is the pod's in all but the driver, the range, the tip
    traffic = manifest.load_traffic(cell["traffic"])
    base = manifest.load_traffic("mine-sweep-pod")
    assert (traffic["driver"], traffic["miner_args"], traffic["tip_age_s"],
            traffic["traced_window_s"]) == ("mine_roll", [], 60, 10)
    own = {"what", "driver", "miner_args", "why_shard", "tip_age_s",
           "why_tip_age", "rehearse"}
    assert {k: v for k, v in traffic.items() if k not in own} == \
        {k: v for k, v in base.items() if k not in own}
    # the pod's thirteen metrics, the whole job's seconds and the new one
    layer = {e["name"]: spec
             for e, spec in manifest.layer_metrics_for(mf, CELL)}
    pods = {e["name"] for e, _s in
            manifest.layer_metrics_for(mf, "mine-sweep-4chip")}
    assert len(pods) == 13
    assert set(layer) == pods | {"job_sweep_s.mine", "build_job_ms.mine"}
    new = layer["build_job_ms.mine"]
    assert (new["reader"], new["span"], new["stat"], new["scale"]) == \
        ("span_stat", "mine.build_job", "mean", 1e-6)
    assert [m["name"] for m in manifest.end_to_end_for(mf, CELL)] == \
        ["search_mhs", "setup_s"]
    driver = manifest.load_module("drivers", "mine_roll")
    assert set(driver.CONTROLS) == {"tighten_target", "skip_rounds",
                                    "mute_memory", "fresh_tip"}
    assert driver.CONTROLS["fresh_tip"] == {"traffic": {"tip_age_s": 0}}


# ---- the reference ----

@pytest.mark.parametrize("ts,ok", [(100, False), (101, True), (160, True),
                                   (161, False)])
def test_the_nodes_rule_has_two_edges(ts, ok):
    assert rollref.valid(100, ts, 160) is ok


def test_the_newest_fresh_second():
    assert rollref.newest_fresh(100, 103, set()) == 103
    assert rollref.newest_fresh(100, 103, {103, 102}) == 101
    assert rollref.newest_fresh(100, 103, {101, 102, 103}) is None
    assert rollref.newest_fresh(100, 100, set()) is None


def test_repeats_are_found_by_all_six_fields():
    job = {"previous_hash": "aa", "merkle_root": "bb", "address": "cc",
           "difficulty": 11.0, "timestamp": 5, "range": (0, 9)}
    others = [dict(job, **{key: value}) for key, value in [
        ("previous_hash", "ab"), ("merkle_root", "bc"), ("address", "cd"),
        ("difficulty", 11.1), ("timestamp", 6), ("range", [0, 8])]]
    assert rollref.repeats([job] + others) == []
    assert rollref.repeats([job] + others + [dict(job, range=[0, 9]),
                                             others[4], job]) == [7, 8, 9]


def test_the_pushed_timestamp_is_read_back_by_the_reference():
    content = (bytes([2]) + bytes(97) + (1_790_000_123).to_bytes(4, "little")
               + bytes(6)).hex()
    assert rollref.pushed_timestamp(content) == 1_790_000_123 \
        == powref.parse_header(content)["timestamp"]
    with pytest.raises(ValueError):
        rollref.pushed_timestamp("00")


# ---- the stub ----

@pytest.fixture
def stub():
    driver = manifest.load_module("drivers", "mine_roll")
    address, address_bytes = _identity(3)
    return driver.AgedTipStub(3, address, address_bytes,
                              dict(TINY, tip_interval_s=60, pending_txs=2))


def _block(stub, info, ts):
    """A block that meets the served job's target, stamped ``ts``."""
    tip, diff = info["last_block"]["hash"], info["difficulty"]
    prefix = (bytes([2]) + bytes.fromhex(tip) + stub.address_bytes
              + bytes.fromhex(powref.miner_merkle(
                  info["pending_transactions_hashes"]))
              + ts.to_bytes(4, "little")
              + int(diff * 10).to_bytes(2, "little"))
    hit = powref.lowest_hit(prefix, 0, 1 << 16, tip, diff, workers=1)
    return {"block_content": (prefix + hit.to_bytes(4, "little")).hex(),
            "txs": info["pending_transactions_hashes"],
            "block_no": info["last_block"]["id"] + 1}


def test_the_stubs_tip_keeps_the_timestamp_it_was_first_served_with(stub):
    t0 = int(time.time())
    first = stub.mining_info()["result"]["last_block"]
    assert t0 - 60 <= first["timestamp"] <= int(time.time()) - 60
    time.sleep(1.1)
    again = stub.mining_info()["result"]["last_block"]
    assert again == first                   # constant for the tip
    # the base class would have served int(now) - 1
    assert again["timestamp"] < int(time.time()) - 60


@pytest.mark.parametrize("offset,refused", [
    (0, False),           # now
    (-59, False),         # the oldest second the rule allows
    (-60, True),          # the tip's own timestamp: too old
    (+5, True),           # the future
])
def test_the_stub_refuses_a_block_stamped_outside_the_rule(stub, offset,
                                                           refused):
    info = stub.mining_info()["result"]
    prev_ts = info["last_block"]["timestamp"]
    reply = stub.push_block(_block(stub, info, prev_ts + 60 + offset))
    assert reply.get("ok") is (not refused), reply
    assert bool(stub.pushes[-1]["faults"]) is refused
    if refused:
        assert "outside the node's rule" in reply["error"]
    # a warm job is moved on whatever was found, as in the base class
    assert stub.warm_index == 1
    assert stub.mining_info()["result"]["last_block"]["hash"] != \
        info["last_block"]["hash"]


# ---- the driver's checks ----

ROLL_CHECKS = ("headers_repeated_in_window",
               "job_timestamps_outside_the_nodes_rule",
               "pushed_timestamp_differs_from_job_line")


def test_a_miner_that_rolls_is_correct():
    result, checks, lines = drive(traffic={})
    assert result["correct"] is True, [ln for ln in lines if "FAILED" in ln]
    assert [checks[name]["value"] for name in ROLL_CHECKS] == [0, 0, 0]
    # mine_sweep's checks come first and are all there
    assert list(checks)[-3:] == list(ROLL_CHECKS) and len(checks) >= 10
    assert result["attempted"] >= 10
    said = next(ln for ln in lines if ln.startswith("[roll] "))
    assert "'repeated': 0" in said and "0 jobs said none" in said
    assert "'rolled': 0" not in said
    assert set(result["metrics"]) == {"search_mhs", "setup_s"}


@pytest.mark.parametrize("stamp,failed", [
    ("clock", {"headers_repeated_in_window"}),
    ("silent", {"headers_repeated_in_window",
                "pushed_timestamp_differs_from_job_line"}),
    ("future", {"job_timestamps_outside_the_nodes_rule",
                "pushed_blocks_refused_by_reference"}),
    ("lie", {"pushed_timestamp_differs_from_job_line"}),
])
def test_a_miner_that_goes_wrong_is_not_correct(stamp, failed):
    result, checks, lines = drive(stamp=stamp, traffic={})
    assert result["correct"] is False
    got = {name for name, c in checks.items() if not c["ok"]}
    # a block stamped in the future is refused, so the warm jobs have no
    # valid block either; the other three go wrong in their own check only
    assert got >= failed if stamp == "future" else got == failed, \
        [ln for ln in lines if "FAILED" in ln]
    if stamp == "silent":
        assert any("jobs without a header: line taken as stamped" in ln
                   for ln in lines)


def test_the_control_fresh_tip_makes_a_sound_miner_repeat():
    """``--control fresh_tip`` through ``--rehearse-cpu`` (the control
    takes the place of a test's traffic, so the sizes are the traffic
    file's rehearsal): no room is left, and the miner that rolls says
    so and is counted."""
    sound, checks, _lines = drive(extra=["--rehearse-cpu"])
    assert checks["headers_repeated_in_window"]["ok"]
    result, checks, lines = drive(extra=["--rehearse-cpu", "--control",
                                         "fresh_tip"])
    assert result["correct"] is False and sound["correct"] is False
    assert not checks["headers_repeated_in_window"]["ok"]
    assert checks["headers_repeated_in_window"]["value"] >= 5
    said = next(ln for ln in lines if ln.startswith("[roll] "))
    assert "'repeated': 0" not in said
