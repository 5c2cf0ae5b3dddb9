"""The cell ``mine-restart-1chip``: its files are found by name; the
plain reference ``harness/outageref.py`` reads hand-made logs; the stub
goes away and comes back as its schedule says and logs what it did; the
driver's four checks read a sound miner as correct and each way of going
wrong as not (``fake_outage_miner.py`` stands in for the child); and the
rehearsal, with the miner itself, reaches its result line."""

import io
import json
import os
import sys
import time
import urllib.error
from contextlib import redirect_stdout

import pytest

import run as bench_run
from fake_miner import http
from harness import manifest, outageref

CELL = "mine-restart-1chip"
FAKE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "fake_outage_miner.py")
#: jobs of 16 rounds of 4 ms; the node is away for a second, then holds
#: requests for 0.3 s; the 4.0 job after the window (2^16 hashes to a hit,
#: some 0.1 s of hashlib: the socket is closed by then) meets a dead node
TINY = {"warm_difficulties": [2.0], "after_difficulties": [4.0],
        "difficulty": 11.0, "round_nonces": 4096, "tip_age_s": 200,
        "tip_interval_s": 60, "arm_timeout_s": 30, "warm_timeout_s": 60,
        "miner_args": [], "ttl_s": 90, "push_outage_s": 1.5,
        "schedule": {"down": [0.3, 1.1], "syncing": [1.1, 1.3],
                     "stall": [1.7, 2.0]}}
OUTAGE_CHECKS = ("templates_never_served_or_past_ttl",
                 "found_blocks_not_delivered",
                 "first_fresh_job_after_return_s",
                 "errors_outside_the_schedule")


def _identity(seed):
    return manifest.load_module("drivers", "mine_sweep")._miner_identity(seed)


def drive(fault="", seed=7, traffic=None, ttl=90.0, extra=()):
    argv = [sys.executable, FAKE, _identity(seed)[1].hex(), "--node",
            "{node}", "--batch", "4096", "--range", "65536",
            "--ttl", str(ttl), "--fault", fault]
    faults = {"child_argv": argv}
    if traffic is not None:
        faults["traffic"] = dict(TINY, **traffic)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(["--workload", CELL, "--seed", str(seed),
                             "--seconds", "3", "--trace", "0", *extra],
                            faults=faults)
    lines = out.getvalue().strip().splitlines()
    assert rc == 0, lines[-5:]
    result = json.loads(lines[-1])
    return result, {c["name"]: c for c in result["checks"]}, lines


# ---- the files ----

def test_the_cell_finds_its_files():
    mf = manifest.load_manifest()
    cell = manifest.find_cell(mf, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("miner-restart", "mine-sweep-restart", 1)
    assert len(cell["why"]) <= 200
    config = manifest.load_config(mf, cell)
    solo = manifest.load_config(
        mf, manifest.find_cell(mf, "mine-sweep-1chip-full"))
    assert config["children"] == solo["children"]
    assert config["rehearse_children"] == solo["rehearse_children"]
    assert config["architecture"] is None
    assert set(config["reduced"]) == {"node", "competing_miners"}
    assert len(config["guarantees"]) == 8
    assert {"schedule", "difficulty", "warm_difficulties"} \
        <= set(config["assumed"])
    entry = next(c for c in mf["configs"] if c["name"] == "miner-restart")
    assert entry["reduced"] == list(config["reduced"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert mf["configs"][-1] is entry and mf["workloads"][-1] is cell
    # the traffic is mine-sweep-full's in all but the driver, the tip's
    # age and the schedule
    traffic = manifest.load_traffic(cell["traffic"])
    base = manifest.load_traffic("mine-sweep-full")
    assert (traffic["driver"], traffic["miner_args"], traffic["tip_age_s"],
            traffic["push_outage_s"], traffic["ttl_s"]) == \
        ("mine_outage", [], 60, 15, 90)
    assert traffic["schedule"] == {"down": [6, 16], "syncing": [16, 20],
                                   "stall": [28, 34]}
    assert "traced_window_s" not in traffic
    own = {"what", "driver", "miner_args", "tip_age_s", "why_tip_age",
           "schedule", "push_outage_s", "why_schedule", "ttl_s", "why_ttl",
           "rehearse"}
    assert {k: v for k, v in traffic.items() if k not in own} == \
        {k: v for k, v in base.items() if k not in own}
    small = traffic["rehearse"]
    assert small["schedule"] == {k: [a / 5, b / 5] for k, (a, b)
                                 in traffic["schedule"].items()}
    away = small["schedule"]["syncing"][1] - small["schedule"]["down"][0]
    assert small["push_outage_s"] < small["ttl_s"] < away   # (6) bites
    # the full-range cell's fifteen metrics and the two new ones
    layer = {e["name"]: spec
             for e, spec in manifest.layer_metrics_for(mf, CELL)}
    full = {e["name"] for e, _s in
            manifest.layer_metrics_for(mf, "mine-sweep-1chip-full")}
    new = {"idle_take_template_share.mine", "take_template_max_s.mine"}
    assert len(full) == 15 and set(layer) == full | new
    for entry in mf["per_layer"][-2:]:
        assert entry["name"] in new and entry["workloads"] == [CELL]
        assert entry["moves"] == "search_mhs" and entry["better"] == "lower"
    share, longest = (layer[n] for n in sorted(new))
    assert (share["reader"], share["spans"]) == \
        ("idle_by_span", ["mine.take_template"])
    assert (longest["reader"], longest["span"], longest["stat"],
            longest["scale"]) == ("span_stat", "mine.take_template", "max",
                                  1e-9)
    assert [m["name"] for m in manifest.end_to_end_for(mf, CELL)] == \
        ["search_mhs", "setup_s"]
    driver = manifest.load_module("drivers", "mine_outage")
    assert driver.CONTROLS == manifest.load_module(
        "drivers", "mine_sweep").CONTROLS


# ---- the reference, against hand-made logs ----

def _state(what, t):
    return {"kind": "state", "what": what, "t": t}


def _request(what, t, answer, answered_t=-1, held=False, **fields):
    return dict(fields, kind="request", what=what, t=t, answer=answer,
                answered_t=t if answered_t == -1 else answered_t, held=held)


def _template(t, block=3, difficulty=11.0, **kw):
    return _request("get_mining_info", t, "ok", block=block,
                    difficulty=difficulty, **kw)


LOG = [_template(100.0), _state("down", 106.0),
       _state("syncing", 116.0),
       _request("get_mining_info", 116.2, "syncing"),
       _state("up", 120.0), _template(120.3),
       _state("stall", 128.0),
       _template(129.0, answered_t=134.0, held=True),
       _state("up", 134.0), _template(140.0, block=4)]


def test_the_log_reads_as_states_and_returns():
    assert outageref.states(LOG, "down") == [(106.0, 116.0)]
    assert outageref.states(LOG, "up") == [(120.0, 128.0), (134.0, None)]
    assert outageref.returns(LOG) == [120.0, 134.0]
    assert [e["t"] for e in outageref.served(LOG)] == \
        [100.0, 120.3, 129.0, 140.0]


def test_a_job_needs_a_template_the_node_served_young_enough():
    def job(t, block=3, difficulty=11.0):
        return {"start_t": t, "block": block, "difficulty": difficulty}

    check = outageref.templates_never_served_or_past_ttl
    assert check(LOG, [job(100.1), job(119.0), job(121.0)], 90) == []
    # the same template 19 s on, held to a ttl of 10 s; one whose age is
    # inside the slack of a line; a block and a difficulty never served;
    # a template served only after the job began
    assert check(LOG, [job(119.0)], 10) == [(0, "19.00s old, ttl 10s")]
    assert check(LOG, [job(110.2)], 10) == []
    assert check(LOG, [job(110.3)], 10) == [(0, "10.30s old, ttl 10s")]
    assert check(LOG, [job(121.0, block=5), job(121.0, difficulty=8.3),
                       job(99.0), job(139.0, block=4)], 90) == \
        [(i, "never served") for i in range(4)]
    # a held request serves its template when it is answered
    assert check(LOG, [job(135.0)], 2) == [(0, None)][:0]
    assert check(LOG, [job(133.9)], 2)[0][1].startswith("13.60s old")


def _header(nonce, timestamp):
    return (bytes([2]) + bytes(97) + timestamp.to_bytes(4, "little")
            + bytes(2) + nonce.to_bytes(4, "little")).hex()


def test_a_found_block_needs_an_answered_push_of_its_header():
    log = [_request("push_block", 50.0, "ok", content=_header(7, 1000)),
           _request("push_block", 51.0, "syncing",
                    content=_header(8, 1001)),
           _request("push_block", 52.0, None, answered_t=None,
                    content=_header(9, 1002)),
           _request("push_block", 53.0, "refused", content="00")]
    found = [{"t": 49.0, "nonce": 7, "timestamp": 1000},
             {"t": 50.5, "nonce": 8, "timestamp": 1001},   # a verdict too
             {"t": 51.5, "nonce": 9, "timestamp": 1002},   # never answered
             {"t": 52.5, "nonce": 7, "timestamp": 1003},   # other bytes
             {"t": 60.0, "nonce": 10, "timestamp": 1004}]  # never pushed
    assert outageref.found_blocks_not_delivered(log, found) == found[2:]


def test_the_return_is_timed_to_the_first_job_on_a_fresh_template():
    waits = outageref.first_fresh_job_after_return_s
    # 120 -> served 120.3 -> a job at 122; 134 -> the held answer at 134
    # -> a job at 134.5
    assert waits(LOG, [100.1, 119.9, 120.1, 122.0, 134.5], 150.0) == \
        pytest.approx([2.0, 0.5])
    # no job after the second return: at least the seconds that were seen
    assert waits(LOG, [100.1, 122.0], 150.0) == pytest.approx([2.0, 16.0])
    # a node that never served again
    assert waits(LOG[:5], [100.1, 122.0], 150.0) == pytest.approx([30.0])


def test_an_error_line_needs_a_cause_in_the_log():
    def line(t, text="node unreachable: refused; retrying"):
        return {"t": t, "text": text}

    odd = outageref.errors_outside_the_schedule
    # refused while down (and a line that trails the state's end); one
    # line for the one enveloped request
    assert odd(LOG, [line(106.0), line(111.0), line(116.1),
                     line(116.25)]) == []
    # a second line for it, a line before the outage, one long after, a
    # push's line with no push in the log, and words no request explains
    again, early, late = line(116.4), line(105.9), line(125.0)
    push = line(116.3, "push_block failed: refused; retrying")
    crash = line(110.0, "Traceback (most recent call last):")
    assert odd(LOG, [line(116.3), again, early, late, push, crash]) == \
        [again, early, late, push, crash]
    # a held request explains a line (the miner's timeout) until it is
    # answered, and a push's line while the node is down is explained
    assert odd(LOG, [line(131.0)]) == [] == odd(LOG, [line(134.2)])
    assert odd(LOG, [line(131.0), line(131.5)]) == [line(131.5)]
    assert odd(LOG, [line(134.3)]) == [line(134.3)]
    assert odd(LOG, [line(110.0, "push_block failed: refused")]) == []


def test_a_block_answered_with_the_envelope_is_paired_with_no_verdict():
    driver = manifest.load_module("drivers", "mine_outage")
    log = [_request("push_block", 50.0, "syncing", content=_header(8, 1001)),
           _request("push_block", 60.0, "ok", content=_header(9, 1002)),
           _request("get_mining_info", 61.0, "syncing")]
    jobs = [{"end": "found", "nonce": 8}, {"end": "expired"},
            {"end": "found", "nonce": 9}, {"end": "found", "nonce": 8}]
    stamps = [{"timestamp": 1001}, {"timestamp": 1001}, {"timestamp": 1002},
              None]
    assert driver.judged_jobs(log, jobs, stamps) == \
        list(zip(jobs, stamps))[1:]
    assert driver.judged_jobs(log[1:], jobs, stamps) == \
        list(zip(jobs, stamps))


# ---- the stub ----

def test_the_stub_goes_away_and_comes_back_on_its_port():
    driver = manifest.load_module("drivers", "mine_outage")
    address, address_bytes = _identity(3)
    stub = driver.RestartingStub(3, address, address_bytes, dict(
        TINY, warm_difficulties=[], after_difficulties=[], pending_txs=2,
        schedule={"down": [0.2, 0.5], "syncing": [0.5, 0.7],
                  "stall": [0.9, 1.2]}))
    url = stub.start()
    try:
        t0 = time.time()
        first = http(url + "get_mining_info")["result"]
        seen = []
        while time.time() - t0 < 1.6:
            t = time.time()
            try:
                reply = http(url + "get_mining_info")
                seen.append((t - stub.window_start, time.time() - t,
                             "ok" if reply.get("ok") else reply["error"]))
            except urllib.error.URLError as e:
                seen.append((t - stub.window_start, 0.0,
                             type(e.reason).__name__))
            time.sleep(0.02)
        assert http(url + "push_block", {"block_content": "00"})["ok"] \
            is False
    finally:
        stub.stop()

    def during(a, b):
        return {what for t, _took, what in seen if a + 0.05 <= t < b - 0.05}

    assert during(0.0, 0.2) == {"ok"} == during(0.7, 0.9) == during(1.25, 2)
    assert during(0.2, 0.5) == {"ConnectionRefusedError"}
    assert during(0.5, 0.7) == {"node is syncing"}
    # one request held from inside the stall to its end
    held = [(t, took) for t, took, _w in seen if 0.9 <= t < 1.2]
    assert len(held) == 1 and held[0][0] + held[0][1] == \
        pytest.approx(1.2, abs=0.05)
    marks = [(e["what"], round(e["t"] - stub.window_start, 1))
             for e in stub.log if e["kind"] == "state"]
    assert marks == [("down", 0.2), ("syncing", 0.5), ("up", 0.7),
                     ("stall", 0.9), ("up", 1.2)]
    asked = [e for e in stub.log if e["kind"] == "request"]
    assert {e["answer"] for e in asked} == {"ok", "syncing", "refused"}
    assert [e["held"] for e in asked].count(True) == 1
    assert all(e["answered_t"] >= e["t"] for e in asked)
    ok = next(e for e in asked if e["answer"] == "ok")
    assert (ok["block"], ok["difficulty"]) == \
        (first["last_block"]["id"] + 1, 11.0)
    assert asked[-1]["what"] == "push_block" and \
        asked[-1]["content"] == "00"


# ---- the driver's checks ----

def test_a_miner_that_holds_its_template_is_correct():
    result, checks, lines = drive(traffic={})
    assert result["correct"] is True, [ln for ln in lines if "FAILED" in ln]
    assert result["failed"] == 0
    assert list(checks)[-4:] == list(OUTAGE_CHECKS)
    assert "jobs_failed_in_window" not in checks
    assert not [ln for ln in lines if "jobs_failed_in_window" in ln]
    assert [checks[n]["value"] for n in OUTAGE_CHECKS
            if n != "first_fresh_job_after_return_s"] == [0, 0, 0]
    said = [ln for ln in lines if ln.startswith("[outage] ")]
    assert "('down', 0.3), ('syncing', 1.1), ('up', 1.3), ('stall', 1.7)" \
        in said[0]
    held = int(said[1].split()[1])
    assert held >= 5
    # the block after the window met a dead node and arrived all the same
    assert "2 'found nonce' lines" in \
        next(ln for ln in lines if "found_blocks_not_delivered" in ln)
    errors = next(ln for ln in lines
                  if ln.startswith("[check] errors_outside_the_schedule"))
    assert int(errors.split(" - ")[1].split()[0]) >= held
    assert set(result["metrics"]) == {"search_mhs", "setup_s"}


@pytest.mark.parametrize("fault,ttl,traffic,failed", [
    ("idle_late", 90, {}, {"first_fresh_job_after_return_s"}),
    # a block that was never pushed also leaves the pushes and the jobs
    # that ended 'found' one apart, which mine_roll's pairing counts
    ("drop_block", 90, {}, {"found_blocks_not_delivered",
                            "pushed_timestamp_differs_from_job_line"}),
    ("stale_template", 0.3, {"ttl_s": 0.3},
     {"templates_never_served_or_past_ttl"}),
    ("odd_error", 90, {}, {"errors_outside_the_schedule"}),
])
def test_a_miner_that_goes_wrong_is_not_correct(fault, ttl, traffic, failed):
    result, checks, lines = drive(fault=fault, ttl=ttl, traffic=traffic)
    assert result["correct"] is False
    assert {n for n, c in checks.items() if not c["ok"]} == failed, \
        [ln for ln in lines if "FAILED" in ln]
    assert result["failed"] == (1 if fault == "odd_error" else 0)


def test_a_miner_that_keeps_to_a_short_ttl_waits_and_is_correct():
    """The stale_template case's sizes with a sound miner: it stops
    holding at 0.3 s and waits for the node."""
    result, checks, lines = drive(ttl=0.3, traffic={"ttl_s": 0.3})
    assert result["correct"] is True, [ln for ln in lines if "FAILED" in ln]


@pytest.mark.parametrize("stamp", ["roll", "silent"])
def test_a_miner_without_the_feed_is_not_run(stamp):
    """The miner before it had a template feed (``fake_roll_miner.py``:
    a ``header:`` line without ``held=`` and ``age=``, or none) cannot
    give this configuration's guarantees: the run ends non-zero at its
    first job, with no result line."""
    roll_fake = os.path.join(os.path.dirname(FAKE), "fake_roll_miner.py")
    argv = [sys.executable, roll_fake, _identity(7)[1].hex(), "--node",
            "{node}", "--batch", "4096", "--range", "65536",
            "--stamp", stamp]
    out = io.StringIO()
    t0 = time.time()
    with redirect_stdout(out):
        rc = bench_run.main(["--workload", CELL, "--seed", "7",
                             "--seconds", "3", "--trace", "0"],
                            faults={"child_argv": argv, "traffic": TINY})
    lines = out.getvalue().strip().splitlines()
    assert rc == 1 and time.time() - t0 < 3
    assert lines[-1].startswith("FAILED: the miner's first job says no "
                                "held= and age=")
    assert "cannot be run on it" in lines[-1]


def test_the_rehearsal_reaches_its_result_line():
    """``--rehearse-cpu`` with the miner itself (jnp on the CPU, --ttl 2
    against an outage of 2.8 s): every outage check holds, and the
    result is not correct only because no chip ran it."""
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(["--workload", CELL, "--seed", "2147483999",
                             "--seconds", "9", "--trace", "0",
                             "--rehearse-cpu"])
    lines = out.getvalue().strip().splitlines()
    assert rc == 0, lines[-5:]
    result = json.loads(lines[-1])
    checks = {c["name"]: c for c in result["checks"]}
    assert result["correct"] is False and result["failed"] == 0
    assert {n for n, c in checks.items() if not c["ok"]} == \
        {"device_platform"}, [ln for ln in lines if "FAILED" in ln]
    assert all(checks[n]["ok"] for n in OUTAGE_CHECKS)
    held = next(ln for ln in lines if ln.startswith("[outage] ")
                and "were held" in ln)
    assert int(held.split()[1]) >= 1
