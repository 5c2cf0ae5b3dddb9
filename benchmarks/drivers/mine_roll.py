"""Driver ``mine_roll``: ``mine_sweep``'s run against a node whose tip has
an age, held to the guarantee that no header is swept twice.

``stub_node.StubNode`` serves ``last_block.timestamp = int(now) - 1``: a
window of one second for the header's timestamp, in which a miner whose
sweep lasts under a second can only build the header it has just swept
again.  ``AgedTipStub`` serves what a node serves: the tip's own
timestamp, constant for the tip, ``tip_age_s`` seconds before the tip
was first served (upstream's miner stamps a template at fetch and keeps
it up to 90 s, and the block target is 60 s), and it refuses a pushed
block whose timestamp breaks the node's rule (``rollref.valid``).

The run, its window, ``search_mhs``, ``setup_s`` and every check of
``mine_sweep`` are ``mine_sweep._drive``'s.  Three checks are added, each
with limit 0, from the miner's ``header:`` lines (one a job, after the
``difficulty:`` line: ``timestamp= behind= window= repeat=``):

    headers_repeated_in_window              ``rollref.repeats`` over the
        jobs that began before the window closed: same tip, merkle root,
        address, difficulty, timestamp and nonce range as an earlier job
    job_timestamps_outside_the_nodes_rule   a job stamped at or before
        the served ``last_block.timestamp``, or after the second its
        line arrived
    pushed_timestamp_differs_from_job_line  a pushed block whose header
        carries another timestamp than its job's line said

A job without a ``header:`` line (a program from before the line) is
taken to be stamped with the second its ``difficulty:`` line arrived,
which is what a miner that reads the clock does; a block pushed by such
a job has no line to agree with and counts as differing.
"""

from __future__ import annotations

import os
import re
import sys
import time

from harness import manifest, minerlog, powref, rollref
from harness.manifest import BENCH
from harness.procs import LineChild
from harness.stub_node import StubNode

sweep = manifest.load_module("drivers", "mine_sweep")

#: ``mine_sweep``'s, and ``fresh_tip``: the guarantee "inside one tip no
#: header is swept twice" with the room for it taken away where it is
#: served: the tip is as old as its first fetch, so the window grows by
#: one second a second and a miner that spends two ranges a second has
#: to repeat (and its first job has no valid second at all).
CONTROLS = dict(sweep.CONTROLS, fresh_tip={"traffic": {"tip_age_s": 0}})

_HEADER = re.compile(r"^header: timestamp=(?P<timestamp>\d+) "
                     r"behind=(?P<behind>-?\d+) window=(?P<window>-?\d+) "
                     r"repeat=(?P<repeat>[01])")


class AgedTipStub(StubNode):
    """The stub node with a tip that has a timestamp of its own."""

    def __init__(self, seed: int, address: str, address_bytes: bytes,
                 traffic: dict, tighten_check: int = 0):
        super().__init__(seed, address, address_bytes, traffic,
                         tighten_check=tighten_check)
        self.tip_age_s = int(traffic["tip_age_s"])
        self.tip_timestamps: dict = {}    # previous hash -> its timestamp

    def mining_info(self) -> dict:
        now = time.time()
        info = super().mining_info()
        last = info["result"]["last_block"]
        last["timestamp"] = self.tip_timestamps.setdefault(
            last["hash"], int(now) - self.tip_age_s)
        return info

    def push_block(self, body: dict) -> dict:
        """The reference's verdict first (the base class also moves the
        warm and after jobs on, whatever it found), then the node's rule
        for the timestamp: a fault of it is added to the push's record,
        where ``pushed_blocks_refused_by_reference`` counts it."""
        now = int(time.time())
        reply = super().push_block(body)
        push = self.pushes[-1]
        ts = _pushed(push)
        prev_ts = self.tip_timestamps.get(push["job"]["previous_hash"])
        # no header (the base class said so), or inside the rule
        if ts is None or prev_ts is None or rollref.valid(prev_ts, ts, now):
            return reply
        fault = (f"timestamp {ts} is outside the node's rule "
                 f"({prev_ts}, {now}]")
        push["faults"].append(fault)
        return {"ok": False, "error": fault}


def header_lines(lines: list) -> list:
    """One entry a job (a ``difficulty:`` line), in order: its ``header:``
    line's fields and arrival time, or None where the job printed none."""
    out = []
    for t, text in lines:
        rec = minerlog.parse_line(text)
        if rec is not None and rec["kind"] == "job":
            out.append(None)
        m = _HEADER.match(text)
        if m and out and out[-1] is None:
            out[-1] = dict({k: int(v) for k, v in m.groupdict().items()},
                           t=t)
    return out


def roll_checks(check, say, stub, job_list, stamps, lo_hi, w1) -> None:
    """The three checks of this driver, from the jobs (``minerlog.jobs``)
    and their ``header:`` lines (``header_lines``), one for one.  The
    first two hold the jobs that began before the window closed at
    ``w1``: while a traced run's profiler stops, for longer than the
    window lasted, the miner mines on, and what the tip's window holds
    by then is the traffic's arithmetic, not the program's."""
    merkle = powref.miner_merkle(stub.pending)
    jobs, outside, unsaid = [], [], 0
    for job, stamp in zip(job_list, stamps):
        if job["start_t"] >= w1:
            break
        # block number -> the tip it was mined on (stub: height = index + 1)
        tip = stub._tips[job["block"] - 2]
        if stamp is None:
            unsaid += 1
            ts, t_line = int(job["start_t"]), job["start_t"]
        else:
            ts, t_line = stamp["timestamp"], stamp["t"]
        jobs.append({"previous_hash": tip, "merkle_root": merkle,
                     "address": stub.address,
                     "difficulty": job["difficulty"], "timestamp": ts,
                     "range": lo_hi})
        prev_ts = stub.tip_timestamps.get(tip)
        if prev_ts is None or not rollref.valid(prev_ts, ts, int(t_line)):
            outside.append((ts, prev_ts, int(t_line)))
    again = rollref.repeats(jobs)
    said = [s for s in stamps[:len(jobs)] if s is not None]
    counts = {"fresh": sum(1 for s in said
                           if not s["repeat"] and not s["behind"]),
              "rolled": sum(1 for s in said
                            if not s["repeat"] and s["behind"] > 0),
              "repeated": sum(s["repeat"] for s in said)}
    say(f"[roll] {len(jobs)} jobs: header lines say {counts}, {unsaid} "
        f"jobs said none; furthest behind "
        f"{max((s['behind'] for s in said), default=0)} s, windows "
        f"{min((s['window'] for s in said), default=0)}-"
        f"{max((s['window'] for s in said), default=0)} s")
    check("headers_repeated_in_window", len(again), 0, not again,
          f"of {len(jobs)} jobs; the miner's own lines say repeat=1 in "
          f"{counts['repeated']}" + (
              f"; first: job {again[0]} timestamp "
              f"{jobs[again[0]]['timestamp']}" if again else "")
          + (f"; {unsaid} jobs without a header: line taken as stamped "
             "when their difficulty: line arrived" if unsaid else ""))
    check("job_timestamps_outside_the_nodes_rule", len(outside), 0,
          not outside, f"(timestamp, served last_block.timestamp, second "
          f"the line arrived) {outside[0]}" if outside else
          "each in (served last_block.timestamp, second its line arrived]")
    found = [s for j, s in zip(job_list, stamps) if j["end"] == "found"]
    differ = [
        (push["content"][196:204], stamp and stamp["timestamp"])
        for push, stamp in zip(stub.pushes, found)
        if stamp is None or _pushed(push) != stamp["timestamp"]]
    unmatched = abs(len(stub.pushes) - len(found))
    check("pushed_timestamp_differs_from_job_line",
          len(differ) + unmatched, 0, not differ and not unmatched,
          f"{len(stub.pushes)} pushed, {len(found)} jobs ended 'found'"
          + (f"; first (header bytes, line) {differ[0]}" if differ else ""))


def _pushed(push: dict):
    try:
        return rollref.pushed_timestamp(push["content"])
    except ValueError:
        return None


def run(ctx) -> dict:
    cell, config, traffic = ctx.cell, ctx.config, ctx.traffic
    which = "rehearse_children" if ctx.rehearse else "children"
    child = config[which][str(cell["chips"])]
    address, address_bytes = sweep._miner_identity(ctx.seed)
    stub = AgedTipStub(ctx.seed, address, address_bytes, traffic,
                       tighten_check=ctx.faults.get("tighten_check", 0))
    node_url = stub.start()
    trace_dir = os.path.join(ctx.work, "trace") if ctx.trace else None
    argv = [sys.executable, os.path.join(BENCH, "launch", "miner_child.py")]
    if trace_dir:
        argv += ["--trace-dir", trace_dir]
    if ctx.faults.get("child_fault"):
        argv += ["--fault", ctx.faults["child_fault"]]
    argv += ["--"] + [a.format(address=address, node=node_url)
                      for a in child["argv"] + traffic.get("miner_args", [])]
    if ctx.faults.get("child_argv"):   # a test's stand-in for the miner
        argv = [a.format(address=address, node=node_url)
                for a in ctx.faults["child_argv"]]
    miner = LineChild(argv, cwd=ctx.work, env=child.get("env"),
                      log_path=os.path.join(ctx.work, "miner.log"))
    try:
        result = sweep._drive(ctx, stub, miner, trace_dir)
    finally:
        miner.stop(timeout=5)
        stub.stop()
    checks = result["checks"]

    def check(name, value, limit, ok, note=""):
        checks.append({"name": name, "value": value, "limit": limit,
                       "ok": bool(ok)})
        ctx.say(f"[check] {name}: {value} (limit {limit}) "
                f"{'ok' if ok else 'FAILED'}{' - ' + note if note else ''}")

    events = result["observed"]["events"]
    start = next((e for e in events if e["kind"] == "start"),
                 {"lo": 0, "hi": 0})
    roll_checks(check, ctx.say, stub, result["observed"]["jobs"],
                header_lines(list(miner.lines)),
                (start["lo"], min(start["hi"], (1 << 32) - 1)),
                result["observed"]["window"][1])
    result["correct"] = all(c["ok"] for c in checks)
    return result
