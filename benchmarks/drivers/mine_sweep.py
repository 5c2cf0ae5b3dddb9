"""Driver ``mine_sweep``: the miner CLI against the stub node.

Set-up: the stub serves one job at each of ``warm_difficulties``; the
miner reaches the device, compiles, finds a nonce and pushes the block,
job by job; the stub judges each with the plain reference.  The window
opens when the stub serves the first job at ``difficulty`` and lasts
``--seconds``: the miner sweeps its nonce range a job, finds nothing
(or, one run in some hundreds, a block), fetches the next job and goes
on.  After the window the stub serves one job at each of
``after_difficulties``, mined to a hit like the warm ones; then the
child is asked for the chip's peak memory, which it answers from a
thread of its own (``launch/miner_child.py``), and only then stopped: a
run on the chip whose child gave no number fails in words
(``_stop_child``) and prints no result.  The reference then searches
the round of every such block for a lower nonce.  A traced run also
holds the rounds the miner's lines claim against the search program's
events on the device; where the traffic has ``traced_window_s``, its window is
that long at most (the profiler's stop costs a time an event, so a
round: a fast miner's whole window cannot be stopped in
``STOP_TRACE_WAIT_S``), and the end-to-end metrics, which come from
untraced runs, never see it.
"""

from __future__ import annotations

import os
import signal
import sys
import time

from harness import minerlog, powref, xplane
from harness.manifest import BENCH, BenchError
from harness.procs import LineChild
from harness.stub_node import StubNode

#: ``--control <name>``; the result has to be not correct.
#: ``tighten_target``: the guarantee "a pushed block meets the served
#: target" broken where it is checked: the stub judges every block two
#: hex chars tighter than the job it served (one char would let one seed
#: in sixteen through by luck).
#: ``skip_rounds``: the guarantee "a job that ends 'template expired' has
#: tested every nonce of its range" broken in the child
#: (``launch/faults.py``): one round in sixteen is claimed and never sent
#: to the device.  Only a traced run can see it.
#: ``mute_memory``: not a guarantee of the configuration but one of the
#: benchmark: the launcher never says its ``memory:`` line, and the run
#: has to end ``FAILED:`` with no result line at all.
CONTROLS = {"tighten_target": {"tighten_check": 2},
            "skip_rounds": {"child_fault": "skip_rounds"},
            "mute_memory": {"child_fault": "mute_memory"}}

#: rounds by which the miner's lines and the device's events may differ
#: over a traced window.  The engine keeps two rounds in flight, so the
#: two can be two apart at either end.  Sound runs read 0 to -2; the
#: control's smallest is 7, on four chips in 10 s (PERF.md section 6).
TRACE_EDGE_ROUNDS = 4

#: seconds the driver waits for the child's 'trace: stopped' line after
#: SIGUSR2.  A stop that takes over half of it is said as a warning: the
#: traffic's ``traced_window_s`` is then due to go down.  Not longer: a
#: run has 360 s for set-up, window, stop and the reading of the trace.
STOP_TRACE_WAIT_S = 120

#: "say your peak memory": the launcher's ``MEMORY_SIGNAL``
MEMORY_SIGNAL = signal.SIGRTMIN

#: seconds the driver waits for the child's ``memory:`` line after the
#: request (the reading takes milliseconds), and for its exit after
#: SIGTERM before the session is killed
MEMORY_WAIT_S = 30
STOP_WAIT_S = 120


def _phases(job_list: list) -> list:
    """[(unix0, unix1, name)]: what the parent saw the child doing, for
    naming the device's idle gaps.  A swap runs from the last completed
    round of a job to the first of the next; the rest is sweeping."""
    out = []
    for prev, nxt in zip([None] + job_list, job_list):
        if not nxt["rounds"]:
            continue
        swap_from = prev["rounds"][-1][0] if prev and prev["rounds"] \
            else nxt["start_t"]
        out.append((swap_from, nxt["rounds"][0][0], "job_swap"))
        out.append((nxt["rounds"][0][0], nxt["rounds"][-1][0], "sweep"))
    return out


def _miner_identity(seed: int):
    """The miner's address from the seed (the program's own key and
    address codecs: an input of the run, not an answer)."""
    from upow_tpu.core import curve, point_to_string
    from upow_tpu.core.codecs import string_to_bytes

    _d, pub = curve.keygen(rng=0x5EED0000 + seed)
    address = point_to_string(pub)
    return address, string_to_bytes(address)


def run(ctx) -> dict:
    cell, config, traffic = ctx.cell, ctx.config, ctx.traffic
    which = "rehearse_children" if ctx.rehearse else "children"
    child = config[which][str(cell["chips"])]
    address, address_bytes = _miner_identity(ctx.seed)
    stub = StubNode(ctx.seed, address, address_bytes, traffic,
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
        return _drive(ctx, stub, miner, trace_dir)
    finally:
        miner.stop(timeout=5)
        stub.stop()


def _rounds_between(job_list: list, t0: float, t1: float) -> int:
    """Rounds whose lines arrived in (t0, t1]."""
    return sum(1 for job in job_list for t, _n in job["rounds"]
               if t0 < t <= t1)


def _check_traced_rounds(ctx, check, events, job_list, trace_dir):
    """The rounds whose lines the miner printed while the trace ran,
    against the search program's events that ended on the device in the
    traced window.  Returns the trace's records."""
    path = xplane.find_trace(trace_dir)
    if path is None:
        raise BenchError(f"the traced run left no .xplane.pb under "
                         f"{trace_dir}")
    records = xplane.extract(path)
    span = {e["what"]: e["unix"] for e in events if e["kind"] == "trace"}
    if set(span) != {"started", "stopped"}:
        raise BenchError(f"the child's trace lines are not a start and a "
                         f"stop: {span}")
    claimed = _rounds_between(job_list, span["started"], span["stopped"])
    program = ctx.traffic["search_program"]
    ended = xplane.program_seconds(records, program)["ended"]
    batch = int(ctx.traffic["round_nonces"])
    check("traced_rounds_claimed_minus_on_device", claimed - ended,
          f"+-{TRACE_EDGE_ROUNDS}",
          ended > 0 and abs(claimed - ended) <= TRACE_EDGE_ROUNDS,
          f"the miner's lines claim {claimed} rounds = {claimed * batch} "
          f"nonces in the traced {span['stopped'] - span['started']:.3f}s;"
          f" {ended} events of '{program}' ended on the device there = "
          f"{ended * batch} nonces")
    return records


def _stop_trace(ctx, miner, w0, w1) -> None:
    """SIGUSR2, then the child's 'trace: stopped' line, timed."""
    t_signal = time.time()
    miner.signal(signal.SIGUSR2)
    try:
        t_line, _text = miner.wait_for(
            lambda s: "trace: stopped" in s, STOP_TRACE_WAIT_S,
            "'trace: stopped' line")
    except BenchError:
        if miner.proc.poll() is not None:
            raise
        rounds = _rounds_between(
            minerlog.jobs(minerlog.parse(list(miner.lines))), w0, w1)
        raise BenchError(
            f"stop_trace did not answer in the {STOP_TRACE_WAIT_S} s the "
            f"driver waits: the traced window of {w1 - w0:.1f}s held "
            f"{rounds} rounds, and the profiler's stop costs a time for "
            "every event of every round (PERF.md section 5).  Trace a "
            "shorter window: 'traced_window_s' in benchmarks/traffic/"
            f"{ctx.cell['traffic']}.json or the mix it includes; its "
            f"'why_traced_window' has the arithmetic.  {miner.tail(4)}")
    took = t_line - t_signal
    late = took > STOP_TRACE_WAIT_S / 2
    ctx.say(f"[trace] {'WARNING: ' if late else ''}stop_trace answered in "
            f"{took:.1f} s of the {STOP_TRACE_WAIT_S} s the driver waits"
            + ("; over half: lower 'traced_window_s' in the cell's traffic "
               "before the miner gets any faster" if late else ""))


def _stop_child(ctx, miner) -> tuple:
    """The child asked for the chip's peak memory, then stopped, and one
    ``[stop]`` line on how it went down.  Returns (exit code, events of
    all its lines, how it went down).  The peak is the last ``memory``
    event that holds a number: the answer to the request, or the line
    the launcher says once more at exit.  Without one a run on the chip
    has no result: BenchError, with the numbers that tell the cause."""
    seen = len(miner.lines)
    t_ask = time.time()
    miner.signal(MEMORY_SIGNAL)
    try:
        t_line, _text = miner.wait_for(
            lambda s: "memory: " in s, MEMORY_WAIT_S, "'memory:' line",
            seen=seen)
    except BenchError:
        t_line = None
    answer_s = (time.time() if t_line is None else t_line) - t_ask
    rc = miner.stop(timeout=STOP_WAIT_S)
    events = minerlog.parse(miner.lines)
    said = [e for e in events if e["kind"] == "memory"]
    stop = {
        "memory_request_answered": int(t_line is not None),
        "memory_answer_s": answer_s,    # or how long it went unanswered
        "memory_lines": len(said),
        "memory_peak_bytes": next(
            (int(e["peak"]) for e in reversed(said)
             if e["peak"] not in (None, "null")), None),
        "child_rc": rc, "stop_s": miner.stop_s, "killed": int(miner.killed),
        "exceptions_ignored": sum("Exception ignored" in text
                                  for _t, text in miner.lines)}
    numbers = " ".join(f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
                       for k, v in stop.items())
    warning = "WARNING: the child had to be killed: " if miner.killed else ""
    ctx.say(f"[stop] {warning}{numbers} (waits: {MEMORY_WAIT_S} s for the "
            "answer, "
            f"{STOP_WAIT_S} s from SIGTERM to exit)")
    if stop["memory_peak_bytes"] is None and not ctx.rehearse:
        raise BenchError(
            "no reading of the chip's peak memory, so no result line: "
            f"{numbers}; the child said of its memory: "
            + str([e["reason"] or f"peak_bytes={e['peak']}" for e in said]
                  or "nothing") + f"; its last lines: {miner.tail(6)}")
    return rc, events, stop


def _drive(ctx, stub, miner, trace_dir) -> dict:
    traffic, say, seconds = ctx.traffic, ctx.say, ctx.seconds
    if trace_dir and "traced_window_s" in traffic:
        seconds = min(seconds, float(traffic["traced_window_s"]))
        say(f"[trace] this traced run's window is {seconds:.1f}s of "
            f"--seconds {ctx.seconds:.1f}: the traffic's traced_window_s")
    batch = int(traffic["round_nonces"])
    # ---- set-up: reach the device, the warm job, its block ----
    t_dev, dev_line = miner.wait_for(
        lambda s: s.startswith(("device: ", "difficulty: ")),
        traffic["arm_timeout_s"], "'device:' line")
    device = minerlog.parse_line(dev_line)
    if device["kind"] != "device":
        # device=cpu prints no such line: only a rehearsal gets past this
        if not ctx.rehearse:
            raise BenchError("the miner fetched a job without saying "
                             f"which device serves: {miner.tail()}")
        device = {"platform": "cpu", "device_kind": "not reported",
                  "count": 0}
    say(f"[setup] {dev_line}  ({t_dev - ctx.t0:.1f}s after start)")
    deadline = time.time() + traffic["warm_timeout_s"]
    while stub.window_start is None:
        if time.time() > deadline or miner.proc.poll() is not None:
            raise BenchError(
                f"the miner answered {stub.warm_index} of the warm jobs "
                f"{stub.warm_difficulties} and never fetched a job at "
                f"difficulty {stub.difficulty}: {miner.tail()}")
        time.sleep(0.01)
    w0 = stub.window_start
    w1 = w0 + seconds
    setup_s = w0 - ctx.t0
    say(f"[setup] warm jobs at difficulties {stub.warm_difficulties} "
        f"answered {[round(p['t'] - ctx.t0, 2) for p in stub.pushes]}s "
        f"after start; window opens {setup_s:.2f}s after start")
    # ---- the window ----
    if trace_dir:
        miner.signal(signal.SIGUSR1)
    while time.time() < w1:
        if miner.proc.poll() is not None:
            break
        time.sleep(min(0.05, max(0.0, w1 - time.time())))
    lateness = time.time() - w1
    if trace_dir:
        _stop_trace(ctx, miner, w0, w1)
    exited_early = miner.proc.poll() is not None
    # ---- after the window: jobs mined to a hit, for the reference ----
    t_after = time.time()
    stub.begin_after()
    while not stub.after_done and miner.proc.poll() is None and \
            time.time() - t_after < traffic["warm_timeout_s"]:
        time.sleep(0.01)
    if stub.after_difficulties:
        say(f"[after] jobs at difficulties {stub.after_difficulties}: "
            f"{stub.after_index} answered in {time.time() - t_after:.2f}s "
            "after the window closed (the sweep in hand, a new target, "
            "the search; no metric holds them)")
    rc, events, stop = _stop_child(ctx, miner)
    job_list = minerlog.jobs(events)
    # ---- what the window held ----
    nonces = minerlog.nonces_between(job_list, w0, w1)
    in_window = [j for j in job_list if w0 <= j["start_t"] < w1
                 and j["difficulty"] == stub.difficulty]
    window_pushes = [p for p in stub.pushes if p["phase"] == "window"
                     and w0 <= p["t"] < w1]
    errors = [e for e in events if e["kind"] == "error"
              and w0 <= e["t"] < w1]
    for n, job in enumerate(job_list):
        sweep = minerlog.sweep_seconds(job)
        first = (job["rounds"][0][0] - job["start_t"]) if job["rounds"] \
            else None
        say(f"[job {n}] difficulty={job['difficulty']} block={job['block']}"
            f" start={job['start_t'] - w0:+.3f}s first_round_after="
            f"{'-' if first is None else f'{first:.3f}s'} sweep="
            f"{'-' if sweep is None else f'{sweep:.3f}s'} rounds="
            f"{len(job['rounds'])} nonces={job['tried']} end={job['end']}")
    swap_list = minerlog.swaps(job_list, w0, w1)
    say(f"[window] {seconds:.1f}s: {len(in_window)} jobs started, {nonces} "
        f"nonces in completed rounds, swaps(s)="
        f"{[round(s, 3) for s in swap_list]}, blocks pushed="
        f"{len(window_pushes)}, parent late by {lateness * 1e3:.1f}ms")
    failed = (sum(1 for p in window_pushes if p["faults"]) + len(errors)
              + (1 if exited_early else 0))
    # ---- correct ----
    checks = []

    def check(name, value, limit, ok, note=""):
        checks.append({"name": name, "value": value, "limit": limit,
                       "ok": bool(ok)})
        say(f"[check] {name}: {value} (limit {limit}) "
            f"{'ok' if ok else 'FAILED'}{' - ' + note if note else ''}")

    bad_pushes = [p for p in stub.pushes if p["faults"]]
    check("pushed_blocks_refused_by_reference", len(bad_pushes), 0,
          not bad_pushes, bad_pushes[0]["faults"][0] if bad_pushes else
          f"{len(stub.pushes)} pushed, each judged by hashlib against the "
          "served job")
    start = next((e for e in events if e["kind"] == "start"),
                 {"lo": 0, "hi": 0})
    for phase, want in ([("warm", d) for d in stub.warm_difficulties]
                        + [("after", d) for d in stub.after_difficulties]):
        name = f"{phase}_{want}_nonce_minus_reference_lowest"
        warm = next((p for p in stub.pushes if not p["faults"]
                     and p["phase"] == phase
                     and p["job"]["difficulty"] == want), None)
        if warm is None:
            check(name, "no valid block", 0, False)
            continue
        head = powref.parse_header(warm["content"])
        lo = start["lo"] + (head["nonce"] - start["lo"]) // batch * batch
        t_ref = time.time()
        ref = powref.lowest_hit(head["prefix"], lo, head["nonce"] + 1,
                                warm["job"]["previous_hash"], want,
                                workers=ctx.ref_workers)
        check(name, head["nonce"] - ref, 0, ref == head["nonce"],
              f"reference searched [{lo}, {head['nonce']}] of the round in "
              f"{time.time() - t_ref:.1f}s, lowest hit {ref}, miner pushed "
              f"{head['nonce']}, sha256 "
              f"{powref.digest_hex(warm['content'])[:20]}.. on tip "
              f"..{warm['job']['previous_hash'][-12:]}")
    # the engine never searches the sentinel, nonce 2^32 - 1
    share = min(start["hi"], (1 << 32) - 1) - start["lo"]
    spent = [j for j in job_list if j["end"] == "expired"]
    short = [j for j in spent if j["reported"] != share
             or j["tried"] != share]
    check("expired_jobs_short_of_their_nonce_range", len(short), 0,
          not short, f"{len(spent)} jobs ended 'template expired'; each "
          f"must report and add up to {share}, the miner's range "
          f"[{start['lo']}, {start['hi']})")
    stride = [j for j in job_list
              if any(n != batch for _t, n in j["rounds"][:-1])]
    check("jobs_with_a_round_not_of_round_nonces", len(stride), 0,
          not stride, f"round_nonces={batch}")
    records = None
    if trace_dir:
        records = _check_traced_rounds(ctx, check, events, job_list,
                                       trace_dir)
    check("jobs_failed_in_window", failed, 0, failed == 0,
          (errors[0].get("kind", "") if errors else "")
          + (f" miner exited rc={rc} inside the window"
             if exited_early else ""))
    check("jobs_started_in_window", len(in_window), ">=1",
          len(in_window) >= 1)
    platform_ok = (device["platform"] == "tpu"
                   and device["count"] >= ctx.cell["chips"])
    check("device_platform", f"{device['platform']} x{device['count']}",
          f"tpu x>={ctx.cell['chips']}", platform_ok)
    mesh = next((e["mesh"] for e in events if e["kind"] == "mesh"), None)
    if mesh:
        say(f"[mesh] {mesh}")
    return {
        "correct": all(c["ok"] for c in checks),
        "checks": checks,
        "attempted": len(in_window),
        "failed": failed,
        "values": {"search_mhs": nonces / seconds / 1e6,
                   "setup_s": setup_s},
        "device": {"platform": device["platform"],
                   "kind": device["device_kind"], "count": device["count"],
                   "memory_peak_bytes": stop["memory_peak_bytes"]},
        "stop": stop,
        "observed": {"events": events, "jobs": job_list, "window": (w0, w1),
                     "nonces": nonces, "trace_dir": trace_dir,
                     "round_nonces": batch, "phases": _phases(job_list),
                     "records": records,
                     "trace_started_unix": next(
                         (e["unix"] for e in events if e["kind"] == "trace"
                          and e["what"] == "started"), None)},
    }
