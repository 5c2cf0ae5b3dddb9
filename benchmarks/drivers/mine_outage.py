"""Driver ``mine_outage``: ``mine_roll``'s run against a node that is
restarted under the miner, held to the guarantees that the chip mines
only templates the node served and not past their TTL, that a found
block is delivered whatever the node was doing when the hit came, and
that the miner is back on a fresh template within a sweep and a second
of the node's return.

``RestartingStub`` is ``mine_roll.AgedTipStub`` with the traffic's
``schedule``, in seconds from the window's start (the first job served
at ``difficulty``), each state lasting [from, to):

    down      the listening socket is closed: connection refused (the
              same port is bound again at the state's end)
    syncing   ``get_mining_info`` and ``push_block`` answer
              ``{"ok": false, "error": "node is syncing"}`` at once
    stall     requests are accepted, held, and answered at the state's
              end

and ``push_outage_s``: from the moment the first job of
``after_difficulties`` is first served the node is ``down`` for so many
seconds, so that job's hit meets a dead node.  The stub keeps a log of
every state it entered and every request that reached it
(``harness/outageref.py`` says its form).  The schedule has to lie
inside the window: the steps are taken one after the other.

The run, its window, ``search_mhs``, ``setup_s`` and the checks are
``mine_sweep._drive``'s, and ``mine_roll.roll_checks`` adds its three.
``_drive``'s ``jobs_failed_in_window`` counts every 'node unreachable'
and 'push_block failed' line of the window as a failure; here the node
fails on purpose, so that check is replaced by the last of four more,
all from ``outageref`` over the stub's log and the miner's lines:

    templates_never_served_or_past_ttl   (limit 0) jobs built from a
        template the node never served, or one older than ``ttl_s``
        (the child's --ttl) when the job began
    found_blocks_not_delivered           (limit 0) 'found nonce' lines
        without an answered push of that header at the node
    first_fresh_job_after_return_s       (limit: the run's longest sweep
        + 1 s) the longest, over the node's returns, from the return to
        the first job on a template served after it
    errors_outside_the_schedule          (limit 0) error lines that no
        refused, enveloped or held request explains, and a miner that
        left inside the window

``failed`` in the result line counts the last and the window's refused
blocks, not the failures the schedule made.

A miner whose first job's ``header:`` line says no ``held=`` and
``age=`` has no template feed: it idles while the node is away and
drops a block that meets a dead node, which guarantees (7) and (8)
forbid, and whether a run shows it is the hit's luck (its 8.3 sweep
holds the hit in 53% of runs).  ``run`` ends there with a BenchError,
before the warm job is done: the configuration cannot be run on that
program, and no result line is given for it.
"""

from __future__ import annotations

import os
import re
import sys
import threading
import time
from http.server import ThreadingHTTPServer
from types import SimpleNamespace

from harness import manifest, minerlog, outageref, powref
from harness.manifest import BENCH, BenchError
from harness.procs import LineChild

sweep = manifest.load_module("drivers", "mine_sweep")
roll = manifest.load_module("drivers", "mine_roll")

CONTROLS = sweep.CONTROLS

SYNCING = {"ok": False, "error": "node is syncing"}
_HELD = re.compile(r"^header: .* held=(?P<held>[01]) age=(?P<age>[\d.]+)")
_ERROR_LINE = re.compile(r"^(node unreachable|push_block failed|no mining "
                         r"progress|Traceback)")


class RestartingStub(roll.AgedTipStub):
    """The stub node, restarted under the miner as the traffic says."""

    def __init__(self, seed: int, address: str, address_bytes: bytes,
                 traffic: dict, tighten_check: int = 0):
        super().__init__(seed, address, address_bytes, traffic,
                         tighten_check=tighten_check)
        self.schedule = {name: (float(a), float(b))
                         for name, (a, b) in traffic["schedule"].items()}
        self.push_outage_s = float(traffic["push_outage_s"])
        self.log: list = []
        self.state = "up"
        self._released = threading.Event()   # a stall has ended
        self._after_outage = threading.Event()
        self._closing = threading.Event()
        self._gate = threading.Lock()        # one hand on the socket
        self._handler = self._port = None

    # --------------------------------------------------------- server ---

    def start(self) -> str:
        """The base class's server, bound again on its port with a poll
        of its own: the base polls twice a second, which is how late a
        ``down`` would begin."""
        url = super().start()
        self._handler = self._server.RequestHandlerClass
        self._port = self._server.server_address[1]
        super().stop()
        self._bind()
        threading.Thread(target=self._run_schedule, daemon=True,
                         name="stub-schedule").start()
        return url

    def _bind(self) -> None:
        self._server = ThreadingHTTPServer(("127.0.0.1", self._port),
                                           self._handler)
        self._thread = threading.Thread(
            target=self._server.serve_forever, args=(0.01,), daemon=True,
            name="stub-node")
        self._thread.start()

    def _unbind(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        self._server = None

    def stop(self) -> None:
        self._closing.set()
        self._released.set()
        with self._gate:
            if self._server is not None:
                self._unbind()

    # ------------------------------------------------------- schedule ---

    def _enter(self, state: str) -> None:
        with self._gate:
            if not self._closing.is_set():
                self._enter_locked(state)

    def _enter_locked(self, state: str) -> None:
        was = self.state
        if state == "down":
            # said first: a connection the closing socket resets is the
            # schedule's doing
            self.log.append({"kind": "state", "what": state,
                             "t": time.time()})
            self.state = state
            self._unbind()
            return
        if was == "down":
            self._bind()
        if state == "stall":
            self._released.clear()
        self.state = state
        self.log.append({"kind": "state", "what": state, "t": time.time()})
        if was == "stall":
            self._released.set()

    def _sleep_until(self, t: float) -> None:
        self._closing.wait(max(0.0, t - time.time()))

    def _run_schedule(self) -> None:
        while self.window_start is None:
            if self._closing.wait(0.002):
                return
        w0 = self.window_start
        starts = {a: name for name, (a, _b) in self.schedule.items()}
        ends = {b for _a, b in self.schedule.values()} - set(starts)
        for t, state in sorted(list(starts.items())
                               + [(b, "up") for b in ends]):
            self._sleep_until(w0 + t)
            self._enter(state)
        while not self._after_outage.wait(0.01):
            if self._closing.is_set():
                return
        t = time.time()
        self._enter("down")
        self._sleep_until(t + self.push_outage_s)
        self._enter("up")

    # ----------------------------------------------------------- wire ---

    def _serve(self, what: str, answer, **fields) -> dict:
        """One request that reached the node, logged; ``answer(entry)``
        is the node's own when it is not syncing."""
        entry = dict(fields, kind="request", what=what, t=time.time(),
                     answered_t=None, answer=None,
                     held=self.state == "stall")
        self.log.append(entry)
        if entry["held"]:
            self._released.wait()
        if self.state == "syncing":
            reply = SYNCING
            entry["answer"] = "syncing"
        else:
            reply = answer(entry)
            entry["answer"] = "ok" if reply.get("ok") else "refused"
        entry["answered_t"] = time.time()
        return reply

    def mining_info(self) -> dict:
        def answer(entry):
            served_before = self._after_served
            info = super(RestartingStub, self).mining_info()
            result = info["result"]
            entry.update(block=result["last_block"]["id"] + 1,
                         difficulty=result["difficulty"])
            if self._after_served and not served_before:
                self._after_outage.set()
            return info

        return self._serve("get_mining_info", answer)

    def push_block(self, body: dict) -> dict:
        def answer(_entry):
            return super(RestartingStub, self).push_block(body)

        return self._serve("push_block", answer,
                           content=str(body.get("block_content", "")))


def outage_checks(check, say, stub, job_list, stamps, lines, ttl, window,
                  exited_early) -> int:
    """The four checks of this driver.  Returns what ``failed`` counts
    of them: the error lines of the window that nothing explains."""
    w0, w1 = window
    log = stub.log
    marks = [(e["what"], round(e["t"] - w0, 2)) for e in log
             if e["kind"] == "state"]
    asked = [e for e in log if e["kind"] == "request"]
    say(f"[outage] the node, seconds from the window's start: {marks}; "
        f"{len(asked)} requests reached it, "
        f"{sum(e['answer'] == 'syncing' for e in asked)} answered the "
        f"syncing envelope, {sum(e['held'] for e in asked)} held")
    held = [m for m in (_HELD.match(text) for _t, text in lines) if m]
    say(f"[outage] {sum(int(m['held']) for m in held)} of {len(held)} jobs "
        "were held (built from the template in hand), by the miner's "
        "header: lines; oldest template "
        f"{max((float(m['age']) for m in held), default=0.0):.1f}s")
    unserved = outageref.templates_never_served_or_past_ttl(
        log, job_list, ttl)
    check("templates_never_served_or_past_ttl", len(unserved), 0,
          not unserved, f"first (job, why) {unserved[0]}" if unserved else
          f"{len(job_list)} jobs, each on a template the node had served "
          f"no more than {ttl:g}s before")
    found = [{"t": j["end_t"], "nonce": j["nonce"],
              "timestamp": int(j["start_t"]) if s is None
              else s["timestamp"]}
             for j, s in zip(job_list, stamps) if j["end"] == "found"]
    lost = outageref.found_blocks_not_delivered(log, found)
    pushes = [e for e in asked if e["what"] == "push_block"]
    check("found_blocks_not_delivered", len(lost), 0, not lost,
          f"{len(found)} 'found nonce' lines, {len(pushes)} pushes reached "
          f"the node, answered {[e['answer'] for e in pushes]}"
          + (f"; first lost: nonce {lost[0]['nonce']} found "
             f"{lost[0]['t'] - w0:+.2f}s" if lost else ""))
    sweeps = [j["end_t"] - j["start_t"] for j in job_list
              if j["end"] == "expired"]
    limit = max(sweeps, default=0.0) + 1.0
    until = lines[-1][0] if lines else w1
    waits = outageref.first_fresh_job_after_return_s(
        log, [j["start_t"] for j in job_list], until)
    check("first_fresh_job_after_return_s",
          round(max(waits, default=0.0), 3), f"<={limit:.3f}",
          max(waits, default=0.0) <= limit,
          f"after each return {[round(w, 3) for w in waits]}; the limit "
          "is the run's longest sweep + 1 s")
    errors = [{"t": t, "text": text} for t, text in lines
              if _ERROR_LINE.match(text)]
    odd = outageref.errors_outside_the_schedule(log, errors)
    check("errors_outside_the_schedule", len(odd) + int(exited_early), 0,
          not odd and not exited_early,
          f"{len(errors)} error lines"
          + (f"; first unexplained {odd[0]['t'] - w0:+.2f}s: "
             f"{odd[0]['text'][:120]}" if odd else
             ", each by a request the node refused, enveloped or held")
          + ("; the miner left inside the window" if exited_early else ""))
    return sum(1 for e in odd if w0 <= e["t"] < w1)


def judged_jobs(log: list, job_list: list, stamps: list) -> list:
    """[(job, its header line)] without the jobs whose found block the
    node answered with the syncing envelope: delivered, and judged by
    nobody, where ``mine_roll.roll_checks`` pairs the jobs that ended
    'found' with the pushes the reference judged, one for one."""
    enveloped = {(head["nonce"], head["timestamp"]) for head in (
        powref.parse_header(e["content"]) for e in log
        if e["kind"] == "request" and e["what"] == "push_block"
        and e["answer"] == "syncing")}
    return [(j, s) for j, s in zip(job_list, stamps)
            if not (j["end"] == "found" and s is not None
                    and (j["nonce"], s["timestamp"]) in enveloped)]


def _first_job_ran(text: str) -> bool:
    """The first job's ``header:`` line or, from a miner that prints
    none, the line of its first round or of its end."""
    rec = minerlog.parse_line(text)
    return text.startswith("header: ") or (
        rec is not None and rec["kind"] in ("round", "expired", "found"))


def needs_the_feed(miner, timeout: float) -> None:
    """BenchError unless the miner's first job says ``held=`` and
    ``age=`` (the module's docstring says why)."""
    _t, text = miner.wait_for(_first_job_ran, timeout,
                              "first job's 'header:' line")
    if not _HELD.match(text):
        raise BenchError(
            "the miner's first job says no held= and age= on a 'header:' "
            f"line (it said {text[:120]!r}): it has no template feed and "
            "builds no job from the template in hand, so the configuration "
            "miner-restart cannot be run on it (it would idle while the "
            "node is away and drop a block found while the node is down)")


def run(ctx) -> dict:
    cell, config, traffic = ctx.cell, ctx.config, ctx.traffic
    which = "rehearse_children" if ctx.rehearse else "children"
    child = config[which][str(cell["chips"])]
    address, address_bytes = sweep._miner_identity(ctx.seed)
    stub = RestartingStub(ctx.seed, address, address_bytes, traffic,
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

    def say(msg: str) -> None:
        # _drive's own verdict on the window's error lines is not this
        # cell's: errors_outside_the_schedule stands in its place
        if not msg.startswith("[check] jobs_failed_in_window"):
            ctx.say(msg)

    try:
        needs_the_feed(miner, traffic["arm_timeout_s"])
        result = sweep._drive(SimpleNamespace(**dict(vars(ctx), say=say)),
                              stub, miner, trace_dir)
    finally:
        miner.stop(timeout=5)
        stub.stop()
    checks = result["checks"] = [c for c in result["checks"]
                                 if c["name"] != "jobs_failed_in_window"]

    def check(name, value, limit, ok, note=""):
        checks.append({"name": name, "value": value, "limit": limit,
                       "ok": bool(ok)})
        ctx.say(f"[check] {name}: {value} (limit {limit}) "
                f"{'ok' if ok else 'FAILED'}{' - ' + note if note else ''}")

    observed = result["observed"]
    job_list, (w0, w1) = observed["jobs"], observed["window"]
    lines = list(miner.lines)
    stamps = roll.header_lines(lines)
    start = next((e for e in observed["events"] if e["kind"] == "start"),
                 {"lo": 0, "hi": 0})
    judged = judged_jobs(stub.log, job_list, stamps)
    roll.roll_checks(check, ctx.say, stub, [j for j, _s in judged],
                     [s for _j, s in judged],
                     (start["lo"], min(start["hi"], (1 << 32) - 1)), w1)
    # _drive's ``failed``: the window's refused blocks, its error lines,
    # and one for a miner that left inside it
    refused = sum(1 for p in stub.pushes if p["phase"] == "window"
                  and w0 <= p["t"] < w1 and p["faults"])
    window_errors = sum(1 for e in observed["events"]
                        if e["kind"] == "error" and w0 <= e["t"] < w1)
    exited_early = result["failed"] - refused - window_errors
    unexplained = outage_checks(
        check, ctx.say, stub, job_list, stamps, lines,
        float(traffic["ttl_s"]), (w0, w1), exited_early)
    result["failed"] = refused + unexplained + exited_early
    result["correct"] = all(c["ok"] for c in checks)
    return result
