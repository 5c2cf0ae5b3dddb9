"""Driver ``block_accept``: one peer feeds a node full blocks over
``push_block``, block by block, and every signature is new to it.

The harness's own work first, in no metric: the fixture
(``harness/blockfixture.py``: the chain from ``--seed``, the forged
pushes among it) and the plain reference's verdict on every push of it
(``harness/chainref.py``; a fixture whose claims the reference does not
share ends the run before a node sees a block).

Set-up: a ``device=cpu`` child accepts the base (heights 1-3, whose
small blocks would cost the chip two more cold shapes) and is stopped;
the node under test starts on that database (``setup_s`` counts from its
launch) and is pushed the warm block: the first dispatch of the block's
shape and, for the failed lanes' second look over the hex text (the
canary pair's bad half fails by design in every dispatch), of the
smallest, so both programs the window runs are compiled here.
``setup_s`` ends at the warm block's acknowledgement.

The window is a closed loop of one client: the next push is sent when
the last is answered, until ``--seconds`` have passed; the push in
flight then is awaited and counted, so the window ends at an
acknowledgement.  Two of its pushes are forged (one bad signature, one
spent input): each has to be refused with the tip unmoved, and the true
block of that height follows.  ``accept_tx_per_s`` is the transactions
of the valid blocks acknowledged over the seconds from the first push to
the last acknowledgement, the forged pushes' seconds included;
``accept_s_p50`` the median seconds of a valid block's push.

After the window the node's own ``/metrics`` and ``/debug/events`` are
read (who did the work), the child says the chip's peak memory and is
ended by ``os._exit``: what it acknowledged has to be in the sqlite file,
which the driver opens and holds against the reference.
"""

from __future__ import annotations

import copy
import json
import os
import re
import signal
import socket
import sqlite3
import statistics
import sys
import time
import urllib.error
import urllib.request

from harness import blockfixture, chainref, manifest, minerlog
from harness.manifest import BENCH, BenchError
from harness.procs import LineChild

sweep = manifest.load_module("drivers", "mine_sweep")

#: ``--control <name>``; the result has to be not correct.
#: ``host_verify``: guarantee (4), "the work was the device's", broken in
#: the node: it arms the chip and verifies every signature on the host
#: (``device.sig_backend = host``); verdicts and state are right.
#: ``unverified``: guarantee (1) broken in the child
#: (``launch/node_faults.py``): every signature verdict reads true, so
#: the forged block is acknowledged and the chain forks from the
#: reference's.
#: ``forged_unmarked``: the compare's power the other way: the driver is
#: told the forged pushes are valid, and a sound node's refusals differ.
CONTROLS = {"host_verify": {"node_config": {"device": {"sig_backend":
                                                       "host"}}},
            "unverified": {"child_fault": "unverified"},
            "forged_unmarked": {"expect_forged_ok": True}}

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(url: str, body=None, timeout: float = 60.0) -> str:
    req = urllib.request.Request(
        url, data=body,
        headers={"Content-Type": "application/json"} if body else {})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read().decode()


def _body(push) -> bytes:
    return json.dumps({"block_content": push.content, "txs": push.txs,
                       "block_no": push.height}).encode()


def _scrape(url: str) -> dict:
    """{sample name: value} of the node's Prometheus scrape."""
    out = {}
    for line in _http(url + "metrics").splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.split(" # ")[0].rpartition(" ")
        try:
            out[name.strip()] = float(value)
        except ValueError:
            pass
    return out


def _metric(samples: dict, dotted: str, unborn=None) -> float:
    """One sample by the program's dotted name (a counter is exported
    with ``_total``).  A family the node does not export is a failed
    run, never a zero; ``unborn`` is for the few the program exports
    only once they have counted something."""
    name = "upow_" + re.sub(r"[^a-zA-Z0-9_]", "_", dotted)
    for key in (name, name + "_total"):
        if key in samples:
            return samples[key]
    if unborn is None:
        raise BenchError(f"the node exports no metric {dotted!r}")
    return unborn


def _events(url: str, kind: str) -> list:
    got = json.loads(_http(url + f"debug/events?kind={kind}"))["result"]
    return [e.get("fields", e) for e in got]


def _tip(url: str) -> dict:
    last = json.loads(_http(url + "get_mining_info"))["result"]["last_block"]
    return {"height": last.get("id", 0), "tip": last.get("hash")}


class _Node:
    """One ``launch/node_child.py`` on a sqlite file under the run's
    work directory."""

    def __init__(self, ctx, name: str, child: dict, db: str,
                 trace_dir=None, fault=None, overrides=None):
        self.port = _free_port()
        self.url = f"http://127.0.0.1:{self.port}/"
        cfg = copy.deepcopy(child["node_config"])
        for section, values in (overrides or {}).items():
            cfg.setdefault(section, {}).update(values)
        cfg.setdefault("node", {}).update(
            host="127.0.0.1", port=self.port, db_path=db,
            peers_file=os.path.join(ctx.work, f"{name}.nodes.json"))
        cfg.setdefault("log", {}).update(
            path=os.path.join(ctx.work, f"{name}.app.log"))
        path = os.path.join(ctx.work, f"{name}.json")
        with open(path, "w") as f:
            json.dump(cfg, f, indent=1)
        argv = [sys.executable, os.path.join(BENCH, "launch",
                                             "node_child.py")]
        if trace_dir:
            argv += ["--trace-dir", trace_dir]
        if fault:
            argv += ["--fault", fault]
        argv += ["--", "--config", path]
        if ctx.faults.get("child_argv"):   # a test's stand-in for the node
            argv = [a.format(port=self.port, db=db, name=name,
                             fault=fault or "-", sig_backend=cfg.get(
                                 "device", {}).get("sig_backend", "auto"))
                    for a in ctx.faults["child_argv"]]
        self.t_launch = time.time()
        self.child = LineChild(argv, cwd=ctx.work, env=child.get("env"),
                               log_path=os.path.join(ctx.work,
                                                     f"{name}.log"))

    def wait_listening(self, timeout: float) -> float:
        """Until the node answers ``get_mining_info``; seconds it took."""
        deadline = time.time() + timeout
        while True:
            if self.child.proc.poll() is not None:
                raise BenchError(f"the node exited rc="
                                 f"{self.child.proc.returncode} at "
                                 f"start-up: {self.child.tail()}")
            try:
                _tip(self.url)
                return time.time() - self.t_launch
            except (urllib.error.URLError, OSError, ValueError, KeyError):
                if time.time() > deadline:
                    raise BenchError(f"the node never answered within "
                                     f"{timeout:.0f}s: {self.child.tail()}")
                time.sleep(0.05)

    def push(self, push, body: bytes, timeout: float) -> dict:
        """One ``push_block``: sent, answered, timed on this side."""
        t0 = time.time()
        try:
            reply = json.loads(_http(self.url + "push_block", body,
                                     timeout))
            error = None
        except (urllib.error.URLError, OSError, ValueError) as e:
            reply, error = {}, f"{type(e).__name__}: {e}"
        t1 = time.time()
        return {"name": push.name, "kind": push.kind, "height": push.height,
                "txs": len(push.txs), "t0": t0, "t1": t1,
                "ok": bool(reply.get("ok")), "error": error,
                "why": reply.get("error")}


def _reference(ctx, fixture) -> dict:
    """The plain reference over every push in the order the node gets
    them: {push name: (acknowledged, state after)}.  The fixture's claims
    have to be the reference's verdicts, and each forged_sig push's
    unforged twin has to be sound where the forged one is pushed."""
    out, now, t0 = {}, time.time(), time.time()
    twins = {t.name: t for t in fixture.twins}
    with chainref.Verifier(ctx.ref_workers, chunk=256) as verify:
        chain = chainref.Chain(verify)
        for push in fixture.base + fixture.setup + fixture.window:
            twin = twins.get(push.name + "-twin")
            if twin is not None:
                try:
                    chain.judge(twin.content, twin.txs, now)
                except chainref.Refused as e:
                    raise BenchError(
                        f"fixture: the unforged twin of {push.name} is "
                        f"refused by the reference ({e}): the forged "
                        "push has more than its one fault")
            ok, why = chain.push(push.content, push.txs, now)
            if ok != push.valid:
                raise BenchError(
                    f"fixture: {push.name} (height {push.height}) is "
                    f"{'sound' if ok else 'refused: ' + why} by the "
                    f"reference, the fixture claims valid={push.valid}")
            out[push.name] = (ok, chain.state(), why)
    ctx.say(f"[reference] {len(out)} pushes judged by harness/chainref.py "
            f"(OpenSSL, {ctx.ref_workers} processes) in "
            f"{time.time() - t0:.2f}s: the fixture's claims hold; refused: "
            + "; ".join(f"{n}: {v[2]}" for n, v in out.items() if not v[0]))
    return out


def _database(db: str) -> dict:
    """Tip, height and unspent outputs straight from the sqlite file of
    a node that is gone, by plain SQL."""
    con = sqlite3.connect(f"file:{db}?mode=ro", uri=True)
    try:
        last = con.execute("SELECT id, hash FROM blocks ORDER BY id DESC "
                           "LIMIT 1").fetchone()
        utxo = {(h, i): (a, v) for h, i, a, v in con.execute(
            "SELECT tx_hash, idx, address, amount FROM unspent_outputs")}
    finally:
        con.close()
    return {"height": last[0] if last else 0,
            "tip": last[1] if last else None,
            "utxo_fingerprint": chainref.fingerprint(utxo),
            "utxo_count": len(utxo)}


def _accept_base(ctx, config, fixture, db: str) -> None:
    """Heights 1-3 into the database, by a ``device=cpu`` child."""
    t0 = time.time()
    node = _Node(ctx, "base", config["base_child"], db)
    try:
        node.wait_listening(ctx.traffic["start_timeout_s"])
        for push in fixture.base:
            got = node.push(push, _body(push), 120)
            if not got["ok"]:
                raise BenchError(f"the device=cpu child refused "
                                 f"{push.name}: {got['why'] or got['error']}"
                                 f" | {node.child.tail(4)}")
    finally:
        node.child.stop(timeout=30)
    ctx.say(f"[setup] base: a device=cpu child accepted heights 1-"
            f"{len(fixture.base)} and was stopped, {time.time() - t0:.2f}s")


def run(ctx) -> dict:
    traffic, config, say = ctx.traffic, ctx.config, ctx.say
    which = "rehearse_children" if ctx.rehearse else "children"
    child = config[which][str(ctx.cell["chips"])]
    t0 = time.time()
    fixture = blockfixture.build(ctx.seed, traffic, workers=ctx.ref_workers,
                                 say=say)
    say(f"[fixture] built in {time.time() - t0:.2f}s (the harness's work, "
        "in no metric)")
    small = [p.name for p in fixture.setup + fixture.window
             if len(p.txs) < traffic["min_block_txs"]]
    if small:
        raise BenchError(f"blocks {small[:3]} hold fewer than "
                         f"{traffic['min_block_txs']} transactions")
    reference = _reference(ctx, fixture)
    bodies = {p.name: _body(p) for p in fixture.setup + fixture.window}
    db = os.path.join(ctx.work, "node.db")
    _accept_base(ctx, config, fixture, db)
    trace_dir = os.path.join(ctx.work, "trace") if ctx.trace else None
    node = _Node(ctx, "node", child, db, trace_dir=trace_dir,
                 fault=ctx.faults.get("child_fault"),
                 overrides=ctx.faults.get("node_config"))
    try:
        return _drive(ctx, fixture, reference, bodies, node, db, trace_dir)
    finally:
        node.child.stop(timeout=5)


def _drive(ctx, fixture, reference, bodies, node, db, trace_dir) -> dict:
    traffic, say, seconds = ctx.traffic, ctx.say, ctx.seconds
    child = node.child
    expect_forged_ok = bool(ctx.faults.get("expect_forged_ok"))
    if trace_dir and "traced_window_s" in traffic:
        seconds = min(seconds, float(traffic["traced_window_s"]))
        say(f"[trace] this traced run's window is {seconds:.1f}s of "
            f"--seconds {ctx.seconds:.1f}: the traffic's traced_window_s")
    # ---- set-up: the node up, both shapes' first dispatch, warm block --
    up_s = node.wait_listening(traffic["start_timeout_s"])
    dev = next((minerlog.parse_line(text) for _t, text in list(child.lines)
                if text.startswith("device: ")), None)
    if dev is None:
        if not ctx.rehearse:
            raise BenchError("the node listens without having said which "
                             f"device serves: {child.tail()}")
        dev = {"platform": "cpu", "device_kind": "not reported", "count": 0}
    say(f"[setup] node up {up_s:.2f}s after its launch: "
        f"platform={dev['platform']} kind={dev['device_kind']} "
        f"count={dev['count']}")
    pushes = []
    for push in fixture.setup:
        got = node.push(push, bodies[push.name],
                        traffic["first_dispatch_timeout_s"])
        got["phase"] = "setup"
        pushes.append(got)
        say(f"[setup] {push.name} (height {push.height}, {got['txs']} txs):"
            f" ok={got['ok']} in {got['t1'] - got['t0']:.2f}s"
            + (f" ({got['why'] or got['error']})" if not got["ok"] else ""))
    setup_s = pushes[-1]["t1"] - node.t_launch
    firsts = _events(node.url, "verify_first_dispatch")
    for f in firsts:
        say(f"[setup] first dispatch at {f['padded']} lanes ({f['real']} "
            f"real): {f['status']} in {f['seconds']}s")
    for c in _events(node.url, "compile"):
        say(f"[setup] compile {c.get('fun_name')}: trace {c.get('trace_s')}s"
            f" lower {c.get('lower_s')}s cache retrieval "
            f"{c.get('cache_retrieval_s')}s backend {c.get('backend_s')}s")
    say(f"[setup] setup_s {setup_s:.2f}s from the node's launch to the warm "
        f"block's acknowledgement; {time.time() - ctx.t0:.2f}s after the "
        "benchmark's start")
    before = _scrape(node.url)
    # ---- the window: closed loop, one client --------------------------
    if trace_dir:
        child.signal(signal.SIGUSR1)
        child.wait_for(lambda s: "trace: started" in s, 60,
                       "'trace: started' line")
    w0 = time.time()
    exhausted = True
    for push in fixture.window:
        if time.time() - w0 >= seconds or child.proc.poll() is not None:
            exhausted = False
            break
        got = node.push(push, bodies[push.name], traffic["push_timeout_s"])
        got["phase"] = "window"
        if not got["ok"]:
            try:
                got["tip_after"] = _tip(node.url)
            except (urllib.error.URLError, OSError, ValueError, KeyError):
                got["tip_after"] = None
        pushes.append(got)
    w1 = time.time()
    exited_early = child.proc.poll() is not None
    if trace_dir:
        t_sig = time.time()
        child.signal(signal.SIGUSR2)
        t_line, _text = child.wait_for(
            lambda s: "trace: stopped" in s, sweep.STOP_TRACE_WAIT_S,
            "'trace: stopped' line")
        say(f"[trace] stop_trace answered in {t_line - t_sig:.1f} s of the "
            f"{sweep.STOP_TRACE_WAIT_S} s the driver waits")
    # ---- after the window: who did the work, then the child's end -----
    after = armed = None
    if not exited_early:
        after = _scrape(node.url)
        armed = (_events(node.url, "device_runtime_armed")
                 or [{}])[-1].get("platform")
    rc, events, stop = sweep._stop_child(ctx, child)
    window = [p for p in pushes if p["phase"] == "window"]
    valid = [p for p in window if p["kind"] == "valid" and p["ok"]]
    for n, p in enumerate(window):
        say(f"[push {n}] {p['name']} height={p['height']} txs={p['txs']} "
            f"sent={p['t0'] - w0:+.3f}s took={p['t1'] - p['t0']:.3f}s "
            f"ok={p['ok']}" + (f" ({p['why'] or p['error']})"
                               if not p["ok"] else ""))
    took = sorted(p["t1"] - p["t0"] for p in valid)
    values = {"setup_s": setup_s}
    if valid:
        span = valid[-1]["t1"] - window[0]["t0"]
        txs = sum(p["txs"] for p in valid)
        values["accept_tx_per_s"] = txs / span
        values["accept_s_p50"] = statistics.median(took)
        say(f"[window] {w1 - w0:.3f}s for --seconds {seconds:.0f}: "
            f"{len(window)} pushes, {len(valid)} valid blocks acknowledged"
            f" = {txs} transactions in {span:.3f}s; seconds a valid block:"
            f" n={len(took)} min={took[0]:.3f} p50="
            f"{values['accept_s_p50']:.3f} max={took[-1]:.3f}")
    # ---- correct -------------------------------------------------------
    checks = []

    def check(name, value, limit, ok, note=""):
        checks.append({"name": name, "value": value, "limit": limit,
                       "ok": bool(ok)})
        say(f"[check] {name}: {value} (limit {limit}) "
            f"{'ok' if ok else 'FAILED'}{' - ' + note if note else ''}")

    def wanted(p) -> bool:
        if expect_forged_ok and p["kind"].startswith("forged"):
            return True
        return reference[p["name"]][0]

    wrong = [p for p in pushes if p["ok"] != wanted(p)]
    check("verdicts_differing_from_reference", len(wrong), 0, not wrong,
          (f"{wrong[0]['name']}: node ok={wrong[0]['ok']} "
           f"({wrong[0]['why'] or wrong[0]['error']}), reference "
           f"{wanted(wrong[0])}") if wrong else
          f"{len(pushes)} pushes, the warm block included, each judged by "
          "harness/chainref.py")
    refused = [p for p in window if not p["ok"]]
    last_sound = {"height": fixture.setup[-1].height,
                  "tip": reference[fixture.setup[-1].name][1]["tip"]}
    moved = []
    for p in window:
        if p["ok"]:
            last_sound = {k: reference[p["name"]][1][k]
                          for k in ("height", "tip")}
        elif p.get("tip_after") != last_sound:
            moved.append((p["name"], p.get("tip_after"), last_sound))
    check("refused_pushes_that_moved_the_tip", len(moved), 0, not moved,
          str(moved[0]) if moved else
          f"{len(refused)} refused in the window "
          f"({[p['name'] for p in refused]}), tip read back after each")
    check("valid_blocks_acknowledged", len(valid), ">=1", len(valid) >= 1)
    check("fixture_exhausted", int(exhausted), "0 or 1", True,
          "the window ended at the fixture's last block: raise "
          "valid_blocks in the traffic file" if exhausted else "")
    failed = sum(1 for p in window if p["error"]) + int(exited_early)
    check("pushes_failed_in_window", failed, 0, failed == 0,
          next((p["error"] for p in window if p["error"]), "")
          + (f" node exited rc={rc} inside the window"
             if exited_early else ""))
    # guarantee (2): what was acknowledged is in the file of a node that
    # got no clean close
    want = reference[pushes[-1]["name"]][1]   # after every push so far
    held = _database(db)
    for key in ("height", "tip", "utxo_fingerprint"):
        check(f"durable_{key}", held[key], want[key],
              held[key] == want[key],
              f"{held['utxo_count']} unspent outputs in {db[-20:]}, "
              f"{want['utxo_count']} in the reference"
              if key == "utxo_fingerprint" else "")
    # guarantee (4): the work was the device's
    lanes = {"real": 0.0, "padded": 0.0}
    if after is not None:
        def moved_by(name):
            return _metric(after, name) - _metric(before, name)

        lanes = {"real": moved_by("kernel.p256_verify.lanes_real"),
                 "padded": moved_by("kernel.p256_verify.lanes_padded")}
        need = sum(p["txs"] for p in window if p["ok"])
        check("p256_lanes_real_in_window", lanes["real"], f">={need}",
              lanes["real"] >= need,
              f"padded {lanes['padded']:.0f}; every transaction of an "
              "acknowledged block is a lane")
        check("canaries_failed", _metric(after, "verify.canary_fail"), 0,
              _metric(after, "verify.canary_fail") == 0
              and _metric(after, "verify.canary_pass") > 0,
              f"{_metric(after, 'verify.canary_pass'):.0f} passed")
        for name in ("resilience.device_fallback",
                     "kernel.p256_verify.pallas_fallbacks",
                     "device_verify_health"):
            check(name.replace(".", "_"), _metric(after, name), 0,
                  _metric(after, name) == 0)
        compiled = _metric(after, "compile.count", 0.0) \
            - _metric(before, "compile.count", 0.0)
        check("programs_compiled_in_window", compiled, 0, compiled == 0,
              f"{_metric(after, 'compile.count', 0.0):.0f} since the node's "
              "start, persistent cache hits "
              f"{_metric(after, 'compile_cache.persistent_hits', 0.0):.0f} "
              f"misses "
              f"{_metric(after, 'compile_cache.persistent_misses', 0.0):.0f}")
    platform_ok = dev["platform"] == "tpu" and dev["count"] >= \
        ctx.cell["chips"] and armed == "tpu"
    check("device_platform", f"{dev['platform']} x{dev['count']}",
          f"tpu x>={ctx.cell['chips']}", platform_ok,
          f"the runtime's own event says {armed}")
    phases = []
    for prev, p in zip([None] + window, window):
        if prev is not None:
            phases.append((prev["t1"], p["t0"], "between"))
        phases.append((p["t0"], p["t1"], "accept" if p["ok"] else
                       "refuse_" + p["kind"]))
    first = next((f for f in firsts if f["padded"] >= fixture.lanes), None)
    observed_values = {}
    if first:
        observed_values["first_dispatch_s"] = float(first["seconds"])
    if lanes["padded"]:
        observed_values["lane_fill_share"] = \
            100.0 * lanes["real"] / lanes["padded"]
        observed_values["p256_lanes_real"] = lanes["real"]
    observed_values["p256_blocks"] = sum(
        1 for p in window if p["ok"] or p["kind"] == "forged_sig")
    return {
        "correct": all(c["ok"] for c in checks),
        "checks": checks,
        "attempted": len(window),
        "failed": failed,
        "values": values,
        "device": {"platform": dev["platform"], "kind": dev["device_kind"],
                   "count": dev["count"],
                   "memory_peak_bytes": stop["memory_peak_bytes"]},
        "stop": stop,
        "observed": {"events": events, "window": (w0, w1),
                     "pushes": pushes, "trace_dir": trace_dir,
                     "phases": phases, "values": observed_values,
                     "trace_started_unix": next(
                         (e["unix"] for e in events if e["kind"] == "trace"
                          and e["what"] == "started"), None)},
    }
