"""Driver ``utxo_accept``: ``block_accept``'s window on a node whose
unspent-output table is a deployment's size and whose double-spend scan
is the HBM-resident index's probe (``device.utxo_index = true``).

It is ``drivers/block_accept.py``, loaded as that file loads
``mine_sweep``, with three things of its own:

*The fill.*  After the ``device=cpu`` child has laid the base and
stopped, ``harness/utxofill.py`` puts ``utxo_fill`` seeded rows into the
node's own ``unspent_outputs`` table by plain SQL (``[fill]`` lines with
their seconds: the harness's work, in no metric).  The node under test
then starts on that file, and ``setup_s`` counts from its launch, the
index's build inside it.

*The ending.*  ``block_accept._drive`` holds the *whole* table against
the reference's live set and reads nothing of the index; here the
loaded module's ``_database`` and ``_scrape`` are wrapped (the module
object is this driver's own copy, nothing else sees it).  The table is
the reference's live set plus the filler: the live rows are read by
their keys and held against the reference's fingerprint as before, and
the whole table, once, by its row count and by an order-free digest
against the generator's (``chainref.Chain.state()`` sorts and hashes its
whole set at every push, so the filler is never put into the ``Chain``).
The live set is the reference's: the pushes it acknowledged, replayed
without judging the signatures a second time, and the replay's
fingerprint has to be the reference's own.

*Guarantee (5)*, by the node's ``/metrics`` over the window: the probe
answered every input, the host's mirror was never consulted, no
re-layout, the rows applied are the blocks', under 1 MB went host ->
device a block, and after the last push the index holds as many entries
as the table has rows.

The program has to have the index this configuration measures: before
the fixture is built, a ``device=cpu`` child on an empty file is asked
for ``/metrics``, and a program that exports no ``index.apply_rows``
ends the run there (``needs_the_delta_index``), as ``needs_the_feed``
ends ``mine-restart-1chip`` on a miner without a feed.
"""

from __future__ import annotations

import os
import sqlite3
import sys
import time

from harness import blockfixture, chainref, indexwork, manifest, utxofill
from harness.manifest import BENCH, BenchError

accept = manifest.load_module("drivers", "block_accept")

#: ``--control <name>``; the result has to be not correct.  The three of
#: ``block_accept`` and two of the index's:
#: ``sql_scan``: guarantee (5) broken in the node: ``device.utxo_index``
#: false over the same table, so sqlite scans for the double spends;
#: verdicts and state are right.  Its ``accept_tx_per_s`` is the number
#: ROADMAP D4 asks for.
#: ``stale_index``: guarantee (1) broken in the child
#: (``launch/index_faults.py``): a block's delta never reaches the
#: index, so the next block's inputs read absent and a sound block is
#: refused.
CONTROLS = dict(accept.CONTROLS,
                sql_scan={"node_config": {"device": {"utxo_index": False}}},
                stale_index={"child_fault": "stale_index"})

INDEX_LAUNCHER = os.path.join(BENCH, "launch", "index_faults.py")


def needs_the_delta_index(ctx, config) -> None:
    """BenchError unless the program exports ``index.apply_rows``: asked
    of a ``device=cpu`` child on an empty file, before anything is
    built."""
    t0 = time.time()
    node = accept._Node(ctx, "probe", config["base_child"],
                        os.path.join(ctx.work, "probe.db"))
    try:
        node.wait_listening(ctx.traffic["start_timeout_s"])
        samples = accept._scrape(node.url)
    finally:
        node.child.stop(timeout=30)
    try:
        accept._metric(samples, "index.apply_rows")
    except BenchError:
        raise BenchError(
            "needs_the_delta_index: the node exports no index.apply_rows "
            f"(asked {time.time() - t0:.1f}s after the run's start, before "
            "any block is built or pushed): its resident index is rebuilt "
            "and uploaded whole after every block, and the configuration "
            "validator-utxo-index cannot be run on it")
    ctx.say(f"[setup] the program has the delta index (index.apply_rows is "
            f"exported), asked of a device=cpu child in "
            f"{time.time() - t0:.2f}s")


def _fill(ctx, db: str) -> tuple:
    """The filler into the node's table; the generator's digest of it."""
    traffic, say = ctx.traffic, ctx.say
    t0 = time.time()
    cols = utxofill.columns(ctx.seed, traffic["utxo_fill"],
                            traffic["fill_addresses"])
    made_s = time.time() - t0
    took = utxofill.load(db, cols)
    t1 = time.time()
    digest = utxofill.digest_of_columns(cols)
    say(f"[fill] {traffic['utxo_fill']} filler rows from --seed over "
        f"{traffic['fill_addresses']} addresses made in {made_s:.2f}s "
        f"(harness/utxofill.py); as text {took['rows_s']:.2f}s; inserted "
        f"in key order in {took['insert_s']:.2f}s; address index made "
        f"after the load in {took['index_s']:.2f}s; the generator's "
        f"digest {time.time() - t1:.2f}s")
    say(f"[fill] unspent_outputs now holds {took['rows']} rows "
        f"({os.path.getsize(db) / 1e6:.1f} MB on disk); "
        f"{time.time() - t0:.2f}s in all, the harness's work, in no metric")
    return digest


def _live_set(fixture, reference, last: str) -> dict:
    """The reference's unspent outputs after push ``last``: the pushes it
    acknowledged, applied again with the signatures taken as judged."""
    chain = chainref.Chain(lambda items: [True] * len(items))
    for push in fixture.base + fixture.setup + fixture.window:
        if reference[push.name][0]:
            ok, why = chain.push(push.content, push.txs, time.time())
            if not ok:
                raise BenchError(f"replay: {push.name} is refused ({why})")
        if push.name == last:
            break
    want = reference[last][1]
    if chain.state() != want:
        raise BenchError(f"replay: the state after {last} is "
                         f"{chain.state()}, the reference's {want}")
    return chain.utxo


def _vm_mb(pid: int):
    """The child's peak resident memory in MB by ``/proc`` (``VmHWM``;
    ``VmRSS`` where the machine's ``/proc`` has no peak), or None."""
    found = {}
    try:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith(("VmHWM:", "VmRSS:")):
                    found[line[:5]] = int(line.split()[1]) / 1024.0
    except (OSError, ValueError, IndexError):
        pass
    return found.get("VmHWM", found.get("VmRSS"))


def run(ctx) -> dict:
    traffic, config, say = ctx.traffic, ctx.config, ctx.say
    if ctx.faults.get("child_fault") == "stale_index" \
            and "child_argv" not in ctx.faults:
        ctx.faults["child_argv"] = [
            sys.executable, INDEX_LAUNCHER, "--fault", "{fault}", "--",
            "--config", "{name}.json"]
    needs_the_delta_index(ctx, config)
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
    reference = accept._reference(ctx, fixture)
    bodies = {p.name: accept._body(p)
              for p in fixture.setup + fixture.window}
    db = os.path.join(ctx.work, "node.db")
    accept._accept_base(ctx, config, fixture, db)
    filler = _fill(ctx, db)
    trace_dir = os.path.join(ctx.work, "trace") if ctx.trace else None
    node = accept._Node(ctx, "node", child, db, trace_dir=trace_dir,
                        fault=ctx.faults.get("child_fault"),
                        overrides=ctx.faults.get("node_config"))
    # ---- the ending of its own: what _drive reads of the file and of
    # the node goes through here (this driver's own copy of the module)
    scrapes, seen = [], {}
    scrape_as_it_is = accept._scrape

    def scrape(url):
        samples = scrape_as_it_is(url)
        scrapes.append(samples)
        if len(scrapes) == 2:      # after the window, the child alive
            seen["host_mb"] = _vm_mb(node.child.proc.pid)
            seen["built"] = accept._events(url, "index_built")
        return samples

    def database(path):
        seen["held"] = _database(path, fixture, reference)
        return seen["held"]

    accept._scrape, accept._database = scrape, database
    try:
        result = accept._drive(ctx, fixture, reference, bodies, node, db,
                               trace_dir)
    finally:
        node.child.stop(timeout=5)
        accept._scrape = scrape_as_it_is
    return _finish(ctx, result, scrapes, seen, filler)


def _database(db: str, fixture, reference) -> dict:
    """Tip and height from the sqlite file of a node that is gone; the
    unspent outputs the reference holds live at that height, read by
    their keys, under the reference's fingerprint; and the whole table's
    row count and order-free digest."""
    con = sqlite3.connect(f"file:{db}?mode=ro", uri=True)
    try:
        last = con.execute("SELECT id, hash FROM blocks ORDER BY id DESC "
                           "LIMIT 1").fetchone() or (0, None)
        at = next((p.name for p in reversed(
            fixture.base + fixture.setup + fixture.window)
            if reference[p.name][0]
            and reference[p.name][1]["height"] == last[0]
            and reference[p.name][1]["tip"] == last[1]), None)
        live = _live_set(fixture, reference, at) if at else {}
        hashes = sorted({h for h, _i in live})
        rows = {}
        for k in range(0, len(hashes), 400):
            part = hashes[k:k + 400]
            for h, i, a, v in con.execute(
                    "SELECT tx_hash, idx, address, amount FROM "
                    "unspent_outputs WHERE tx_hash IN (%s)"
                    % ",".join("?" * len(part)), part):
                rows[(h, i)] = (a, v)
    finally:
        con.close()
    t0 = time.time()
    table = utxofill.digest_of_table(db)
    return {"height": last[0], "tip": last[1],
            "utxo_fingerprint": chainref.fingerprint(rows),
            "utxo_count": len(rows), "table": table,
            "table_read_s": time.time() - t0,
            "live": utxofill.digest_of_rows(
                (h, i, a, v) for (h, i), (a, v) in live.items())}


def _finish(ctx, result, scrapes, seen, filler) -> dict:
    """Guarantee (5) and the table's whole, added to ``_drive``'s checks;
    the [setup] and [window] lines of the index; what the per-layer
    metrics of the index read."""
    traffic, say = ctx.traffic, ctx.say
    checks = result["checks"]

    def check(name, value, limit, ok, note=""):
        checks.append({"name": name, "value": value, "limit": limit,
                       "ok": bool(ok)})
        say(f"[check] {name}: {value} (limit {limit}) "
            f"{'ok' if ok else 'FAILED'}{' - ' + note if note else ''}")

    pushes = result["observed"]["pushes"]
    window = [p for p in pushes if p["phase"] == "window"]
    valid = [p for p in window if p["kind"] == "valid" and p["ok"]]
    tenth = next((p for p in valid if p["height"] % 10 == 0), None)
    if tenth:
        rest = sorted(p["t1"] - p["t0"] for p in valid if p is not tenth)
        say(f"[window] the height-{tenth['height']} push (every tenth "
            f"block's fingerprint over the whole table) took "
            f"{tenth['t1'] - tenth['t0']:.3f}s; the other {len(rest)} "
            f"valid blocks min={rest[0]:.3f} max={rest[-1]:.3f}"
            if rest else f"[window] only the height-{tenth['height']} push")
    held = seen["held"]
    want = utxofill.combine(filler, held["live"])
    check("durable_table_rows", held["table"][2], want[2],
          held["table"][2] == want[2],
          f"{filler[2]} filler rows and {held['live'][2]} live in the "
          f"reference; the table read in {held['table_read_s']:.2f}s")
    check("durable_table_digest", "%016x%016x" % held["table"][:2],
          "%016x%016x" % want[:2], held["table"][:2] == want[:2],
          "order-free, the generator's filler combined with the "
          "reference's live rows (harness/utxofill.py)")
    values = result["observed"]["values"]
    result["observed"]["device_kind"] = result["device"]["kind"]
    if len(scrapes) >= 2:
        before, after = scrapes[0], scrapes[1]

        def moved(name):
            return accept._metric(after, name) - accept._metric(before,
                                                                name)

        built = [b for b in seen.get("built") or []
                 if b.get("table") == "unspent_outputs"]
        capacity = built[-1]["capacity"] if built else 0
        for b in built:
            say(f"[setup] index built over {b['table']}: {b['entries']} "
                f"entries at capacity {b['capacity']}, {b['resident_bytes']}"
                f" B resident, in {b['seconds']}s; the node's peak host "
                f"memory {b.get('host_peak_before_mb')} MB before the build "
                f"and {b.get('host_peak_mb')} MB after it (its own "
                f"ru_maxrss, a TPU runtime's mappings in both), "
                f"{seen.get('host_mb')} MB after the window by /proc")
        acked = [p for p in window if p["ok"]]
        probed = [p for p in window if p["ok"]
                  or p["kind"].startswith("forged")]
        need = sum(p["txs"] for p in acked)
        got = moved("index.probe_outpoints")
        check("index_probe_outpoints_in_window", got, f">={need}",
              got >= need and need > 0,
              "every input of an acknowledged block is a query of the "
              "resident index")
        for name in ("index.shadow_consults", "index.relayouts"):
            check(name.replace(".", "_") + "_in_window", moved(name), 0,
                  moved(name) == 0)
        rows = sum(2 * p["txs"] + 1 for p in acked)
        check("index_apply_rows_in_window", moved("index.apply_rows"),
              rows, moved("index.apply_rows") == rows,
              f"{len(acked)} blocks acknowledged: their inputs spent, "
              "their outputs and coinbases created")
        per_block = moved("index.upload_bytes") / max(1, len(acked))
        check("index_upload_bytes_a_block", round(per_block), "<1000000",
              per_block < 1e6 and len(acked) > 0,
              "host -> device: the probe's queries and the apply's delta")
        entries = accept._metric(after, "utxo_index_entries", -1.0)
        check("index_entries_after_last_push", entries, held["table"][2],
              entries == held["table"][2] == want[2],
              "the index is the table: utxo_index_entries, the sqlite "
              "row count, the reference's live set plus the filler")
        values["probe_blocks"] = len(probed)
        values["apply_blocks"] = len(acked)
        values["index_upload_mb_per_block"] = per_block / 1e6
        if built:
            values["index_build_s"] = float(built[-1]["seconds"])
        values["probe_query_bytes"] = sum(indexwork.probe_bytes(
            p["txs"], capacity, traffic["probe_window"]) for p in probed)
        values["apply_delta_bytes"] = sum(indexwork.apply_bytes(
            p["txs"] + 1, p["txs"]) for p in acked)
    result["correct"] = all(c["ok"] for c in checks)
    return result
