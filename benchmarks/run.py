#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
                              --trace <0|1> [--rehearse-cpu]
                              [--control <name>]

The last line of stdout is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, in a traced run, ``breakdown``;
then two keys of the harness's own, ``stop`` (how the child went down)
and, last, ``checks``: each number compared beside its limit, which are
also the last lines on stderr.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.  Earlier lines say what is worth
keeping and is no metric.  A run that cannot be made — no chip, fewer
chips than the cell asks for, a file missing — prints no result and
exits non-zero.

This parent never initialises a JAX backend: the chip belongs to the
child that is the system under test.  ``--rehearse-cpu`` runs tiny, with
the children on the CPU, and always ends ``"correct": false``.
``--control`` runs the cell with one guarantee broken where it is
checked (the driver's ``CONTROLS``); its result has to be
``"correct": false``.  The benchmark's own runs use neither.
"""

from __future__ import annotations

import time

T0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.dirname(BENCH))

from harness import manifest, procs, xplane  # noqa: E402
from harness.manifest import BenchError  # noqa: E402


def say(msg: str) -> None:
    print(msg, flush=True)


def _layer_values(mf: dict, cell: dict, result: dict) -> tuple:
    """The cell's per-layer metrics, each by its own reader, and the
    breakdown, from what the driver observed and the trace it left."""
    observed = result["observed"]
    records = observed.get("records")
    if records is None:
        trace_dir = observed.get("trace_dir")
        path = xplane.find_trace(trace_dir) if trace_dir else None
        if path is None:
            raise BenchError("the traced run left no .xplane.pb under "
                             f"{trace_dir}")
        records = xplane.extract(path)
    for line in xplane.summary(records)[:40]:
        say(f"[trace] {line}")
    observed["records"] = records
    observed["trace"] = xplane.reduce(
        records, phases=observed.get("phases"),
        started_unix=observed.get("trace_started_unix"))
    trace = observed["trace"]
    say(f"[trace] window {trace['window_s']:.3f}s, busy "
        f"{trace['busy_s']:.3f}s averaged over {trace['devices']} "
        f"device(s) {trace['per_device_busy_s']}, longest gap "
        f"{trace.get('longest_gap_s', 0.0):.3f}s")
    values = {}
    for entry, spec in manifest.layer_metrics_for(mf, cell["name"]):
        reader = manifest.load_module("readers", spec["reader"])
        value = reader.read(observed, spec)
        if value is None:
            say(f"[layer] {entry['name']}: nothing to read, left out")
            continue
        values[entry["name"]] = {"value": value, "unit": entry["unit"]}
    breakdown = {"device_ops": trace["device_ops"],
                 "idle_gaps": trace["idle_gaps"]}
    return values, breakdown, trace


def run(args, faults=None) -> dict:
    mf = manifest.load_manifest()
    cell = manifest.find_cell(mf, args.workload)
    config = manifest.load_config(mf, cell)
    traffic = manifest.load_traffic(cell["traffic"])
    driver = manifest.load_module("drivers", traffic["driver"])
    faults = dict(faults or {})
    if args.control:
        if args.control not in getattr(driver, "CONTROLS", {}):
            raise BenchError(f"driver {traffic['driver']} has no control "
                             f"{args.control!r}")
        faults.update(driver.CONTROLS[args.control])
    if args.rehearse_cpu:
        traffic.update(traffic.get("rehearse", {}))
    traffic.update(faults.get("traffic", {}))   # tests only: tiny sizes
    work = os.path.join(manifest.ROOT, ".benchwork", cell["name"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    ctx = SimpleNamespace(
        t0=T0, cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=float(args.seconds), trace=bool(args.trace),
        rehearse=args.rehearse_cpu, work=work, say=say, faults=faults,
        ref_workers=max(1, min(8, (os.cpu_count() or 2) - 1)))
    say(f"[run] cell={cell['name']} config={cell['config']} traffic="
        f"{cell['traffic']} driver={traffic['driver']} chips="
        f"{cell['chips']} seed={args.seed} seconds={args.seconds} trace="
        f"{args.trace}{' REHEARSAL ON THE CPU' if args.rehearse_cpu else ''}"
        f"{' CONTROL ' + args.control if args.control else ''}")
    result = driver.run(ctx)
    device = result["device"]
    if not args.rehearse_cpu:
        if device["platform"] != "tpu" or device["count"] < cell["chips"]:
            raise BenchError(f"the child ran on {device}, the cell asks "
                             f"for {cell['chips']} TPU chip(s)")
        manifest.peaks(device["kind"])   # a chip the table lacks: refused
    line = {"correct": bool(result["correct"]) and not args.rehearse_cpu,
            "attempted": result["attempted"], "failed": result["failed"]}
    if args.trace:
        values, breakdown, trace = _layer_values(mf, cell, result)
        line["metrics"] = values
        device = dict(device, busy_s=trace["busy_s"],
                      window_s=trace["window_s"])
        line["device"] = device
        line["breakdown"] = breakdown
    else:
        wanted = manifest.end_to_end_for(mf, cell["name"])
        line["metrics"] = {
            m["name"]: {"value": result["values"][m["name"]],
                        "unit": m["unit"]}
            for m in wanted if m["name"] in result["values"]}
        line["device"] = device
    if args.rehearse_cpu:
        say("[run] rehearsal: the children ran on the CPU, so the result "
            "is not correct whatever the checks said")
    # how the child went down, and last each number compared beside its
    # limit: keys of the harness's own, which the contract lets be
    line["stop"] = result.get("stop")
    line["checks"] = result.get("checks", [])
    return line


def main(argv=None, faults=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)
    try:
        line = run(args, faults)
    except BenchError as e:
        say(f"FAILED: {e}")
        return 1
    except Exception as e:
        traceback.print_exc()
        say(f"FAILED: {type(e).__name__}: {e}")
        return 1
    finally:
        procs.stop_all()
    for c in line["checks"]:
        print(f"[check] {c['name']}: {c['value']} (limit {c['limit']}) "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
