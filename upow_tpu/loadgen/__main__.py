"""CLI: ``python -m upow_tpu.loadgen`` — run the perf observatory.

Examples::

    python -m upow_tpu.loadgen --smoke --out observatory.json
    python -m upow_tpu.loadgen --progress PROGRESS.jsonl
    python -m upow_tpu.loadgen --smoke --against BENCH_r05.json --report-only
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

from .population import PopulationSpec


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="python -m upow_tpu.loadgen")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny population (CI-sized)")
    ap.add_argument("--seed", type=lambda s: int(s, 0), default=None,
                    help="population seed (default spec's)")
    ap.add_argument("--bench-seconds", type=float, default=0.4,
                    help="per-kernel measurement window")
    ap.add_argument("--device", action="store_true",
                    help="probe/arm a real accelerator (provenance "
                         "records the failure reason if it degrades)")
    ap.add_argument("--out", default="observatory.json",
                    help="artifact path (default observatory.json)")
    ap.add_argument("--progress", default=None,
                    help="also append a summary line to this JSONL file")
    ap.add_argument("--against", default=None,
                    help="after the run, gate the artifact against this "
                         "baseline")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="gate tolerance override")
    ap.add_argument("--report-only", action="store_true",
                    help="gate reports but never fails the run "
                         "(except --enforce'd metrics)")
    ap.add_argument("--metric-tolerance", action="append", default=[],
                    metavar="NAME=TOL",
                    help="per-metric gate tolerance (passed through)")
    ap.add_argument("--enforce", action="append", default=[],
                    metavar="SUBSTR",
                    help="gate metrics matching SUBSTR even under "
                         "--report-only (passed through)")
    args = ap.parse_args(argv)

    from .coresidency import CoresidencySpec
    from .observatory import append_progress, run_observatory, write_artifact
    from .readpath import ReadpathSpec

    spec = PopulationSpec.smoke() if args.smoke else PopulationSpec()
    rp_spec = ReadpathSpec.smoke() if args.smoke else ReadpathSpec()
    co_spec = CoresidencySpec.smoke() if args.smoke else CoresidencySpec()
    if args.seed is not None:
        spec.seed = args.seed
        rp_spec.seed = args.seed
        co_spec.seed = args.seed

    artifact = run_observatory(spec, bench_seconds=args.bench_seconds,
                               device=args.device,
                               readpath_spec=rp_spec,
                               coresidency_spec=co_spec)
    write_artifact(artifact, args.out)
    if args.progress:
        append_progress(artifact, args.progress)

    print(json.dumps({
        "artifact": args.out,
        "events": artifact["slo"]["events"],
        # fleet propagation/node rows carry quantiles only — no req_s
        "endpoints": {ep: {"req_s": row.get("req_s"),
                           "p95_ms": row.get("p95_ms")}
                      for ep, row in artifact["slo"]["endpoints"].items()},
        "provenance": artifact["provenance"],
    }, sort_keys=True))

    if args.against:
        from . import gate

        gate_argv = ["--against", args.against, "--current", args.out]
        if args.tolerance is not None:
            gate_argv += ["--tolerance", str(args.tolerance)]
        if args.report_only:
            gate_argv.append("--report-only")
        for spec_arg in args.metric_tolerance:
            gate_argv += ["--metric-tolerance", spec_arg]
        for substr in args.enforce:
            gate_argv += ["--enforce", substr]
        return gate.main(gate_argv)
    return 0


if __name__ == "__main__":
    sys.exit(main())
