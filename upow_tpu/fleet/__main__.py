"""CLI entry: run the geo-soak and print the fleet view.

    python -m upow_tpu.fleet                          # geo-soak, print rows
    python -m upow_tpu.fleet --check-determinism      # two runs, compare fp
    python -m upow_tpu.fleet --out fleet.json --trace

Exit status is non-zero when a core assertion failed, the stitched
push_tx trace did not cross three nodes, or (under
``--check-determinism``) the two same-seed fingerprints differ — so
CI's ``fleet-smoke`` job can gate on the run directly.
"""

from __future__ import annotations

import argparse
import json
import sys

from .geosoak import GEO_NODES, GEO_SEED, fleet_rows, run_geo_artifact


def _core_ok(core: dict) -> bool:
    return all(v for v in core.values() if isinstance(v, bool))


def _print_run(artifact: dict) -> bool:
    core = artifact["core"]
    good = _core_ok(core)
    print(f"{'ok  ' if good else 'FAIL'} {artifact['scenario']:>16} "
          f"n={artifact['nodes']} seed={artifact['seed']} "
          f"{artifact['observed']['elapsed_s']:.2f}s "
          f"fp={artifact['fingerprint'][:16]}")
    if not good:
        for key, val in sorted(core.items()):
            if isinstance(val, bool) and not val:
                print(f"     core failed: {key}", file=sys.stderr)
    return good


def _print_propagation(artifact: dict) -> None:
    prop = artifact["observed"].get("propagation") or {}
    for family in ("blocks", "txs"):
        row = prop.get(family)
        if not row:
            continue
        print(f"     {family:>6}: hashes={row['hashes']} "
              f"covered={row['covered']} "
              f"p50={row['p50_ms']}ms p95={row['p95_ms']}ms "
              f"p99={row['p99_ms']}ms")


def _print_trace(artifact: dict) -> None:
    stitched = artifact["observed"].get("stitched_push_tx")
    if not stitched:
        print("     no stitched push_tx trace", file=sys.stderr)
        return
    print(f"     trace {stitched['trace_id'][:16]} crossed "
          f"{stitched['node_count']} nodes in "
          f"{stitched['duration_ms']}ms:")
    for hop in stitched["hops"]:
        print(f"       {hop['node']:>8} {hop['name']:<28} "
              f"{hop['duration_ms']}ms spans={hop['spans']}"
              + (" ERROR" if hop.get("error") else ""))
    for edge in stitched["hop_latencies_ms"]:
        print(f"       edge {edge['from']} -> {edge['to']}: "
              f"{edge['latency_ms']}ms")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m upow_tpu.fleet",
        description="fleet observatory: deterministic geo-soak, "
                    "propagation percentiles, stitched traces")
    parser.add_argument("--nodes", type=int, default=GEO_NODES,
                        help=f"swarm size (default {GEO_NODES})")
    parser.add_argument("--seed", type=int, default=GEO_SEED)
    parser.add_argument("--out", help="write the JSON artifact here")
    parser.add_argument("--trace", action="store_true",
                        help="print the stitched push_tx fleet trace")
    parser.add_argument("--check-determinism", action="store_true",
                        help="run twice with the same seed and fail "
                             "unless the core fingerprints are identical")
    args = parser.parse_args(argv)

    artifact = run_geo_artifact(nodes=args.nodes, seed=args.seed)
    ok = _print_run(artifact)
    _print_propagation(artifact)
    if args.trace:
        _print_trace(artifact)

    stitched = artifact["observed"].get("stitched_push_tx") or {}
    if (stitched.get("node_count") or 0) < 3:
        print("fleet: stitched push_tx trace crossed "
              f"{stitched.get('node_count', 0)} nodes (< 3)",
              file=sys.stderr)
        ok = False

    if args.check_determinism:
        again = run_geo_artifact(nodes=args.nodes, seed=args.seed)
        if again["fingerprint"] != artifact["fingerprint"]:
            print("fleet: DETERMINISM BROKEN "
                  f"{artifact['fingerprint'][:16]} != "
                  f"{again['fingerprint'][:16]}", file=sys.stderr)
            ok = False
        else:
            print(f"ok   determinism: fp={artifact['fingerprint'][:16]} "
                  "reproduced")

    if args.out:
        from ..snapshot.layout import write_manifest

        write_manifest(args.out, artifact)

    rows = fleet_rows(artifact)
    print(json.dumps({"kind": "fleet_observatory",
                      "fingerprint": artifact["fingerprint"],
                      "kernels": {k: v["value"]
                                  for k, v in rows["kernels"].items()}},
                     sort_keys=True))

    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
