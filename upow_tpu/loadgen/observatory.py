"""The perf observatory: one artifact merging SLO + kernel metrics.

``python -m upow_tpu.loadgen`` (and ``make perf-observatory`` /
bench_suite config 11) runs the load generator against the in-process
node, measures the cheap host-path kernel benches, and writes a single
structured JSON artifact:

* ``slo`` — per-endpoint req/s + p50/p95/p99 (client-measured, exact)
  plus the node's own server-side histogram estimates.
* ``kernels`` — host kernel rates (python / native search + verify)
  and, when armed, the freshest persisted TPU capture.
* ``readpath`` — the hot-state cache scenario (:mod:`.readpath`):
  cached vs bypassed p99 under block cadence, with its byte-identity
  differential; headline metrics are mirrored into ``kernels`` with
  explicit gate directions.
* ``coresidency`` — the shared device-runtime scenario
  (:mod:`.coresidency`): miner + block verify + mempool intake on one
  runtime, cross-source coalescing and fairness deltas with the same
  differential-gated mirroring into ``kernels``.
* ``fleet`` — the deterministic geo-soak (:mod:`..fleet.geosoak`):
  cross-node propagation percentiles, the stitched push_tx trace
  span, and ``fleet_core_ok`` mirrored into ``kernels`` with the
  propagation quantiles (zeroed on any core assertion failure so the
  enforced gate trips on broken distribution semantics).
* ``archive`` — the cold-block archival differential
  (:mod:`..archive.parity`): the archive_prune scenario's pruned node
  vs unpruned twin byte parity, with ``archive_parity_ok`` mirrored
  into ``kernels`` (zeroed on any divergence so the enforced gate
  trips on a broken hot/archive seam, same idiom as
  ``fleet_core_ok``).
* ``provenance`` — what actually ran: ``backend``, ``platform``,
  ``attempted_backend``, ``arm_failure_reason``: the machine-readable
  record of whether the kernels ran on a device or on the host.
* optionally appended (``--progress``) to PROGRESS.jsonl so the
  trajectory file carries SLO metrics alongside kernel throughput.

The regression gate (:mod:`.gate`) consumes these artifacts.
"""

from __future__ import annotations

import asyncio
import datetime
import json
import os
from typing import Optional

from ..logger import get_logger
from .population import PopulationSpec, build_schedule, schedule_fingerprint

log = get_logger("loadgen")


def kernel_bench(seconds: float = 0.4) -> dict:
    """Cheap, always-available host kernel measurements (no XLA
    compiles — CI smoke must stay fast): the pure-python reference
    loops plus the native C++ paths when the extension is present."""
    from .. import native
    from ..benchutil import (python_loop_mhs, python_verify_rate,
                             timed_reps, verify_fixture)

    prefix = bytes(range(32)) * 2
    out = {
        "search_python_loop": {
            "value": round(python_loop_mhs(prefix, seconds), 3),
            "unit": "MH/s"},
    }
    digests, sigs, pubs, msgs = verify_fixture(512)
    out["verify_python"] = {
        "value": round(python_verify_rate(msgs, sigs, pubs, seconds), 1),
        "unit": "sigs/s"}
    if native.load() is not None:
        first = native.p256_verify_batch(digests, sigs, pubs)  # warm
        if first is not None and all(first):
            reps, elapsed = timed_reps(
                lambda: native.p256_verify_batch(digests, sigs, pubs),
                seconds)
            out["verify_native"] = {
                "value": round(reps * len(digests) / elapsed, 1),
                "unit": "sigs/s"}
    try:
        from ..benchutil import verify_pipeline_bench

        vp = verify_pipeline_bench(seconds=min(seconds, 0.4))
        # explicit direction overrides (consumed by gate.py): the
        # speedup/rate names don't match its latency-token inference
        out["verify_pipeline"] = {
            "value": round(vp["pipelined_tx_s"], 1), "unit": "tx/s",
            "direction": "higher",
            "verdicts_equal": vp["verdicts_equal"],
            "differential_txs": vp["differential_txs"]}
        out["verify_pipeline_serial"] = {
            "value": round(vp["serial_tx_s"], 1), "unit": "tx/s",
            "direction": "higher"}
        out["verify_pipeline_speedup"] = {
            "value": round(vp["speedup"], 2) if vp["verdicts_equal"]
            else 0.0,  # divergence zeroes the headline so the gate trips
            "unit": "x", "direction": "higher"}
    except Exception as e:
        log.warning("verify_pipeline bench skipped: %s", e)
    try:
        from ..benchutil import accept_resident_bench

        # smoke-sized chain (the full 8k block belongs to bench_suite
        # config 15); the differential contract is identical, and a
        # divergence zeroes both speedups so the gate trips
        ar = accept_resident_bench(seconds=min(seconds, 0.4),
                                   n_fan=16, n_per=8)
        out["accept_resident"] = {
            "value": ar["resident_tx_s"], "unit": "tx/s",
            "direction": "higher",
            "differential_ok": ar["differential_ok"],
            "shadow_consults": ar["shadow_consults"]}
        out["accept_serial"] = {
            "value": ar["serial_tx_s"], "unit": "tx/s",
            "direction": "higher"}
        out["accept_scan_speedup"] = {
            "value": ar["scan_speedup"], "unit": "x",
            "direction": "higher"}
    except Exception as e:
        log.warning("accept_resident bench skipped: %s", e)
    try:
        from ..benchutil import mining_mesh_bench

        # smoke-sized rounds on whatever mesh is visible (one device on
        # a plain CPU host; the 8-shard case is CI's mesh job).  A
        # diverged differential zeroes the sharded headline and the
        # speedup so the enforced gate trips on correctness breaks.
        mm = mining_mesh_bench(seconds=min(seconds, 0.4),
                               batch_per_device=1 << 12)
        out["mine_mesh_sharded"] = {
            "value": mm["sharded_mhs"], "unit": "MH/s",
            "direction": "higher",
            "differential_ok": mm["differential_ok"],
            "differential_checks": mm["differential_checks"],
            "n_devices": mm["n_devices"]}
        out["mine_mesh_serial"] = {
            "value": mm["serial_mhs"], "unit": "MH/s",
            "direction": "higher"}
        out["mine_mesh_speedup"] = {
            "value": mm["speedup"], "unit": "x", "direction": "higher"}
    except Exception as e:
        log.warning("mining_mesh bench skipped: %s", e)
    return out


def _arm_device(probe_timeout: float) -> dict:
    """Try to arm a real accelerator; provenance either way, plus the
    structured ``bench_arm_failed`` event on failure."""
    from .. import telemetry
    from ..benchutil import probed_platform_cached

    platform = probed_platform_cached(probe_timeout)
    if platform is None:
        reason = f"backend probe hung/failed after {probe_timeout:.0f}s"
        telemetry.event("bench_arm_failed", reason=reason,
                        attempted_backend="tpu", source="observatory")
        return {"platform": None, "attempted_backend": "tpu",
                "arm_failure_reason": reason}
    if platform == "cpu":
        reason = "only cpu visible to jax"
        telemetry.event("bench_arm_failed", reason=reason,
                        attempted_backend="tpu", source="observatory")
        return {"platform": "cpu", "attempted_backend": "tpu",
                "arm_failure_reason": reason}
    return {"platform": platform, "attempted_backend": "tpu",
            "arm_failure_reason": None}


def run_observatory(spec: Optional[PopulationSpec] = None,
                    bench_seconds: float = 0.4,
                    device: bool = False,
                    probe_timeout: float = 90.0,
                    readpath_spec=None,
                    coresidency_spec=None) -> dict:
    """Run loadgen + kernel benches; return the merged artifact."""
    from .harness import run_against_node

    spec = spec or PopulationSpec()
    provenance = {"backend": "node-inprocess", "platform": "host",
                  "attempted_backend": None, "arm_failure_reason": None}
    if device:
        provenance.update(_arm_device(probe_timeout))

    load = asyncio.run(run_against_node(spec))
    kernels = kernel_bench(bench_seconds)

    readpath = None
    try:
        from .readpath import ReadpathSpec, run_readpath

        readpath = asyncio.run(run_readpath(readpath_spec
                                            or ReadpathSpec()))
    except Exception as e:
        log.warning("readpath scenario skipped: %s", e)
    if readpath is not None:
        diff_ok = readpath["differential"]["ok"]
        # divergence zeroes the headline (run_readpath already refused
        # to report latencies); the explicit direction keeps gate.py
        # from latency-token-inferring "lower" off the _p99 suffix
        kernels["readpath_speedup_p99"] = {
            "value": readpath["speedup_p99"] or 0.0, "unit": "x",
            "direction": "higher", "differential_ok": diff_ok,
            "differential_checks": readpath["differential"]["checks"]}
        if diff_ok:
            kernels["readpath_bypass_p99_ms"] = {
                "value": readpath["bypass"]["p99_ms"], "unit": "ms",
                "direction": "lower"}
            kernels["readpath_cached_p99_ms"] = {
                "value": readpath["cached"]["p99_ms"], "unit": "ms",
                "direction": "lower"}
            kernels["readpath_hit_ratio"] = {
                "value": readpath["cached_pass"]["hit_ratio"],
                "unit": "ratio", "direction": "higher"}

    coresidency = None
    try:
        from .coresidency import CoresidencySpec, run_coresidency

        coresidency = run_coresidency(coresidency_spec
                                      or CoresidencySpec.smoke())
    except Exception as e:
        log.warning("coresidency scenario skipped: %s", e)
    if coresidency is not None:
        co_ok = coresidency["differential"]["ok"]
        # same convention as readpath: divergence already zeroed the
        # headline and withheld the perf sections; the explicit
        # directions keep gate.py's token inference out of it
        kernels["coresidency_coalesce_ratio"] = {
            "value": coresidency["coalesce_ratio"] or 0.0, "unit": "x",
            "direction": "higher", "differential_ok": co_ok,
            "differential_checks": coresidency["differential"]["checks"]}
        if co_ok:
            conc = coresidency["concurrent"]
            kernels["coresidency_dispatch_reduction"] = {
                "value": coresidency["dispatch_reduction"], "unit": "x",
                "direction": "higher"}
            kernels["coresidency_occupancy"] = {
                "value": conc["occupancy"] or 0.0, "unit": "ratio",
                "direction": "higher"}
            kernels["coresidency_verify_wait_p99_ms"] = {
                "value": conc["verify_wait_p99_ms"], "unit": "ms",
                "direction": "lower"}

    fleet = None
    try:
        from ..fleet.geosoak import observatory_section

        fleet = observatory_section()
    except Exception as e:
        log.warning("fleet geo-soak skipped: %s", e)
    if fleet is not None:
        # direction-annotated rows (fleet_core_ok zeroes on any failed
        # core assertion, defeating any gate tolerance — same idiom as
        # the differential-zeroed kernel headlines above)
        kernels.update(fleet["kernels"])

    archive = None
    try:
        from ..archive.parity import observatory_section \
            as archive_section

        archive = archive_section()
    except Exception as e:
        log.warning("archive parity differential skipped: %s", e)
    if archive is not None:
        # archive_parity_ok zeroes on ANY failed core assertion in the
        # pruned-vs-twin scenario, defeating any gate tolerance
        kernels.update(archive["kernels"])

    artifact = {
        "kind": "perf_observatory",
        "schedule_fingerprint": schedule_fingerprint(build_schedule(spec)),
        "population": spec.to_dict(),
        "slo": {
            "elapsed_s": load["elapsed_s"],
            "events": load["events"],
            "endpoints": load["endpoints"],
            "server_estimates": load.get("server_slo", {}),
        },
        "ws": load.get("ws_hub", {}),
        "kernels": kernels,
        "provenance": provenance,
    }
    if readpath is not None:
        artifact["readpath"] = readpath
    if coresidency is not None:
        artifact["coresidency"] = coresidency
    if fleet is not None:
        artifact["fleet"] = fleet["section"]
        # per-node fleet latency rows + propagation quantile rows ride
        # the endpoint table (names are fleet.-prefixed: no collisions)
        artifact["slo"]["endpoints"].update(fleet["slo_endpoints"])
    if archive is not None:
        artifact["archive"] = archive["section"]
        artifact["slo"]["endpoints"].update(archive["slo_endpoints"])
    return artifact


def write_artifact(artifact: dict, out_path: str) -> None:
    tmp = f"{out_path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, out_path)


def append_progress(artifact: dict, progress_path: str) -> None:
    """One compact trajectory line per observatory run, additive to the
    driver's own PROGRESS.jsonl records (distinguished by ``kind``)."""
    line = {
        "ts": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "kind": "perf_observatory",
        "slo": {ep: {"req_s": row.get("req_s"),
                     "p50_ms": row.get("p50_ms"),
                     "p95_ms": row.get("p95_ms"),
                     "p99_ms": row.get("p99_ms"),
                     "errors": row.get("errors")}
                for ep, row in artifact["slo"]["endpoints"].items()},
        "kernels": {name: entry.get("value")
                    for name, entry in artifact["kernels"].items()
                    if isinstance(entry, dict) and "value" in entry},
        "provenance": artifact["provenance"],
    }
    with open(progress_path, "a") as f:
        f.write(json.dumps(line, sort_keys=True) + "\n")
