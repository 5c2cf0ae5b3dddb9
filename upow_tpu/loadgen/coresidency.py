"""Co-residency scenario: miner + block verify + mempool intake on ONE
device runtime (ISSUE 10 acceptance; run by
tests/test_device_runtime.py at smoke size).

Three subsystem clients hammer a fresh :class:`DeviceRuntime`
concurrently — a saturating miner stream (``source='mine'``, weight 1),
block-verify signature batches (``source='block'``, weight 4) and
mempool-intake batches (``source='mempool'``, weight 2) submitted in
bursts like the intake front produces — while the single drainer
coalesces compatible sig batches across sources and schedules the mix
with weighted fairness.

The differential is built in and decides whether performance numbers
are reported at all: every concurrent verdict slice must be
byte-identical to the serial single-sig host reference AND to a serial
one-dispatch-per-batch pass over the same deterministic batches.  A
divergence zeroes ``coalesce_ratio`` and omits the latency/dispatch
sections — the same refuse-to-report convention as readpath.

Reported deltas (ISSUE wording: "measurably fewer dispatches, no
verify starvation"):

* ``dispatch_reduction`` — serial sig dispatches / coalesced sig
  dispatches (>1 means the runtime merged cross-source batches).
* ``occupancy`` — aggregate real/padded lanes of the shared
  ``device_runtime`` dispatches vs the serial pass's occupancy.
* ``verify_wait_p99_ms`` — block-source queue wait under the miner
  flood; bounded wait IS the no-starvation claim.
"""

from __future__ import annotations

import hashlib
import threading
import time
from dataclasses import asdict, dataclass
from typing import Dict, List

from ..logger import get_logger

log = get_logger("loadgen")

_PAD = 128  # pad_block shared by every sig submission (one dispatch key)


@dataclass
class CoresidencySpec:
    """Fixed-work sizing (wall time follows from the host's speed, so
    the dispatch/occupancy deltas stay deterministic)."""

    seed: int = 0x10C0DE
    n_unique: int = 48        # distinct keypairs/messages in the universe
    invalid_every: int = 5    # corrupted-signature cadence in the mix
    verify_batches: int = 36  # block-verify submissions
    verify_batch: int = 24    # checks per block-verify submission
    intake_batches: int = 54  # mempool submissions
    intake_batch: int = 6     # checks per mempool submission
    burst: int = 6            # submissions in flight per source client
    miner_chunk: int = 1500   # hashlib nonces per miner dispatch

    @classmethod
    def smoke(cls) -> "CoresidencySpec":
        return cls(n_unique=24, verify_batches=12, intake_batches=18,
                   miner_chunk=600)

    def to_dict(self) -> dict:
        return asdict(self)


def _host_reference(checks) -> List[bool]:
    """Serial single-sig host verdicts — the semantics every batched or
    coalesced path must reproduce bit for bit."""
    from ..verify import txverify

    return [bool(txverify._host_verify_digest(c[0], c[2], c[3])
                 or txverify._host_verify_digest(c[1], c[2], c[3]))
            for c in checks]


def _build_batches(spec: CoresidencySpec):
    """Deterministic (source, checks) work lists for both passes."""
    from .fixtures import pipeline_verify_fixture

    total = (spec.verify_batches * spec.verify_batch
             + spec.intake_batches * spec.intake_batch)
    checks = pipeline_verify_fixture(total, n_unique=spec.n_unique,
                                     invalid_every=spec.invalid_every,
                                     rng_base=spec.seed & 0xFFFF)
    batches = []
    cursor = 0
    for _ in range(spec.verify_batches):
        batches.append(("block", checks[cursor:cursor + spec.verify_batch]))
        cursor += spec.verify_batch
    for _ in range(spec.intake_batches):
        batches.append(("mempool", checks[cursor:cursor + spec.intake_batch]))
        cursor += spec.intake_batch
    return batches


def _miner_work(chunk: int, base: int):
    """One miner dispatch: a hashlib stride over ``chunk`` nonces —
    the reference miner's hot loop shape, cheap and GIL-releasing
    enough to model a saturating device stream on the drainer."""
    prefix = b"coresidency-miner" + base.to_bytes(8, "big")
    h = 0
    for n in range(base, base + chunk):
        h ^= hashlib.sha256(prefix + n.to_bytes(4, "little")).digest()[0]
    return h


def _p99_ms(waits: List[float]) -> float:
    if not waits:
        return 0.0
    ordered = sorted(waits)
    return round(ordered[min(len(ordered) - 1,
                             int(len(ordered) * 0.99))] * 1000.0, 3)


def run_coresidency(spec: CoresidencySpec = None) -> dict:
    """Serial reference pass, then the concurrent co-residency pass on a
    fresh runtime; return the scenario artifact."""
    from ..device.runtime import DeviceRuntime
    from ..telemetry import metrics
    from ..verify import txverify

    spec = spec or CoresidencySpec()
    batches = _build_batches(spec)
    expected = {i: _host_reference(c) for i, (_, c) in enumerate(batches)}

    diff = {"ok": True, "checks": 0, "mismatches": 0}

    # --- serial pass: one dispatch per batch, the pre-runtime shape ----
    txverify.clear_sig_verdicts()
    t0 = time.perf_counter()
    serial_lanes = 0
    for i, (_, checks) in enumerate(batches):
        got = txverify.run_sig_checks(checks, backend="host",
                                      pad_block=_PAD, use_cache=False)
        serial_lanes += len(checks)
        diff["checks"] += 1
        if got != expected[i]:
            diff["mismatches"] += 1
            diff["ok"] = False
    serial_seconds = time.perf_counter() - t0
    serial_dispatches = len(batches)
    serial_padded = serial_dispatches * _PAD
    serial_occupancy = round(serial_lanes / serial_padded, 4)

    # --- concurrent pass: miner + verify + intake on one runtime ------
    txverify.clear_sig_verdicts()
    rt = DeviceRuntime()
    counters0 = metrics.counters()
    real0 = counters0.get("kernel.device_runtime.lanes_real", 0)
    padded0 = counters0.get("kernel.device_runtime.lanes_padded", 0)
    sig_done = threading.Event()
    miner_chunks = [0]
    errors: List[str] = []

    def sig_client(source: str):
        mine_batches = [(i, c) for i, (s, c) in enumerate(batches)
                        if s == source]
        cursor = 0
        try:
            while cursor < len(mine_batches):
                wave = mine_batches[cursor:cursor + spec.burst]
                futs = [(i, rt.submit_sig_checks(
                    c, backend="host", pad_block=_PAD, source=source))
                    for i, c in wave]
                for i, fut in futs:
                    got = fut.result(timeout=120.0)
                    diff["checks"] += 1
                    if got != expected[i]:
                        diff["mismatches"] += 1
                        diff["ok"] = False
                cursor += spec.burst
        except Exception as e:
            log.warning("coresidency %s client failed: %r", source, e)
            errors.append("%s client: %r" % (source, e))

    def miner_client():
        base = 0
        try:
            while not sig_done.is_set():
                fut = rt.submit_call(
                    lambda b=base: _miner_work(spec.miner_chunk, b),
                    kernel="pow_chunk", source="mine")
                fut.result(timeout=120.0)
                miner_chunks[0] += 1
                base += spec.miner_chunk
        except Exception as e:
            log.warning("coresidency miner client failed: %r", e)
            errors.append("miner client: %r" % (e,))

    t0 = time.perf_counter()
    miner = threading.Thread(target=miner_client, daemon=True)
    clients = [threading.Thread(target=sig_client, args=(s,), daemon=True)
               for s in ("block", "mempool")]
    miner.start()
    for c in clients:
        c.start()
    for c in clients:
        c.join(timeout=300.0)
    sig_done.set()
    miner.join(timeout=300.0)
    concurrent_seconds = time.perf_counter() - t0

    stats = rt.stats()
    counters1 = metrics.counters()
    rt.close()
    if errors:
        diff["ok"] = False
        diff["errors"] = errors

    per_source = stats["per_source"]
    mine_n = per_source.get("mine", 0)
    sig_submissions = per_source.get("block", 0) \
        + per_source.get("mempool", 0)
    sig_dispatches = max(1, stats["dispatches"] - mine_n)
    # each miner call records one real/padded lane pair; subtract them
    # to isolate the shared sig dispatches' occupancy
    lanes_real = counters1.get("kernel.device_runtime.lanes_real", 0) \
        - real0 - mine_n
    lanes_padded = counters1.get("kernel.device_runtime.lanes_padded", 0) \
        - padded0 - mine_n

    result = {
        "kind": "coresidency",
        "spec": spec.to_dict(),
        "differential": diff,
        "serial": {
            "dispatches": serial_dispatches,
            "occupancy": serial_occupancy,
            "seconds": round(serial_seconds, 3),
        },
    }
    if not diff["ok"]:
        log.warning("coresidency differential FAILED (%d/%d probes) — "
                    "refusing to report dispatch deltas",
                    diff["mismatches"], diff["checks"])
        result["coalesce_ratio"] = 0.0
        return result

    waits = stats["queue_waits"]
    result["concurrent"] = {
        "seconds": round(concurrent_seconds, 3),
        "submissions": stats["submissions"],
        "dispatches": stats["dispatches"],
        "per_source": per_source,
        "miner_chunks": miner_chunks[0],
        "sig_submissions": sig_submissions,
        "sig_dispatches": sig_dispatches,
        "occupancy": round(lanes_real / lanes_padded, 4)
        if lanes_padded > 0 else None,
        "verify_wait_p99_ms": _p99_ms(waits.get("block", [])),
        "intake_wait_p99_ms": _p99_ms(waits.get("mempool", [])),
        "mine_wait_p99_ms": _p99_ms(waits.get("mine", [])),
    }
    result["coalesce_ratio"] = round(sig_submissions / sig_dispatches, 3)
    result["dispatch_reduction"] = round(
        serial_dispatches / sig_dispatches, 3)
    return result
