"""Headline benchmark: sha256 PoW search throughput on the real chip.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "MH/s", "vs_baseline": N}

The baseline is the reference miner's hot loop — a pure-Python
hashlib-per-nonce stride (reference miner.py:83-98) — measured live on
this host's CPU for a short window, single worker (the reference's unit
of scaling is one process per core; BASELINE.md pegs it at order
0.1–1 Mh/s per core).  ``vs_baseline`` is our device rate over that.

Run directly (``python bench.py``) on a machine with the chip; without
one it exits 3 and prints no number (a CPU number under a device
metric's name would be worse than none).  Options:
    --backend pallas|jnp|native|python   (default pallas)
    --seconds N      measurement window after warmup (default 10)
    --batch N        nonces per device dispatch (default 2^24)
"""

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


# Append-only event log shared with the watchtower's alert_fired
# records, so paging incidents and bench arms interleave on one timeline.
_BENCH_EVENTS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".bench_events.jsonl")
_BENCH_EVENTS_MAX = 1 << 20     # rotate past 1 MiB (soak runs append forever)


def _rotate_keep_tail(path: str, max_bytes: int) -> None:
    """Size-cap an append-only log: past ``max_bytes``, keep the newest
    half aligned to a line boundary (atomic replace, never raises)."""
    try:
        if os.path.getsize(path) <= max_bytes:
            return
        with open(path, "rb") as f:
            f.seek(-(max_bytes // 2), os.SEEK_END)
            tail = f.read()
        cut = tail.find(b"\n")
        if cut >= 0:
            tail = tail[cut + 1:]
        tmp = path + ".rot"
        with open(tmp, "wb") as f:
            f.write(tail)
        os.replace(tmp, path)
    except OSError:
        pass


def _record_bench_event(kind: str, **fields) -> None:
    """Append one event line to .bench_events.jsonl; never let
    bookkeeping take the bench down."""
    entry = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S%z"), "kind": kind,
             **fields}
    try:
        _rotate_keep_tail(_BENCH_EVENTS, _BENCH_EVENTS_MAX)
        with open(_BENCH_EVENTS, "a") as f:
            f.write(json.dumps(entry, sort_keys=True) + "\n")
    except OSError as e:
        sys.stderr.write(f"bench event not recorded: {e}\n")


def _baseline_python_mhs(prefix: bytes, seconds: float = 1.0) -> float:
    from upow_tpu.benchutil import python_loop_mhs

    return python_loop_mhs(prefix, seconds)


def _measure_verify(platform: str, seconds: float) -> dict:
    """The second flagship kernel, in the driver-captured line: batched
    P-256 ECDSA verify (reference hot spot transaction_input.py:100-109
    inside manager.py:628-632).

    TPU: the production dispatch unit (fused pallas-jac program, device
    scalar prep) at 8192 lanes — kernel-only rate plus the pipelined
    end-to-end rate (host packing of batch k+1 overlaps device batch k).
    CPU fallback: the framework's fastest host path (C++ OpenMP batch),
    else the jnp program on XLA:CPU.  Baseline = pure-python
    ``curve.verify`` on this host, same convention as bench_suite.
    """
    from upow_tpu.benchutil import (python_verify_rate, timed_reps,
                                    verify_fixture)
    from upow_tpu.crypto import p256 as P

    n_lanes = 8192 if platform != "cpu" else 2048
    digests, sigs, pubs, msgs = verify_fixture(n_lanes)
    base_rate = python_verify_rate(msgs, sigs, pubs)

    if platform != "cpu" and P.PALLAS_KERNEL == "jac":
        import jax

        from upow_tpu.benchutil import pipelined_loop
        import numpy as np

        tile = P._pick_tile(n_lanes)
        inputs, *_ = P._pack_device_inputs(digests, sigs, pubs, n_lanes)

        def kernel_call():
            # w passed explicitly: the jitted default binds _WINDOW at
            # module load, NOT the PALLAS_JAC_WINDOW knob
            return P._prep_and_verify_pallas_jac(
                inputs, tile=tile, w=P.PALLAS_JAC_WINDOW)

        res = np.asarray(jax.block_until_ready(kernel_call()))  # warm/compile
        assert bool(res[0].all()) and not bool(res[1].any())
        reps, elapsed = timed_reps(
            lambda: jax.block_until_ready(kernel_call()), seconds)
        kernel_rate = reps * n_lanes / elapsed

        def dispatch():
            pk, *_ = P._pack_device_inputs(digests, sigs, pubs, n_lanes)
            return P._prep_and_verify_pallas_jac(
                pk, tile=tile, w=P.PALLAS_JAC_WINDOW)

        def check(r):
            r = np.asarray(r)
            assert bool(r[0].all()) and not bool(r[1].any())

        reps, elapsed = pipelined_loop(dispatch, check, seconds, depth=2)
        rate = reps * n_lanes / elapsed
        return {
            "metric": f"verify_8k_pipelined_{platform}",
            "value": round(rate, 1), "unit": "sigs/s",
            "vs_baseline": round(rate / base_rate, 1),
            "kernel_only": round(kernel_rate, 1),
            "lanes": n_lanes,
        }
    if platform != "cpu":
        # non-default kernel selection: measure the public API end-to-end
        # (no direct _prep_and_verify_pallas_jac dispatch to pipeline)
        v = P.verify_batch_prehashed(digests, sigs, pubs, pad_block=n_lanes)
        assert all(v)
        reps, elapsed = timed_reps(
            lambda: P.verify_batch_prehashed(digests, sigs, pubs,
                                             pad_block=n_lanes), seconds)
        rate = reps * n_lanes / elapsed
        return {
            "metric": f"verify_8k_batch_{platform}",
            "value": round(rate, 1), "unit": "sigs/s",
            "vs_baseline": round(rate / base_rate, 1),
            "lanes": n_lanes,
            "note": f"PALLAS_KERNEL={P.PALLAS_KERNEL}: sync API path",
        }

    from upow_tpu import native

    if native.load() is not None:
        out = native.p256_verify_batch(digests, sigs, pubs)  # warm
        assert out is not None and all(out)
        reps, elapsed = timed_reps(
            lambda: native.p256_verify_batch(digests, sigs, pubs), seconds)
        rate = reps * n_lanes / elapsed
        backend = "native"
    else:
        v = P.verify_batch_prehashed(digests, sigs, pubs, pad_block=128)
        assert all(v)
        reps, elapsed = timed_reps(
            lambda: P.verify_batch_prehashed(digests, sigs, pubs,
                                             pad_block=128),
            seconds, max_reps=64)
        rate = reps * n_lanes / elapsed
        backend = "jnp"
    return {
        "metric": f"verify_batch_{backend}_cpu",
        "value": round(rate, 1), "unit": "sigs/s",
        "vs_baseline": round(rate / base_rate, 1),
        "lanes": n_lanes,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default=None)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--batch", type=int, default=0,
                    help="0 = 2^28")
    ap.add_argument("--depth", type=int, default=2,
                    help="pipelined dispatches in flight")
    ap.add_argument("--trace-dir", default=None,
                    help="capture a jax.profiler trace of the measurement")
    args = ap.parse_args()

    # Any node built inside a bench-driven process inherits this:
    # watchtower alert_fired records land in the same rotated
    # .bench_events.jsonl, so paging incidents and bench arms
    # interleave on one timeline.
    os.environ.setdefault("UPOW_WATCHTOWER_BENCH_EVENTS", _BENCH_EVENTS)

    # One arm, through the device runtime (which also enables the
    # persistent compile cache).  No chip, no number: there is no CPU
    # re-execution and no carried-forward device reading.
    from upow_tpu.device import runtime as device_runtime

    try:
        info = device_runtime.start("tpu")
    except device_runtime.DeviceUnavailable as e:
        _record_bench_event("bench_arm", platform="none", reason=str(e))
        sys.stderr.write(f"bench.py: {e}\n")
        return 3
    platform = info["platform"]
    _record_bench_event("bench_arm", platform=platform,
                        device_kind=info["device_kind"],
                        device_count=info["device_count"])
    if args.batch == 0:
        args.batch = 1 << 28
    backend = args.backend or "pallas"

    from upow_tpu.core import curve, point_to_string
    from upow_tpu.core.header import BlockHeader
    from upow_tpu.core.merkle import merkle_root
    from upow_tpu.crypto import SENTINEL, make_template, target_spec
    from upow_tpu.crypto import sha256 as sk

    _, pub = curve.keygen(rng=0xBE7C)
    header = BlockHeader(
        previous_hash=bytes(range(32)).hex(),
        address=point_to_string(pub),
        merkle_root=merkle_root([]),
        timestamp=1_753_791_000,
        difficulty_x10=90,  # difficulty 9: no realistic hit, pure throughput
        nonce=0,
    )
    template = make_template(header.prefix_bytes())
    spec = target_spec(header.previous_hash, "9.0")

    if backend in ("native", "python"):
        # host loops: synchronous search over successive ranges
        from upow_tpu.mine.engine import MiningJob, _make_searcher

        job = MiningJob(header.prefix_bytes(), header.previous_hash, "9.0")
        searcher = _make_searcher(job, backend)
        batch = min(args.batch, 1 << 22 if backend == "native" else 1 << 14)
        searcher(0, batch)  # warmup (compiles the C++ ext on first use)
        t0 = time.perf_counter()
        hashes = 0
        base = 0
        while time.perf_counter() - t0 < args.seconds:
            searcher(base, batch)
            base = (base + batch) % (1 << 31)
            hashes += batch
        mhs = hashes / (time.perf_counter() - t0) / 1e6
    else:
        search = (sk.pow_search_pallas if backend == "pallas"
                  else sk.pow_search_jnp)

        # warmup/compile
        r = search(template, spec, nonce_base=0, batch=args.batch)
        _ = int(r)

        # pipelined measurement: keep `depth` dispatches in flight so the
        # chip never idles on the host round-trip (the production
        # engine.mine loop does the same)
        from upow_tpu.benchutil import pipelined_loop
        from upow_tpu.trace import profile

        base = [0]

        def dispatch():
            r = search(template, spec, nonce_base=base[0], batch=args.batch)
            base[0] = (base[0] + args.batch) % (1 << 32)
            return r

        with profile(args.trace_dir):
            rounds, elapsed = pipelined_loop(
                dispatch, lambda r: int(r), args.seconds,
                depth=max(1, args.depth))
            mhs = rounds * args.batch / elapsed / 1e6

    baseline = _baseline_python_mhs(header.prefix_bytes())
    result = {
        "metric": f"sha256_pow_search_{backend}_{platform}",
        "value": round(mhs, 3),
        "unit": "MH/s",
        "vs_baseline": round(mhs / baseline, 1),
    }
    # second flagship kernel in the same driver-captured line
    try:
        result["verify"] = _measure_verify(platform,
                                           min(args.seconds, 10.0))
    except Exception as e:
        traceback.print_exc()
        result["verify"] = {"error": f"{type(e).__name__}: {e}"[:300]}

    result["device"] = {"platform": platform, "kind": info["device_kind"],
                        "count": info["device_count"]}
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    try:
        raise SystemExit(main())
    except SystemExit:
        raise
    except BaseException as e:  # leave a parseable line, and fail
        traceback.print_exc()
        print(json.dumps({
            "metric": "sha256_pow_search_error",
            "value": 0.0, "unit": "MH/s", "vs_baseline": 0.0,
            "error": f"{type(e).__name__}: {e}"[:300],
        }))
        raise SystemExit(1)
