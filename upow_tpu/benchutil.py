"""Shared benchmark plumbing for bench.py / bench_suite.py.

Two things both scoreboards need and must agree on:

* :func:`probe_platform` — backend detection that survives a backend
  init HANGING inside ``jax.devices()`` (exceptions are the easy
  case).  The probe runs on a daemon thread; on timeout the caller
  decides what a missing device means (device/runtime.py ``start``).
* :func:`python_loop_mhs` — the reference miner's hashlib-per-nonce
  loop (reference miner.py:83-98), the CPU baseline every
  ``vs_baseline`` field is computed against.
"""

from __future__ import annotations

import hashlib
import time
from typing import Optional


def boxed_call(fn, timeout: float):
    """DEPRECATED shim: the hang-survival idiom moved to
    :func:`upow_tpu.device.runtime.boxed_call` (the device-runtime
    service is the only sanctioned dispatcher — upowlint rule DR002
    flags new callers).  Kept delegating because bench tooling and
    tests monkeypatch ``benchutil.boxed_call`` to fake probe results;
    :func:`probe_platform` still resolves it through this module global
    so those seams keep intercepting.

    Returns ("ok", result) | ("err", exception) | ("timeout", None).
    """
    from .device.runtime import boxed_call as _boxed_call

    return _boxed_call(fn, timeout)


def text_fingerprint(text: str) -> str:
    """Short stable hash of diagnostic text (stderr tails, frame lists)
    so repeated arm failures can be grouped without comparing full
    tracebacks."""
    return hashlib.sha256(text.encode("utf-8", "replace")).hexdigest()[:12]


def traceback_fingerprint(exc: BaseException) -> str:
    """Fingerprint of an exception's traceback SHAPE (file:function per
    frame, no line numbers or message text): two arm attempts that died
    on the same code path share a fingerprint even when addresses or
    timeouts in the message differ."""
    import traceback as _tb

    frames = _tb.extract_tb(exc.__traceback__) if exc.__traceback__ else []
    sig = "|".join("%s:%s" % (f.filename.rsplit("/", 1)[-1], f.name)
                   for f in frames[-8:])
    return text_fingerprint("%s|%s" % (type(exc).__name__, sig))


def probe_platform_detail(timeout: float = 90.0) -> dict:
    """Backend probe that KEEPS the failure: returns
    ``{status, platform, seconds, error, traceback_fingerprint}`` where
    ``status`` is the boxed_call outcome ("ok" / "err" / "timeout"),
    ``platform`` is ``jax.devices()[0].platform`` (None unless ok), and ``error``
    is the actual exception text — the thing every "hung/failed" log
    line used to throw away."""
    import jax

    # module-global boxed_call on purpose: tests monkeypatch it to fake
    # probe outcomes; jax.devices() here IS the probe the runtime arms
    # through, not a stray dispatch
    t0 = time.perf_counter()
    # RC001: loop-reachable only via Node.__init__'s one-time cached
    # device probe at startup, before the node serves traffic
    status, value = boxed_call(  # upowlint: disable=DR002,RC001
        lambda: jax.devices()[0].platform, timeout)  # upowlint: disable=DR001
    detail = {"status": status, "platform": None,
              "seconds": round(time.perf_counter() - t0, 3),
              "error": None, "traceback_fingerprint": None}
    if status == "ok":
        detail["platform"] = value
    elif status == "timeout":
        detail["error"] = ("backend init still inside jax.devices() after "
                           "%.0fs (native hang; no Python exception to "
                           "show)" % timeout)
    else:  # "err": value IS the exception boxed_call caught
        detail["error"] = repr(value)
        if isinstance(value, BaseException):
            detail["traceback_fingerprint"] = traceback_fingerprint(value)
    return detail


def probe_platform(timeout: float = 90.0) -> Optional[str]:
    """Platform string of jax.devices()[0]; None if init hung or failed."""
    return probe_platform_detail(timeout)["platform"]


_PROBE_CACHE: dict = {}


def probe_detail_cached(timeout: float = 90.0) -> dict:
    """One probe per process (see :func:`probed_platform_cached`), but
    returning the full :func:`probe_platform_detail` record so callers
    can surface the real failure text instead of a bare None."""
    if "detail" not in _PROBE_CACHE:
        _PROBE_CACHE["detail"] = probe_platform_detail(timeout)
        _PROBE_CACHE["platform"] = _PROBE_CACHE["detail"]["platform"]
    return _PROBE_CACHE["detail"]


def probed_platform_cached(timeout: float = 90.0) -> Optional[str]:
    """One probe per process, shared by every jax consumer that must not
    wedge on a backend that hangs (node signature dispatch, device UTXO
    index, bench) — so a hung backend costs the process ONE timeout, not
    one per subsystem."""
    if "platform" not in _PROBE_CACHE:
        _PROBE_CACHE["platform"] = probe_detail_cached(timeout)["platform"]
    return _PROBE_CACHE["platform"]


def python_loop_mhs(prefix: bytes, seconds: float = 1.0) -> float:
    """Reference-shaped loop: one hashlib sha256 per nonce (the
    difficulty-prefix compare costs nothing next to the hash)."""
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < seconds:
        for _ in range(2000):
            hashlib.sha256(prefix + n.to_bytes(4, "little")).hexdigest()
            n += 1
    return n / (time.perf_counter() - t0) / 1e6


def verify_fixture(n_lanes: int, n_unique: int = 128, rng_base: int = 7000):
    """Shared signature-verify bench fixture (bench.py and bench_suite
    config 3): ``n_unique`` distinct keypairs/messages tiled to
    ``n_lanes`` lanes.  Returns (digests, sigs, pubs, msgs)."""
    from .core import curve

    msgs, sigs, pubs = [], [], []
    for i in range(n_unique):
        d, pub = curve.keygen(rng=rng_base + i)
        m = i.to_bytes(4, "big") * 8
        sigs.append(curve.sign(m, d))
        msgs.append(m)
        pubs.append(pub)
    k = n_lanes // n_unique
    msgs, sigs, pubs = msgs * k, sigs * k, pubs * k
    digests = [hashlib.sha256(m).digest() for m in msgs]
    return digests, sigs, pubs, msgs


def python_verify_rate(msgs, sigs, pubs, seconds: float = 1.0) -> float:
    """Pure-python ECDSA verify rate on this host (the bench baseline
    convention for the reference's per-input fastecdsa loop)."""
    from .core import curve

    n_u = len(msgs)
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < seconds:
        curve.verify(sigs[n % n_u], msgs[n % n_u], pubs[n % n_u])
        n += 1
    return n / (time.perf_counter() - t0)


def pipeline_verify_fixture(n_txs: int, n_unique: int = 128,
                            invalid_every: int = 13, rng_base: int = 9100):
    """Per-tx signature-check tuples (the txverify check shape:
    ``(digest, digest_hexform, sig, pub)``) with a deterministic mix of
    valid and invalid signatures — every ``invalid_every``-th check
    carries a corrupted ``s``, which fails BOTH verify passes (raw and
    hex-form digest) exactly like a forged wire signature would.
    ``n_unique`` keypairs/messages tiled to ``n_txs``, bench-cheap like
    :func:`verify_fixture`."""
    from .core import curve

    base = []
    for i in range(n_unique):
        d, pub = curve.keygen(rng=rng_base + i)
        m = (b"vp" + i.to_bytes(4, "big")) * 6
        digest = hashlib.sha256(m).digest()
        hexform = hashlib.sha256(m.hex().encode()).digest()
        base.append((digest, hexform, curve.sign(m, d), pub))
    checks = []
    for i in range(n_txs):
        digest, hexform, (r, s), pub = base[i % n_unique]
        if invalid_every and i % invalid_every == 0:
            s = s - 1 if s > 1 else s + 1
        checks.append((digest, hexform, (r, s), pub))
    return checks


def verify_pipeline_bench(seconds: float = 0.4, n_txs: int = 1024,
                          microbatch: int = 128) -> dict:
    """The ``verify_pipeline`` bench (ISSUE 7): pipelined engine vs the
    serial per-tx dispatch, same host backend, with a built-in
    differential check.

    * ``serial`` — one cache-bypassed ``run_sig_checks`` call per tx
      (the reference's profile: every hop re-verifies every signature
      through the same ``verify_batch_native_cpu`` host path, one tx at
      a time).
    * ``pipelined`` — micro-batched submissions coalesced through the
      shared dispatch front (verify/dispatch.py) with the verdict cache
      live, sustained over ``seconds`` after one cold populate pass —
      the engine's steady-state gossip profile, where block accept
      re-verifies intake-verified txs.  The cold pass computes every
      verdict through the identical host path, so the cache can never
      answer something the serial path would not.

    Returns serial/pipelined tx-verify/s, their ratio, and the
    differential verdict comparison over all ``n_txs`` checks (serial
    vs cold pipelined vs warm pipelined must be identical lists).
    """
    import asyncio

    from .verify import txverify
    from .verify.dispatch import get_front

    checks = pipeline_verify_fixture(n_txs)

    # serial reference: per-tx dispatch, no cache
    txverify.clear_sig_verdicts()
    t0 = time.perf_counter()
    serial_verdicts: list = []
    for c in checks:
        serial_verdicts.extend(txverify.run_sig_checks(
            [c], backend="host", use_cache=False))
    serial_rate = n_txs / (time.perf_counter() - t0)

    async def one_pass():
        front = get_front()
        outs = await asyncio.gather(*[
            front.submit(checks[i:i + microbatch], backend="host",
                         source="bench")
            for i in range(0, n_txs, microbatch)])
        return [v for out in outs for v in out]

    async def pipelined():
        txverify.clear_sig_verdicts()
        cold = await one_pass()  # intake populate pass, untimed
        t0 = time.perf_counter()
        reps, warm = 0, cold
        while time.perf_counter() - t0 < seconds:
            warm = await one_pass()
            reps += 1
        elapsed = time.perf_counter() - t0
        return cold, warm, (reps * n_txs / elapsed) if reps else 0.0

    cold_verdicts, warm_verdicts, pipe_rate = asyncio.run(pipelined())
    equal = serial_verdicts == cold_verdicts == warm_verdicts
    return {
        "serial_tx_s": round(serial_rate, 1),
        "pipelined_tx_s": round(pipe_rate, 1),
        "speedup": round(pipe_rate / serial_rate, 2) if serial_rate else None,
        "differential_txs": n_txs,
        "verdicts_equal": equal,
        "n_invalid": sum(1 for v in serial_verdicts if not v),
    }


def timed_reps(fn, seconds: float, max_reps: Optional[int] = None):
    """Repeat ``fn`` until the deadline (or ``max_reps``); returns
    (reps, elapsed).  The shared timed-loop plumbing for synchronous
    bench measurements."""
    t0 = time.perf_counter()
    reps = 0
    while time.perf_counter() - t0 < seconds and (
            max_reps is None or reps < max_reps):
        fn()
        reps += 1
    return reps, time.perf_counter() - t0


async def chain_with_utxo_fanout(n_fan: int, n_per: int, rng_key: int):
    """3-block in-memory chain fanning one coinbase into n_fan x n_per
    spendable leaf outputs — shared scaffolding for the bench_suite
    accept/intake configs and the loadgen funded-wallet fixture.
    Returns (state, manager, d, pub, addr, mids, mine_block) where
    ``mine_block(txs)`` accepts one more block and returns its accept
    seconds.  Mutates process-global difficulty/clock state; callers
    must ``clock.reset()`` when done (bench configs and the loadgen
    harness both do)."""
    import time
    from decimal import Decimal

    from .core import clock, curve, difficulty, point_to_string
    from .core.header import BlockHeader
    from .core.merkle import merkle_root
    from .core.tx import Tx, TxInput, TxOutput
    from .mine.engine import MiningJob, mine
    from .state import ChainState
    from .verify import BlockManager

    difficulty.START_DIFFICULTY = Decimal("1.0")
    genesis_prev = (18_884_643).to_bytes(32, "little").hex()

    state = ChainState()
    manager = BlockManager(state)
    d, pub = curve.keygen(rng=rng_key)
    addr = point_to_string(pub)
    pub_of = lambda _i: pub  # noqa: E731

    async def mine_block(txs):
        clock.advance(60)
        diff, last = await manager.calculate_difficulty()
        prev = last["hash"] if last else genesis_prev
        header = BlockHeader(
            previous_hash=prev, address=addr, merkle_root=merkle_root(txs),
            timestamp=clock.timestamp(), difficulty_x10=int(diff * 10),
            nonce=0)
        if last:
            r = mine(MiningJob(header.prefix_bytes(), prev, diff),
                     "python", batch=1 << 14, ttl=600)
            header.nonce = r.nonce
        errors = []
        t0 = time.perf_counter()
        ok = await manager.create_block(header.hex(), txs, errors=errors)
        dt = time.perf_counter() - t0
        assert ok, errors
        return dt

    await mine_block([])                      # block 1: coinbase to addr
    coin = (await state.get_spendable_outputs(addr))[0]
    reward = coin.amount

    per = reward // n_fan
    outs = [TxOutput(addr, per)] * (n_fan - 1)
    outs = outs + [TxOutput(addr, reward - per * (n_fan - 1))]
    fan = Tx([coin], outs).sign([d], pub_of)
    await mine_block([fan])

    mids = []
    for j in range(n_fan):
        amt = fan.outputs[j].amount
        sub = amt // n_per
        souts = [TxOutput(addr, sub)] * (n_per - 1)
        souts = souts + [TxOutput(addr, amt - sub * (n_per - 1))]
        mids.append(Tx([TxInput(fan.hash(), j)], souts).sign([d], pub_of))
    await mine_block(mids)
    return state, manager, d, pub, addr, mids, mine_block


def leaf_spends(parents, addr, d, pub):
    """One 1-in-1-out spend per output of each parent tx (the bench
    and loadgen push_tx payload generator)."""
    from .core.tx import Tx, TxInput, TxOutput

    out = []
    for m in parents:
        h = m.hash()
        for k, o in enumerate(m.outputs):
            out.append(Tx([TxInput(h, k)], [TxOutput(addr, o.amount)])
                       .sign([d], lambda _i: pub))
    return out


def accept_resident_bench(seconds: float = 0.4, n_fan: int = 255,
                          n_per: int = 32) -> dict:
    """Config 15: end-to-end 8k-tx block accept, host-round-trip path
    (per-table SQL membership) vs the HBM-resident fused accept path
    (state/device_index.py probes fused into the digest-prep dispatch),
    with the byte-identity differential — resident probe vs host shadow
    map vs SQL — checked after accept, after a FORCED REORG
    (remove_blocks), and after re-accepting the same block.  Shared by
    bench_suite config 15 and the loadgen observatory so ``make
    perf-smoke`` can enforce the same numbers.

    The speedup fields are ZEROED unless every differential passed —
    callers refuse to emit a headline from a diverged run."""
    import asyncio

    from .core import clock
    from .verify import txverify

    ABSENT = [("ff" * 32, i) for i in range(16)]

    async def scenario(resident: bool) -> dict:
        state, manager, d, pub, addr, mids, mine_block = \
            await chain_with_utxo_fanout(n_fan, n_per, 0xACC7)
        manager.fused_accept = resident
        if resident:
            state.enable_device_index()
            if not state.resident_indexes():
                raise RuntimeError("device UTXO index failed to arm")
        txs = leaf_spends(mids, addr, d, pub)
        spent = [i.outpoint for t in txs for i in t.inputs]
        created = [(t.hash(), 0) for t in txs]
        sample = spent + created + ABSENT
        pre_hash = await state.get_unspent_outputs_hash()
        txverify.clear_sig_verdicts()  # cold-signature accept, both paths
        dt = await mine_block(txs)
        out = {"n_txs": len(txs), "accept_seconds": dt,
               "utxo_hash": await state.get_unspent_outputs_hash()}

        async def parity() -> bool:
            """Resident probe vs host shadow map vs SQL, one sample."""
            idx = state.resident_indexes()["unspent_outputs"]
            dev = [bool(v) for v in idx.contains_batch(sample)]
            shadow = [bool(v) for v in idx.shadow_contains_batch(sample)]
            sql = [bool(v) for v in
                   await state.outpoints_exist(sample, "unspent_outputs")]
            return dev == shadow == sql

        # membership-scan micro-measure: the double-spend scan isolated
        # from rules/sig work — the serial path's per-accept SQL
        # round-trip vs one resident probe dispatch
        t0 = time.perf_counter()
        reps = 0
        while time.perf_counter() - t0 < seconds or reps == 0:
            if resident:
                state.resident_indexes()["unspent_outputs"] \
                    .contains_batch(sample)
            else:
                await state.outpoints_exist(sample, "unspent_outputs")
            reps += 1
        out["scan_tx_s"] = reps * len(sample) / (time.perf_counter() - t0)

        if resident:
            ok = await parity()
            # forced reorg: drop the 8k block, O(delta) index rollback —
            # the unspent-set fingerprint must return EXACTLY to its
            # pre-accept value
            await state.remove_blocks(4)
            ok = ok and await parity()
            ok = ok and pre_hash == await state.get_unspent_outputs_hash()
            # re-accept the same transactions (the re-mined header gets
            # a fresh timestamp, so its coinbase outpoint differs — the
            # three-way parity is the byte-identity check here)
            dt2 = await mine_block(txs)
            ok = ok and await parity()
            out["reaccept_seconds"] = dt2
            out["reorg_ok"] = bool(ok)
            stats = state.index_stats()
            out["shadow_consults"] = stats["shadow_consults"]
            out["twin_fingerprints"] = stats["twin_fingerprints"]
        state.close()
        return out

    # both paths must see identical per-block timestamps or the block
    # hashes (and therefore the coinbase outpoints) diverge and the
    # hash differential is meaningless — the clock base is wall time,
    # so a scenario crossing a wall-second boundary would skew the
    # second run.  Freeze to a fixed epoch before EACH path; advance(60)
    # per mined block still moves chain time on top of the frozen base.
    clock.freeze(1_700_000_000)
    serial = asyncio.run(scenario(False))
    clock.freeze(1_700_000_000)
    resident = asyncio.run(scenario(True))
    clock.reset()

    ok = bool(resident.get("reorg_ok")
              and serial["utxo_hash"] == resident["utxo_hash"]
              and serial["n_txs"] == resident["n_txs"])
    speedup = serial["accept_seconds"] / resident["accept_seconds"]
    scan_speedup = resident["scan_tx_s"] / serial["scan_tx_s"] \
        if serial["scan_tx_s"] else 0.0
    return {
        "n_txs": serial["n_txs"],
        "serial_tx_s": round(serial["n_txs"] / serial["accept_seconds"], 1),
        "resident_tx_s": round(
            resident["n_txs"] / resident["accept_seconds"], 1),
        "speedup": round(speedup, 2) if ok else 0.0,
        "scan_serial_tx_s": round(serial["scan_tx_s"], 1),
        "scan_resident_tx_s": round(resident["scan_tx_s"], 1),
        "scan_speedup": round(scan_speedup, 2) if ok else 0.0,
        "differential_ok": ok,
        "reaccept_seconds": round(resident["reaccept_seconds"], 4),
        "shadow_consults": resident["shadow_consults"],
        "twin_fingerprints": resident["twin_fingerprints"],
    }


def mining_mesh_bench(seconds: float = 0.4, n_jobs: int = 3,
                      batch_per_device: int = 1 << 12,
                      shard_counts=()) -> dict:
    """Config 16: resident mesh-sharded nonce search (mine/mesh_engine)
    vs the serial single-device jnp path, with the bit-identity
    differential built in: over ``n_jobs`` seeded jobs every mesh round
    must return EXACTLY the serial path's min-hit for the same window
    (full rounds AND a ragged tail round), and the engine's own dispatch
    accounting must show disjoint, gapless shard coverage.  Shared by
    bench_suite config 16 and the loadgen observatory so ``make
    perf-smoke`` enforces the same numbers.

    The sharded headline and the speedup are ZEROED unless every
    differential check passed — a diverged run trips the gate instead of
    reporting a fast wrong number.  ``shard_counts`` adds per-mesh-size
    hashrate rows (each extra size is one extra compile; the observatory
    smoke passes none)."""
    import random as _random
    from decimal import Decimal

    from .crypto import sha256 as sk
    from .mine.engine import MiningJob
    from .mine.mesh_engine import MeshEngine

    def seeded_job(seed: int) -> MiningJob:
        r = _random.Random(seed)
        prefix = bytes(r.randrange(256) for _ in range(104))
        prev = bytes(r.randrange(256) for _ in range(32)).hex()
        # difficulty 3: a hit lands roughly once per 4k nonces, so the
        # differential windows mix hits (at varying shards) and misses
        return MiningJob(prefix, prev, Decimal("3.0"))

    engine = MeshEngine(batch_per_device=batch_per_device)
    if not engine.arm()["armed"]:
        raise RuntimeError("mesh engine failed to arm: "
                           + (engine.arm_failure_reason or "unknown"))
    cap = engine.capacity

    ok, checks = True, 0
    template = spec = job = None
    for i in range(n_jobs):
        job = seeded_job(0xD1F0 + i)
        engine.set_job(job)
        template = sk.make_template(job.prefix)
        spec = sk.target_spec(job.previous_hash, job.difficulty)
        for start, count in ((0, cap), (1 << 20, cap),
                             (1 << 24, cap // 3 + 1)):
            got = int(engine.dispatch(start, count))
            want = int(sk.pow_search_jnp(template, spec,
                                         nonce_base=start, batch=count))
            ok = ok and got == want
            if got != int(sk.SENTINEL):
                ok = ok and job.check(got)
            checks += 1
    for rec in engine.stats()["rounds"]:
        shards = rec["shards"]
        ok = ok and shards[0][0] == rec["lo"] \
            and shards[-1][1] == rec["hi"] \
            and all(b == c for (_, b), (c, _) in zip(shards, shards[1:]))
        checks += 1

    def rate_of(dispatch_round, round_size) -> float:
        cursor = [0]

        def dispatch():
            r = dispatch_round(cursor[0], round_size)
            cursor[0] = (cursor[0] + round_size) % (1 << 31)
            return r

        int(dispatch())  # warm outside the timed window
        rounds, elapsed = pipelined_loop(dispatch, lambda r: int(r),
                                         seconds)
        return rounds * round_size / elapsed / 1e6

    sharded_mhs = rate_of(engine.dispatch, cap)
    serial_mhs = rate_of(
        lambda start, count: sk.pow_search_jnp(
            template, spec, nonce_base=start, batch=count), cap)

    rows = []
    for n in shard_counts:
        if not 1 <= n <= engine.n_devices:
            continue
        if n == engine.n_devices:
            rows.append({"shards": n, "mhs": round(sharded_mhs, 3)})
            continue
        sub = MeshEngine(mesh_devices=n,
                         batch_per_device=batch_per_device)
        if not sub.arm()["armed"]:
            continue
        sub.set_job(job)
        rows.append({"shards": n,
                     "mhs": round(rate_of(sub.dispatch, sub.capacity), 3)})

    speedup = sharded_mhs / serial_mhs if serial_mhs else 0.0
    return {
        "n_devices": engine.n_devices,
        "batch_per_device": engine.batch_per_device,
        "differential_ok": ok,
        "differential_checks": checks,
        "serial_mhs": round(serial_mhs, 3),
        "sharded_mhs": round(sharded_mhs, 3) if ok else 0.0,
        "speedup": round(speedup, 2) if ok else 0.0,
        "per_shard_counts": rows,
    }


def pipelined_loop(dispatch, finalize, seconds: float, depth: int = 2):
    """Keep up to ``depth`` async dispatches in flight until the deadline,
    then drain.  Returns (completed_rounds, elapsed) — elapsed includes
    the drain, so rate accounting stays honest.

    The canonical deadline/drain loop for device benchmarks (the mining
    engine pipelines the same way): JAX dispatch is async, so the host
    only blocks inside ``finalize`` on the oldest round while newer
    rounds execute."""
    t0 = time.perf_counter()
    done = 0
    inflight = []
    while time.perf_counter() - t0 < seconds or inflight:
        if len(inflight) < depth and time.perf_counter() - t0 < seconds:
            inflight.append(dispatch())
            continue
        if not inflight:  # deadline crossed between the two time checks
            break
        finalize(inflight.pop(0))
        done += 1
    return done, time.perf_counter() - t0
