"""The five BASELINE.json measurement configs plus the chain-level
configs, one JSON line each.

    python bench_suite.py [--configs 1,...,9] [--seconds N]

1. miner single-block sha256 at difficulty 1 (CPU reference loop)
2. fractional difficulty 6.3 mine (charset-restricted prefix match)
3. 8k-tx block P-256 ECDSA batch-verify
4. full-chain replay validate (rebuild_utxos + fingerprint oracle)
5. mesh-sharded nonce search at difficulty 8 (all visible devices)
6. full 8,160-tx block accept through BlockManager, cold (signatures
   never seen) and warm (every tx intake-verified first — the gossip
   profile, where the verdict cache removes signature work)
7. host-vs-device batched txid hashing crossover (sync pages)
8. push_tx intake over real localhost HTTP (per-tx gossip ingest)
9. end-to-end HTTP chain sync, wire to state (cold catch-up)
10. coalesced push_tx waves through the micro-batching intake
11. perf observatory: wallet-population loadgen SLO + kernel artifact
12. verify_pipeline: pipelined verify engine (coalesced front + verdict
    cache, steady state) vs serial per-tx host dispatch + differential
13. readpath: block-anchored hot-state read cache vs the bypassed SQL
    path under block cadence, byte-identity differential built in
14. coresidency: miner + block verify + mempool intake sharing ONE
    device runtime — cross-source coalescing and fairness deltas,
    byte-identity differential built in
15. accept_resident: end-to-end 8k-tx block accept, SQL membership
    path vs the HBM-resident fused accept (device probe + digest prep
    in one dispatch), byte-identity differential incl. forced reorg +
    re-accept built in
16. mining_mesh: resident mesh-sharded nonce search (one compiled SPMD
    program, job fields as runtime data) vs the serial single-device
    path — bit-identity differential over seeded jobs built in, plus
    per-shard-count hashrate rows

``bench.py`` stays the driver-facing single-line headline (sha256
search + the verify sub-metric); this suite is the full scoreboard.
Each line mirrors bench.py's shape:
``{"metric", "value", "unit", "vs_baseline"}``.
"""

import argparse
import asyncio
import hashlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


_PLATFORM = None


def _platform() -> str:
    """Probe the backend once (shared logic: upow_tpu.benchutil) —
    'hung' skips the device-bound configs rather than wedging the run."""
    global _PLATFORM
    if _PLATFORM is None:
        from upow_tpu.benchutil import probe_platform

        _PLATFORM = probe_platform(90.0) or "hung"
    return _PLATFORM


def _emit(metric, value, unit, baseline, direction=None):
    line = {
        "metric": metric, "value": round(value, 3), "unit": unit,
        "vs_baseline": round(value / baseline, 1) if baseline else None,
    }
    if direction:
        # explicit gate direction (upow_tpu.loadgen.gate honors it over
        # its name inference — "speedup_p99" would otherwise read as a
        # latency)
        line["direction"] = direction
    print(json.dumps(line), flush=True)


def _python_loop_mhs(prefix: bytes, seconds: float = 1.0) -> float:
    from upow_tpu.benchutil import python_loop_mhs

    return python_loop_mhs(prefix, seconds)


def _job(difficulty: str, rng: int = 0xBE7C):
    from upow_tpu.core import curve, point_to_string
    from upow_tpu.core.header import BlockHeader
    from upow_tpu.core.merkle import merkle_root
    from upow_tpu.mine.engine import MiningJob

    _, pub = curve.keygen(rng=rng)
    prev = hashlib.sha256(rng.to_bytes(4, "big")).hexdigest()
    header = BlockHeader(
        previous_hash=prev, address=point_to_string(pub),
        merkle_root=merkle_root([]), timestamp=1_753_791_000,
        difficulty_x10=int(float(difficulty) * 10), nonce=0)
    return MiningJob(header.prefix_bytes(), prev, difficulty)


def config1_cpu_reference(seconds: float):
    """Reference-shaped hashlib loop (miner.py:83-98) at difficulty 1:
    verifies a block is found, reports the sustained loop rate (a
    difficulty-1 hit lands in ~16 hashes, far too few to time)."""
    from upow_tpu.mine.engine import mine

    job = _job("1.0")
    result = mine(job, "python", batch=1 << 14, ttl=seconds * 10)
    assert result.nonce is not None and job.check(result.nonce)
    _emit("mine_d1_python_cpu", _python_loop_mhs(job.prefix, seconds),
          "MH/s", None)


def config2_fractional(seconds: float, backend: str):
    """Difficulty 6.3: the fractional charset restricts the 7th nibble."""
    from upow_tpu.mine.engine import mine

    job = _job("6.3")
    batch = 1 << 26 if backend == "pallas" else 1 << 20
    result = mine(job, backend, batch=batch, ttl=seconds * 6)
    base = _python_loop_mhs(job.prefix)
    _emit(f"mine_d6.3_{backend}_{_platform()}",
          result.hashrate / 1e6, "MH/s", base)
    if result.nonce is not None:
        assert job.check(result.nonce)


def config3_batch_verify(seconds: float):
    """8k-signature block verify (the reference's per-input fastecdsa
    loop, transaction_input.py:100-109, measures ~2-6k/s/core)."""
    from upow_tpu.benchutil import python_verify_rate, verify_fixture
    from upow_tpu.crypto import p256

    digests, sigs, pubs, msgs = verify_fixture(8192, n_unique=256)

    # host baseline: pure-python ECDSA verify, short sample
    base_rate = python_verify_rate(msgs, sigs, pubs)

    v = p256.verify_batch_prehashed(digests, sigs, pubs, pad_block=8192)
    assert all(v)
    t0 = time.perf_counter()
    reps = 0
    while time.perf_counter() - t0 < seconds:
        v = p256.verify_batch_prehashed(digests, sigs, pubs, pad_block=8192)
        reps += 1
    rate = reps * 8192 / (time.perf_counter() - t0)
    _emit(f"verify_8k_batch_{_platform()}", rate, "sigs/s", base_rate)

    # kernel-only split (host prep + transfer excluded): how much of the
    # end-to-end gap is the device program vs the host pipeline
    import jax

    import upow_tpu.crypto.p256 as P

    captured = {}
    orig_pallas, orig_jnp = P._prep_and_verify_pallas, P._prep_and_verify_jnp
    orig_jac = P._prep_and_verify_pallas_jac

    def cap_pallas(*a, **kw):
        captured["call"] = lambda: orig_pallas(*a, **kw)
        return orig_pallas(*a, **kw)

    def cap_jac(*a, **kw):
        captured["call"] = lambda: orig_jac(*a, **kw)
        return orig_jac(*a, **kw)

    def cap_jnp(*a, **kw):
        captured["call"] = lambda: orig_jnp(*a, **kw)
        return orig_jnp(*a, **kw)

    P._prep_and_verify_pallas, P._prep_and_verify_jnp = cap_pallas, cap_jnp
    P._prep_and_verify_pallas_jac = cap_jac
    try:
        p256.verify_batch_prehashed(digests, sigs, pubs, pad_block=8192,
                                    scalar_prep="device")
    finally:
        P._prep_and_verify_pallas, P._prep_and_verify_jnp = (orig_pallas,
                                                             orig_jnp)
        P._prep_and_verify_pallas_jac = orig_jac
    if "call" in captured:
        jax.block_until_ready(captured["call"]())
        t0 = time.perf_counter()
        reps = 0
        while time.perf_counter() - t0 < seconds:
            jax.block_until_ready(captured["call"]())
            reps += 1
        krate = reps * 8192 / (time.perf_counter() - t0)
        _emit(f"verify_8k_kernel_{_platform()}", krate, "sigs/s", base_rate)

    # pipelined end-to-end: host packing of batch k+1 overlaps the device's
    # batch k (chain-sync batch-ingest profile; also hides the per-sync
    # host round trip).  TPU-only, and only when the
    # production dispatch unit (the fused pallas-jac program) is active;
    # a kernel failure skips the metric rather than voiding the config's
    # earlier lines (no _pallas_or_jnp safety net on this direct path).
    if _platform() == "tpu" and P.PALLAS_KERNEL == "jac":
        tile = P._pick_tile(8192)
        depth = 2

        def dispatch():
            inputs, *_meta = P._pack_device_inputs(digests, sigs, pubs, 8192)
            # w passed explicitly: the jitted default binds _WINDOW at
            # module load, NOT the PALLAS_JAC_WINDOW knob
            return P._prep_and_verify_pallas_jac(
                inputs, tile=tile, w=P.PALLAS_JAC_WINDOW)

        def check(res):
            res = np.asarray(res)
            assert bool(res[0].all()) and not bool(res[1].any())

        try:
            jax.block_until_ready(dispatch())  # warm
            from upow_tpu.benchutil import pipelined_loop

            reps, elapsed = pipelined_loop(dispatch, check, seconds, depth)
            _emit(f"verify_8k_pipelined_{_platform()}",
                  reps * 8192 / elapsed, "sigs/s", base_rate)
        except Exception:
            import traceback

            traceback.print_exc(file=sys.stderr)


def config4_replay(seconds: float):
    """Full-chain replay: mine a chain with sends, wipe the UTXO tables,
    rebuild from the tx log, check the fingerprint oracle."""
    from decimal import Decimal

    from upow_tpu.core import clock, curve, difficulty, point_to_string
    from upow_tpu.core.constants import SMALLEST
    from upow_tpu.core.header import BlockHeader
    from upow_tpu.core.merkle import merkle_root
    from upow_tpu.core.tx import Tx, TxInput, TxOutput
    from upow_tpu.mine.engine import MiningJob, mine
    from upow_tpu.state import ChainState
    from upow_tpu.verify import BlockManager
    from upow_tpu.wallet.builders import WalletBuilder

    difficulty.START_DIFFICULTY = Decimal("1.0")
    GENESIS_PREV = (18_884_643).to_bytes(32, "little").hex()

    async def scenario():
        state = ChainState()
        manager = BlockManager(state, sig_backend="host")
        builder = WalletBuilder(state)
        d, pub = curve.keygen(rng=0xC0DE)
        addr = point_to_string(pub)
        _, pub2 = curve.keygen(rng=0xC0DF)
        addr2 = point_to_string(pub2)
        n_blocks = 60
        for i in range(n_blocks):
            clock.advance(60)
            txs = []
            if i > 2 and i % 2:
                txs = [await builder.create_transaction(0xC0DE, addr2, "0.5")]
                for t in txs:
                    await state.add_pending_transaction(t)
                txs = await state.get_pending_transactions_limit(hex_only=False)
            diff, last = await manager.calculate_difficulty()
            prev = last["hash"] if last else GENESIS_PREV
            header = BlockHeader(
                previous_hash=prev, address=addr,
                merkle_root=merkle_root(txs), timestamp=clock.timestamp(),
                difficulty_x10=int(diff * 10), nonce=0)
            if last:
                r = mine(MiningJob(header.prefix_bytes(), prev, diff),
                         "python", batch=1 << 14, ttl=600)
                header.nonce = r.nonce
            assert await manager.create_block(header.hex(), txs, errors=[])
        want = await state.get_unspent_outputs_hash()
        t0 = time.perf_counter()
        await state.rebuild_utxos()
        dt = time.perf_counter() - t0
        assert await state.get_unspent_outputs_hash() == want
        state.close()
        return n_blocks / dt

    rate = asyncio.run(scenario())
    clock.reset()
    _emit("replay_validate", rate, "blocks/s", None)


def config5_sharded(seconds: float):
    """Mesh-sharded difficulty-8 search over every visible device."""
    import jax

    from upow_tpu.crypto import sha256 as sk
    from upow_tpu.parallel import make_mesh, pow_search_sharded

    job = _job("8.0")
    template = sk.make_template(job.prefix)
    spec = sk.target_spec(job.previous_hash, "8.0")
    mesh = make_mesh()
    n_dev = len(mesh.devices.ravel())
    # 2^28/device matches bench.py's production round size (raised from
    # 2^26 together with pipelining — TPU numbers from before that change
    # are not comparable under this metric name)
    per_dev = (1 << 28) if _platform() == "tpu" else (1 << 17)
    _ = int(pow_search_sharded(template, spec, 0, per_dev, mesh))
    # pipelined like the production mining loop (engine.mine, bench.py):
    # two rounds in flight hide the host<->device sync round trip
    from upow_tpu.benchutil import pipelined_loop

    base = [0]

    def dispatch():
        r = pow_search_sharded(template, spec, base[0], per_dev, mesh)
        base[0] = (base[0] + per_dev * n_dev) % (1 << 32)
        return r

    rounds, elapsed = pipelined_loop(dispatch, lambda r: int(r), seconds)
    rate = rounds * per_dev * n_dev / elapsed / 1e6
    base_rate = _python_loop_mhs(job.prefix)
    _emit(f"mine_d8_sharded_{n_dev}x_{_platform()}", rate, "MH/s", base_rate)


def _python_verify_baseline(seconds: float = 1.0) -> float:
    """Serial pure-python ECDSA verify rate — the baseline convention
    for the accept/intake/sync configs (the reference's dominant per-tx
    cost is one fastecdsa verify per input)."""
    from upow_tpu.core import curve

    dd, bpub = curve.keygen(rng=0xBA5E)
    sig = curve.sign(b"base", dd)
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < seconds:
        curve.verify(sig, b"base", bpub)
        n += 1
    return n / (time.perf_counter() - t0)


async def _chain_with_utxo_fanout(n_fan: int, n_per: int, rng_key: int):
    """Funded-chain scaffolding, now shared with the loadgen fixture —
    moved to upow_tpu.benchutil.chain_with_utxo_fanout."""
    from upow_tpu.benchutil import chain_with_utxo_fanout

    return await chain_with_utxo_fanout(n_fan, n_per, rng_key)


def _leaf_spends(parents, addr, d, pub):
    from upow_tpu.benchutil import leaf_spends

    return leaf_spends(parents, addr, d, pub)


def config6_block8k(seconds: float):
    """Full 8k-tx block accept, end to end through BlockManager: header +
    PoW checks, per-tx rules, ONE batched signature dispatch, batched
    UTXO double-spend set-diffs, and all state writes.  This is the
    README design point the reference never demonstrates (~8,300 tx per
    2 MB block, README.md:26-28; its accept path verifies signatures
    serially per input, transaction_input.py:100-109)."""
    from upow_tpu.core import curve

    async def scenario():
        # 255 x 32 = 8160 spendable leaf outputs
        state, manager, d, pub, addr, mids, mine_block = \
            await _chain_with_utxo_fanout(255, 32, 0xB10C)

        # block 4 (measured, cold): 8160 txs, each 1-in-1-out, signatures
        # never seen before — the worst-case accept
        def leaf_spends(parents):
            return _leaf_spends(parents, addr, d, pub)

        leaves = leaf_spends(mids)
        dt_cold = await mine_block(leaves)

        # block 5 (measured, warm): same shape, but every tx was verified
        # at "intake" first — the gossip profile, where the verdict cache
        # makes block accept pay zero signature work
        from upow_tpu.verify.txverify import TxVerifier, run_sig_checks

        verifier = TxVerifier(state)
        leaves2 = leaf_spends(leaves)
        for t in leaves2:
            c = await verifier.collect_sig_checks(t)
            if c is None:
                raise RuntimeError("warm-path tx failed to collect checks")
            # one call per tx, as real push_tx intake does — small batches
            # resolve to the host path, whose verdicts are the ones the
            # cache keeps (device verdicts are deliberately not cached)
            if not all(run_sig_checks(c, backend="auto")):
                raise RuntimeError("warm-path intake verification failed")
        dt_warm = await mine_block(leaves2)

        assert await state.get_next_block_id() == 6
        state.close()
        return len(leaves) / dt_cold, len(leaves2) / dt_warm

    # baseline: the reference's accept path verifies each input serially
    # (fastecdsa in C there; our measured pure-python loop here is the
    # same-host stand-in, consistent with the other configs)
    base_rate = _python_verify_baseline(seconds)

    rate_cold, rate_warm = asyncio.run(scenario())
    from upow_tpu.core import clock

    clock.reset()
    _emit(f"block_accept_8k_{_platform()}", rate_cold, "tx/s", base_rate)
    _emit(f"block_accept_8k_warm_{_platform()}", rate_warm, "tx/s", base_rate)


def config8_intake(seconds: float):
    """push_tx intake over real localhost HTTP: JSON parse + wire parse
    + rules + signature verify (native C++ on the host path) + pending
    insert + gossip spawn, one round trip per tx — the reference's
    per-tx gossip ingest cost (main.py:267-323)."""
    import tempfile

    from aiohttp.test_utils import TestClient, TestServer

    from upow_tpu.config import Config
    from upow_tpu.core import clock, curve
    from upow_tpu.node.app import Node

    N_TX = 2048  # fan a coinbase into this many spendable outputs

    async def scenario():
        # 10 x 224 = 2240 leaf outputs (<=255 per tx)
        state, manager, d, pub, addr, mids, _mine = \
            await _chain_with_utxo_fanout(10, 224, 0x17A4)
        txs = _leaf_spends(mids, addr, d, pub)
        assert len(txs) >= N_TX
        payloads = [t.hex() for t in txs[:N_TX]]

        cfg = Config()
        with tempfile.TemporaryDirectory() as tmp:
            cfg.node.db_path = ""
            cfg.node.seed_url = ""
            cfg.node.peers_file = f"{tmp}/nodes.json"
            cfg.node.ip_config_file = ""
            cfg.log.path = ""
            cfg.log.console = False
            node = Node(cfg, state=state)
            server = TestServer(node.app)
            await server.start_server()
            client = TestClient(server)
            node.started = True
            node.rate_limiter.enabled = False  # measuring us, not limits
            try:
                # warm one request (route setup, first-parse imports) —
                # outside the timed window AND the numerator
                r = await (await client.post(
                    "/push_tx", json={"tx_hex": payloads[0]})).json()
                assert r.get("ok"), r
                t0 = time.perf_counter()
                done = 0
                for p in payloads[1:]:
                    r = await (await client.post(
                        "/push_tx", json={"tx_hex": p})).json()
                    assert r.get("ok"), r
                    done += 1
                    if time.perf_counter() - t0 > seconds:
                        break
                elapsed = time.perf_counter() - t0
            finally:
                await client.close()
                await server.close()
                await node.close()
        return done / elapsed

    # baseline: serial pure-python verify, one per tx (the dominant
    # reference-side cost of intake)
    base_rate = _python_verify_baseline()

    rate = asyncio.run(scenario())
    clock.reset()
    _emit(f"push_tx_intake_{_platform()}", rate, "tx/s", base_rate)


def config10_coalesced_intake(seconds: float):
    """Concurrent push_tx through the coalescing mempool intake
    (upow_tpu/mempool/intake.py): waves of simultaneous HTTP requests
    share one signature dispatch per micro-batch instead of paying one
    per tx — the continuous-batching win over config 8's serial
    round-trips, measured on the same wire path."""
    import tempfile

    from aiohttp.test_utils import TestClient, TestServer

    from upow_tpu.config import Config
    from upow_tpu.core import clock
    from upow_tpu.node.app import Node

    N_TX = 2048
    WAVE = 64  # concurrent pushers per wave

    async def scenario():
        state, manager, d, pub, addr, mids, _mine = \
            await _chain_with_utxo_fanout(10, 224, 0xC0A1)
        txs = _leaf_spends(mids, addr, d, pub)
        assert len(txs) >= N_TX
        payloads = [t.hex() for t in txs[:N_TX]]

        cfg = Config()
        with tempfile.TemporaryDirectory() as tmp:
            cfg.node.db_path = ""
            cfg.node.seed_url = ""
            cfg.node.peers_file = f"{tmp}/nodes.json"
            cfg.node.ip_config_file = ""
            cfg.log.path = ""
            cfg.log.console = False
            node = Node(cfg, state=state)
            server = TestServer(node.app)
            await server.start_server()
            client = TestClient(server)
            node.started = True
            node.rate_limiter.enabled = False

            async def push(p):
                r = await (await client.post(
                    "/push_tx", json={"tx_hex": p})).json()
                assert r.get("ok"), r

            try:
                await push(payloads[0])  # warm, untimed
                t0 = time.perf_counter()
                done = 0
                for i in range(1, len(payloads), WAVE):
                    wave = payloads[i:i + WAVE]
                    await asyncio.gather(*[push(p) for p in wave])
                    done += len(wave)
                    if time.perf_counter() - t0 > seconds:
                        break
                elapsed = time.perf_counter() - t0
            finally:
                await client.close()
                await server.close()
                await node.close()
        return done / elapsed

    base_rate = _python_verify_baseline()

    rate = asyncio.run(scenario())
    clock.reset()
    _emit(f"push_tx_coalesced_{_platform()}", rate, "tx/s", base_rate)


def config11_perf_observatory(seconds: float):
    """The perf observatory: seeded wallet-population loadgen against
    the in-process node (Zipf reads, miner polling, push_tx bursts, ws
    churn) merged with kernel benches into one artifact
    (``observatory.json``) that the regression gate consumes.  Emits a
    suite-shaped line per endpoint so the driver's capture carries the
    SLO scoreboard too."""
    from upow_tpu.loadgen.observatory import (append_progress,
                                              run_observatory,
                                              write_artifact)
    from upow_tpu.loadgen.population import PopulationSpec

    spec = PopulationSpec(duration=min(seconds, 4.0))
    artifact = run_observatory(spec, bench_seconds=min(seconds / 4, 1.0))
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "observatory.json")
    write_artifact(artifact, out_path)
    progress = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "PROGRESS.jsonl")
    append_progress(artifact, progress)

    for ep, row in sorted(artifact["slo"]["endpoints"].items()):
        _emit(f"slo_{ep}_req_s", row["req_s"] or 0.0, "req/s", None)
        _emit(f"slo_{ep}_p95", row["p95_ms"], "ms", None)


def config12_verify_pipeline(seconds: float):
    """Pipelined block-verify engine vs the serial per-tx dispatch on
    the SAME host backend (ISSUE 7 acceptance): micro-batched
    submissions coalesced through the shared dispatch front with the
    verdict cache live (steady-state gossip profile) against one
    cache-bypassed ``verify_batch_native_cpu``-path call per tx.  The
    bench asserts byte-identical accept/reject verdicts between the two
    paths over >=1k mixed valid/invalid signatures before emitting."""
    from upow_tpu.benchutil import verify_pipeline_bench

    r = verify_pipeline_bench(seconds=min(seconds / 4, 1.0))
    assert r["verdicts_equal"], \
        "pipelined verdicts diverged from the serial path"
    _emit(f"verify_pipeline_{_platform()}", r["pipelined_tx_s"], "tx/s",
          r["serial_tx_s"])
    _emit(f"verify_pipeline_serial_{_platform()}", r["serial_tx_s"],
          "tx/s", None)


def config13_readpath_cache(seconds: float):
    """Block-anchored hot-state read cache (ISSUE 9 acceptance):
    Zipfian wallet readers + miner polling against the in-process node,
    the SAME deterministic schedule replayed bypassed and cached while
    blocks land at a fixed cadence (every window re-pays invalidation).
    The scenario's built-in differential — cached vs recomputed bodies
    byte-identical at every stage, including across a forced
    ``remove_blocks`` reorg — must hold or the run refuses to emit."""
    import asyncio

    from upow_tpu.loadgen.readpath import ReadpathSpec, run_readpath

    r = asyncio.run(run_readpath(ReadpathSpec()))
    assert r["differential"]["ok"], \
        "readpath differential diverged: cached body != recomputed body"
    _emit("readpath_bypass_p99", r["bypass"]["p99_ms"], "ms", None,
          direction="lower")
    _emit("readpath_cached_p99", r["cached"]["p99_ms"], "ms", None,
          direction="lower")
    _emit("readpath_speedup_p99", r["speedup_p99"], "x", None,
          direction="higher")
    _emit("readpath_hit_ratio", r["cached_pass"]["hit_ratio"], "ratio",
          None, direction="higher")


def config14_coresidency(seconds: float):
    """Co-residency on the device runtime (ISSUE 10 acceptance):
    saturating miner stream + block-verify + mempool-intake sig batches
    on ONE DeviceRuntime, with the built-in differential — every
    concurrent verdict byte-identical to the serial host reference AND
    a serial one-dispatch-per-batch pass — required before any number
    is emitted.  Headlines: cross-source coalescing ratio (fewer
    dispatches), shared-dispatch occupancy, and the block-verify queue
    wait under the flood (bounded wait = no starvation)."""
    from upow_tpu import telemetry
    from upow_tpu.loadgen.coresidency import (CoresidencySpec,
                                              run_coresidency)

    telemetry.configure()
    r = run_coresidency(
        CoresidencySpec() if seconds >= 4 else CoresidencySpec.smoke())
    assert r["differential"]["ok"], \
        "coresidency differential diverged from the serial paths"
    _emit("coresidency_coalesce_ratio", r["coalesce_ratio"], "x", None,
          direction="higher")
    _emit("coresidency_dispatch_reduction", r["dispatch_reduction"], "x",
          None, direction="higher")
    _emit("coresidency_occupancy", r["concurrent"]["occupancy"] or 0.0,
          "ratio", None, direction="higher")
    _emit("coresidency_verify_wait_p99",
          r["concurrent"]["verify_wait_p99_ms"], "ms", None,
          direction="lower")


def config15_accept_resident(seconds: float):
    """HBM-resident UTXO accept path (ISSUE 11 acceptance): end-to-end
    8k-tx block accept through the host-round-trip path (per-table SQL
    membership scans) vs the fused resident path (device membership
    probe + digest prep in ONE runtime dispatch, shadow map consulted
    only on fingerprint ambiguity).  The byte-identity differential —
    resident probe vs host shadow map vs SQL, plus the unspent-set
    fingerprint across a FORCED REORG and re-accept — must hold or the
    run refuses to emit (the helper zeroes the speedups too)."""
    from upow_tpu.benchutil import accept_resident_bench

    r = accept_resident_bench(seconds=min(seconds / 4, 1.0))
    assert r["differential_ok"], \
        "resident accept differential diverged from the SQL path"
    _emit(f"accept_resident_8k_{_platform()}", r["resident_tx_s"], "tx/s",
          r["serial_tx_s"])
    _emit(f"accept_serial_8k_{_platform()}", r["serial_tx_s"], "tx/s",
          None)
    _emit(f"accept_scan_speedup_{_platform()}", r["scan_speedup"], "x",
          None, direction="higher")
    _emit("accept_shadow_consults", float(r["shadow_consults"]), "",
          None, direction="lower")


def config16_mining_mesh(seconds: float):
    """Resident mesh-sharded nonce search (ISSUE 12 acceptance): one
    compiled SPMD program across the dp mesh, every job field a traced
    argument (a chain-tip change never recompiles), dispatched through
    the device runtime under source "mine".  The bit-identity
    differential — mesh min-hit == serial jnp min-hit per window over
    >= 3 seeded jobs, plus disjoint shard coverage from the engine's
    own accounting — must hold or the sharded headline and the speedup
    are zeroed (the gate trips on correctness, not just slowdowns)."""
    from upow_tpu.benchutil import mining_mesh_bench

    batch = (1 << 22) if _platform() == "tpu" else (1 << 14)
    r = mining_mesh_bench(seconds=min(seconds / 2, 4.0),
                          batch_per_device=batch,
                          shard_counts=(1, 2, 4, 8))
    assert r["differential_ok"], \
        "mesh search diverged from the serial path"
    _emit(f"mine_mesh_sharded_{r['n_devices']}x_{_platform()}",
          r["sharded_mhs"], "MH/s", r["serial_mhs"], direction="higher")
    _emit(f"mine_mesh_serial_{_platform()}", r["serial_mhs"], "MH/s",
          None, direction="higher")
    _emit(f"mine_mesh_speedup_{_platform()}", r["speedup"], "x", None,
          direction="higher")
    for row in r["per_shard_counts"]:
        _emit(f"mine_mesh_{row['shards']}shard_{_platform()}",
              row["mhs"], "MH/s", None, direction="higher")


def config9_sync(seconds: float):
    """End-to-end chain sync over real localhost HTTP: node B downloads
    node A's chain in pages (prefetch pipeline, page-level signature
    dispatch, batched txid seeding per device config) and accepts every
    block — the full reference catch-up path (main.py:97-150) measured
    as wire-to-state throughput."""
    import tempfile

    from aiohttp.test_utils import TestServer

    from upow_tpu.config import Config
    from upow_tpu.core import clock
    from upow_tpu.node.app import Node
    from upow_tpu.state import ChainState

    N_BLOCKS = 240  # after the 3 fan-out blocks; 2 spends per block

    async def scenario():
        state, manager, d, pub, addr, mids, mine_block = \
            await _chain_with_utxo_fanout(10, 64, 0x57AC)
        leaves = _leaf_spends(mids, addr, d, pub)
        assert len(leaves) >= 2 * N_BLOCKS
        it = iter(leaves)
        for _ in range(N_BLOCKS):
            await mine_block([next(it), next(it)])
        total_blocks = 3 + N_BLOCKS
        # block 1 is coinbase-only; then the fan (1 tx), the mids (10),
        # and 2 spends per measured block — plus one coinbase each
        total_txs = sum(1 + n for n in ([0, 1, 10] + [2] * N_BLOCKS))

        def node_cfg(tmp, name):
            cfg = Config()
            cfg.node.db_path = ""
            cfg.node.seed_url = ""
            cfg.node.peers_file = f"{tmp}/{name}-nodes.json"
            cfg.node.ip_config_file = ""
            cfg.node.sync_fetch_interval = 0.0
            cfg.node.sync_page = 64  # several pages: prefetch pipeline on
            cfg.log.path = ""
            cfg.log.console = False
            return cfg

        with tempfile.TemporaryDirectory() as tmp:
            node_a = Node(node_cfg(tmp, "a"), state=state)
            server_a = TestServer(node_a.app)
            await server_a.start_server()
            node_a.started = True
            node_a.rate_limiter.enabled = False
            # node B needs no HTTP server: it syncs as a CLIENT of A
            node_b = Node(node_cfg(tmp, "b"), state=ChainState())
            node_b.started = True
            try:
                t0 = time.perf_counter()
                ok = await node_b.sync_blockchain(
                    f"http://127.0.0.1:{server_a.port}")
                elapsed = time.perf_counter() - t0
                assert ok is True, ok
                assert (await node_b.state.get_next_block_id()
                        == total_blocks + 1)
                assert (await node_a.state.get_unspent_outputs_hash()
                        == await node_b.state.get_unspent_outputs_hash())
            finally:
                await server_a.close()
                await node_a.close()
                await node_b.close()
        return total_blocks / elapsed, total_txs / elapsed

    # baseline convention (config 6): serial pure-python verify — the
    # reference's dominant per-tx catch-up cost
    base_rate = _python_verify_baseline()

    blocks_s, txs_s = asyncio.run(scenario())
    clock.reset()
    _emit(f"sync_http_blocks_{_platform()}", blocks_s, "blocks/s", None)
    _emit(f"sync_http_txs_{_platform()}", txs_s, "tx/s", base_rate)


def config7_txid_batch(seconds: float):
    """Host hashlib vs device sha256_batch_jnp for an 8k-tx page of
    ~400 B payloads — the measured crossover behind device.txid_backend
    (crypto/sha256.txid_batch; reference manager.py:365-378)."""
    import random

    from upow_tpu.crypto.sha256 import sha256_batch_jnp

    rng = random.Random(0xD1E5)
    payloads = [rng.randbytes(rng.randint(150, 600)) for _ in range(8192)]

    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < seconds:
        for p in payloads:
            hashlib.sha256(p).digest()
        n += len(payloads)
    host_rate = n / (time.perf_counter() - t0)
    _emit(f"txid_batch_host_{_platform()}", host_rate, "hash/s", None)

    sha256_batch_jnp(payloads)  # compile warmup
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < seconds:
        sha256_batch_jnp(payloads)
        n += len(payloads)
    dev_rate = n / (time.perf_counter() - t0)
    _emit(f"txid_batch_device_{_platform()}", dev_rate, "hash/s", host_rate)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="1,2,3,4,5,6")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--require-tpu", action="store_true",
                    help="exit 3 unless the real chip answers the probe")
    args = ap.parse_args()
    if args.require_tpu and _platform() in ("cpu", "hung"):
        print(json.dumps({"error": f"--require-tpu: platform={_platform()}"}),
              flush=True)
        return 3

    from upow_tpu import compile_cache

    compile_cache.enable()  # same directory as the runtime's arm

    runners = {
        "1": lambda: config1_cpu_reference(args.seconds),
        "2": lambda: config2_fractional(
            args.seconds, "pallas" if _platform() == "tpu" else "jnp"),
        "3": lambda: config3_batch_verify(args.seconds),
        "4": lambda: config4_replay(args.seconds),
        "5": lambda: config5_sharded(args.seconds),
        "6": lambda: config6_block8k(args.seconds),
        "7": lambda: config7_txid_batch(args.seconds),
        "8": lambda: config8_intake(args.seconds),
        "9": lambda: config9_sync(args.seconds),
        "10": lambda: config10_coalesced_intake(args.seconds),
        "11": lambda: config11_perf_observatory(args.seconds),
        "12": lambda: config12_verify_pipeline(args.seconds),
        "13": lambda: config13_readpath_cache(args.seconds),
        "14": lambda: config14_coresidency(args.seconds),
        "15": lambda: config15_accept_resident(args.seconds),
        "16": lambda: config16_mining_mesh(args.seconds),
    }
    needs_device = {"2", "3", "5", "7", "16"}
    failed = []
    for key in args.configs.split(","):
        key = key.strip()
        if key in needs_device and _platform() == "hung":
            print(json.dumps({
                "metric": f"config{key}_error", "value": 0.0, "unit": "",
                "vs_baseline": 0.0, "error": "jax backend hung"}), flush=True)
            failed.append(key)
            continue
        try:
            runners[key]()
        except Exception as e:  # keep the suite going; record the failure
            print(json.dumps({
                "metric": f"config{key}_error", "value": 0.0, "unit": "",
                "vs_baseline": 0.0, "error": f"{type(e).__name__}: {e}"[:200],
            }), flush=True)
            failed.append(key)
    # under --require-tpu a config that produced no numbers must fail
    # the run: all cells or non-zero
    return 3 if (args.require_tpu and failed) else 0


if __name__ == "__main__":
    raise SystemExit(main())
