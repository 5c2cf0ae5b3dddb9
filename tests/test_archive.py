"""Cold-block archival tier (docs/ARCHIVE.md): content-addressed
segment store, crash-safe two-phase compaction, transparent read
fallthrough on both storage backends, the /archive/* serving surface,
and the archive_prune scenario.

The crash tests inject an error at the exact seam a kill -9 would hit
— between archive-commit (CURRENT swing) and hot-delete — and assert
the re-run resumes from the published manifest with ZERO lost rows and
ZERO double-deletes, then still passes the full pruned-vs-twin
deep-read differential.
"""

import asyncio
import json
import os
import shutil
import tempfile

import pytest

from upow_tpu.archive import compactor, parity
from upow_tpu.archive.reader import ArchiveReader
from upow_tpu.archive.store import ArchiveStore
from upow_tpu.config import ArchiveConfig
from upow_tpu.node.ratelimit import RateLimiter
from upow_tpu.resilience import faultinject
from upow_tpu.state import ChainState
from upow_tpu.swarm import Swarm, run_scenario
from upow_tpu.swarm.scenarios import _wallet, core_ok, deterministic_world


def run(coro):
    return asyncio.run(coro)


def _twins(tmp, blocks=96, *, seed=0, segment_blocks=8, safety_window=8):
    """A (pruned, twin, cfg, dirs) fixture: identical synthetic chains,
    a published snapshot anchored at the tip, archive dir wired to the
    first state."""
    arch_dir = os.path.join(tmp, "archive")
    snap_dir = os.path.join(tmp, "snapshot")
    os.makedirs(snap_dir, exist_ok=True)
    pruned, twin = ChainState(), ChainState()
    witness_from = blocks - safety_window
    for st in (pruned, twin):
        parity.build_synthetic_chain(st, blocks, seed=seed,
                                     witness_from=witness_from)
    tip_hash = pruned.db.execute(
        "SELECT hash FROM blocks WHERE id = ?", (blocks,)).fetchone()[0]
    parity.publish_fake_snapshot(snap_dir, blocks, tip_hash)
    cfg = ArchiveConfig(dir=arch_dir, segment_blocks=segment_blocks,
                        safety_window=safety_window)
    pruned.archive = ArchiveReader(arch_dir)
    return pruned, twin, cfg, (arch_dir, snap_dir)


# ---------------------------------------------------------------- store ----

def test_segment_encode_is_deterministic_and_roundtrips(tmp_path):
    async def main():
        st = ChainState()
        parity.build_synthetic_chain(st, 8, seed=3)
        blocks, txs = await st.archive_export_span(1, 8)
        from upow_tpu.archive import store as store_mod

        p1, i1 = store_mod.encode_segment(1, 8, blocks, txs)
        p2, i2 = store_mod.encode_segment(1, 8, blocks, txs)
        assert p1 == p2 and i1 == i2
        decoded = store_mod.decode_segment(p1)
        assert sorted(decoded) == list(range(1, 9))
        assert decoded[3][0] == blocks[2]
        assert decoded[3][1] == txs[blocks[2][1]]
        # write twice: the second write must verify and reuse, and the
        # records must be identical (content addressing)
        s = ArchiveStore(str(tmp_path), 8)
        r1 = s.write_segment(1, 8, blocks, txs)
        r2 = s.write_segment(1, 8, blocks, txs)
        assert r1 == r2
        assert s.verify_segment(r1)

    run(main())


def test_store_rejects_malformed_current_and_payload(tmp_path):
    s = ArchiveStore(str(tmp_path), 8)
    assert s.current_manifest() is None
    for hostile in ("../etc/passwd", ".hidden", "a/b"):
        with open(os.path.join(str(tmp_path), "CURRENT"), "w") as fh:
            fh.write(hostile + "\n")
        assert s.current_manifest() is None
    from upow_tpu.archive.store import decode_segment

    with pytest.raises(ValueError):
        decode_segment(b"not json lines\n")


def test_fetched_segment_rejects_lying_peer(tmp_path):
    """A hostile peer cannot plant a payload or index whose bytes do
    not reproduce the record's content hashes."""
    async def main():
        st = ChainState()
        parity.build_synthetic_chain(st, 8, seed=5)
        blocks, txs = await st.archive_export_span(1, 8)
        src = ArchiveStore(str(tmp_path / "src"), 8)
        record = src.write_segment(1, 8, blocks, txs)
        payload = src.read_payload(record["name"])

        dst = ArchiveStore(str(tmp_path / "dst"), 8)
        # tampered payload bytes: must raise, not land on disk
        evil = bytearray(payload)
        evil[5] ^= 0xFF
        with pytest.raises(ValueError):
            dst.write_fetched_segment(record, bytes(evil))
        # lying index digest: correct payload, forged record
        forged = dict(record)
        forged["index_sha256"] = "0" * 64
        with pytest.raises(ValueError):
            dst.write_fetched_segment(forged, payload)
        # the honest pair lands and verifies
        dst.write_fetched_segment(record, payload)
        assert dst.verify_segment(record)

    run(main())


# ------------------------------------------------------------ ratelimit ----

def test_archive_segment_indexes_share_one_ratelimit_bucket():
    rl = RateLimiter()
    # 10/second shared across the whole segment space: distinct
    # indexes must not multiply the budget
    allowed = sum(rl.allow("1.2.3.4", f"/archive/segment/{i}")
                  for i in range(15))
    assert allowed == 10
    # the manifest budget is separate and unaffected
    assert rl.allow("1.2.3.4", "/archive/manifest")
    # and another IP gets its own segment window
    assert rl.allow("5.6.7.8", "/archive/segment/0")


# ------------------------------------------------------- crash + resume ----

def test_kill_between_commit_and_prune_resumes_lossless(tmp_path):
    """kill -9 after the CURRENT swing but before any hot-delete: the
    journal survives, no row is lost, the re-run reports a resume,
    completes the prune, and a further run double-deletes nothing."""
    async def main():
        pruned, twin, cfg, (arch_dir, snap_dir) = _twins(str(tmp_path))
        faultinject.install("archive.compact:error:key=prune", 1)
        try:
            with pytest.raises(faultinject.FaultInjected):
                await compactor.compact(pruned, arch_dir, snap_dir, cfg,
                                        reader=pruned.archive)
        finally:
            faultinject.uninstall()
        store = ArchiveStore(arch_dir, cfg.segment_blocks)
        assert store.read_journal() is not None
        assert store.current_manifest() is not None  # commit landed
        hot = await pruned.archive_hot_row_counts()
        assert hot["blocks"] == 96  # nothing deleted before the crash

        stats = await compactor.compact(pruned, arch_dir, snap_dir, cfg,
                                        reader=pruned.archive)
        assert stats["ok"] and stats["resumed"]
        assert stats["pruned_blocks"] > 0
        assert store.read_journal() is None

        again = await compactor.compact(pruned, arch_dir, snap_dir, cfg,
                                        reader=pruned.archive)
        assert again["ok"]
        assert again["segments_built"] == 0 and again["pruned_blocks"] == 0

        # zero lost rows: every archived read still answers exactly
        for h in range(1, 97):
            a = await pruned.get_block_by_id(h)
            b = await twin.get_block_by_id(h)
            assert a == b, f"height {h} diverged after resume"

    run(main())


def test_kill_before_publish_deletes_nothing(tmp_path):
    """kill -9 before the CURRENT swing: no manifest, no journal, no
    deletes — and the re-run reuses every staged segment from disk."""
    async def main():
        pruned, _twin, cfg, (arch_dir, snap_dir) = _twins(str(tmp_path))
        faultinject.install("archive.compact:error:key=publish", 1)
        try:
            with pytest.raises(faultinject.FaultInjected):
                await compactor.compact(pruned, arch_dir, snap_dir, cfg,
                                        reader=pruned.archive)
        finally:
            faultinject.uninstall()
        store = ArchiveStore(arch_dir, cfg.segment_blocks)
        assert store.current_manifest() is None
        assert store.read_journal() is None
        assert (await pruned.archive_hot_row_counts())["blocks"] == 96

        stats = await compactor.compact(pruned, arch_dir, snap_dir, cfg,
                                        reader=pruned.archive)
        assert stats["ok"] and stats["pruned_blocks"] > 0

    run(main())


def test_export_gap_aborts_instead_of_publishing_a_hole(tmp_path):
    """A hot store missing rows below the cutoff (manual tampering,
    partial restore) must abort the cycle with a structured reason —
    never publish a segment with a hole."""
    async def main():
        pruned, _twin, cfg, (arch_dir, snap_dir) = _twins(str(tmp_path))
        pruned.db.execute("DELETE FROM blocks WHERE id = 5")
        pruned.db.commit()
        stats = await compactor.compact(pruned, arch_dir, snap_dir, cfg,
                                        reader=pruned.archive)
        assert not stats["ok"] and stats["reason"] == "export_gap"
        assert ArchiveStore(arch_dir,
                            cfg.segment_blocks).current_manifest() is None

    run(main())


# ---------------------------------------------------------- differential ----

def test_pruned_reads_match_unpruned_twin():
    """Storage-level deep-read differential over every fallthrough
    path (the CI smoke runs the 2400-block version)."""
    res = run(parity.storage_differential(
        320, seed=11, segment_blocks=32, safety_window=16))
    assert res["ok"], res["mismatches"]
    assert res["compaction"]["pruned_blocks"] > 0
    assert res["hot_after"]["blocks"] < res["hot_before"]["blocks"]
    assert res["reader"]["fallthrough_reads"] > 0


def test_witness_blocks_stay_hot_and_unsplit(tmp_path):
    """A block holding even one witness (UTXO-referenced) tx keeps ALL
    its rows hot: a block's txs are never split across the seam."""
    async def main():
        pruned, _twin, cfg, (arch_dir, snap_dir) = _twins(
            str(tmp_path), blocks=64, segment_blocks=8, safety_window=8)
        # plant a witness UTXO deep in prunable territory (height 10)
        r = pruned.db.execute(
            "SELECT t.tx_hash, t.outputs_addresses FROM transactions t"
            " JOIN blocks b ON b.hash = t.block_hash WHERE b.id = 10"
        ).fetchone()
        pruned.db.execute(
            "INSERT INTO unspent_outputs (tx_hash, idx, address, amount)"
            " VALUES (?,?,?,?)",
            (r["tx_hash"], 0, json.loads(r["outputs_addresses"])[0], 1))
        pruned.db.commit()
        stats = await compactor.compact(pruned, arch_dir, snap_dir, cfg,
                                        reader=pruned.archive)
        assert stats["ok"]
        blk = pruned.db.execute(
            "SELECT hash FROM blocks WHERE id = 10").fetchone()
        assert blk is not None, "witness block was pruned"
        txs = pruned.db.execute(
            "SELECT COUNT(*) AS n FROM transactions WHERE block_hash = ?",
            (blk["hash"],)).fetchone()["n"]
        assert txs == 1, "witness block's txs were split from it"
        # neighbours without witnesses were pruned
        assert pruned.db.execute(
            "SELECT COUNT(*) AS n FROM blocks WHERE id IN (9, 11)"
        ).fetchone()["n"] == 0

    run(main())


def test_pg_backend_archive_parity():
    """The archive seam is backend-neutral: identical chains restored
    into two pg states (mock driver runs the real pg SQL), one
    compacted — every read must match the unpruned pg twin."""
    from upow_tpu.snapshot import builder, client
    from upow_tpu.state.pg import PgChainState
    from upow_tpu.state.pgdriver import MockPgDriver
    from upow_tpu.verify import BlockManager

    from test_wallet import make_actors, mine_block  # noqa: F401

    async def main():
        # deterministic_world pins START_DIFFICULTY to 1.0 so the
        # python nonce search stays trivial over 24 blocks
        sqlite_state = ChainState()
        manager = BlockManager(sqlite_state, sig_backend="host")
        _, addr = make_actors()["genesis"]
        for _ in range(24):
            await mine_block(manager, sqlite_state, addr)
        payload, _ = await builder.serialize_payload(sqlite_state,
                                                    blocks_tail=24)
        tables, txs, blocks = client.parse_payload(payload)

        pruned = PgChainState(driver=MockPgDriver())
        twin = PgChainState(driver=MockPgDriver())
        for pg in (pruned, twin):
            await pg.restore_snapshot(tables, txs, blocks)
            # retire the early coinbases from the witness closure in
            # BOTH twins so the closure predicate has work to do
            # (MockPgDriver.execute is synchronous)
            pg.drv.execute(
                "DELETE FROM unspent_outputs WHERE tx_hash IN (SELECT"
                " t.tx_hash FROM transactions t JOIN blocks b ON"
                " b.hash = t.block_hash WHERE b.id <= 16)")

        with tempfile.TemporaryDirectory(prefix="archive-pg-") as tmp:
            arch_dir = os.path.join(tmp, "archive")
            snap_dir = os.path.join(tmp, "snapshot")
            os.makedirs(snap_dir)
            tip = await twin.get_block_by_id(24)
            parity.publish_fake_snapshot(snap_dir, 24, tip["hash"])
            cfg = ArchiveConfig(dir=arch_dir, segment_blocks=4,
                                safety_window=4)
            pruned.archive = ArchiveReader(arch_dir)
            stats = await compactor.compact(pruned, arch_dir, snap_dir,
                                            cfg, reader=pruned.archive)
            assert stats["ok"] and stats["archived_through"] == 16
            assert stats["pruned_blocks"] > 0

            for h in range(1, 25):
                assert await pruned.get_block_by_id(h) == \
                    await twin.get_block_by_id(h), f"height {h}"
                b = await twin.get_block_by_id(h)
                assert await pruned.get_block(b["hash"]) == \
                    await twin.get_block(b["hash"])
                for th in await twin.get_block_transaction_hashes(
                        b["hash"]):
                    assert await pruned.get_transaction_info(th) == \
                        await twin.get_transaction_info(th)
                    ta = await pruned.get_transaction(th)
                    tb = await twin.get_transaction(th)
                    assert ta.hex() == tb.hex()
            assert await pruned.get_blocks(1, 24, tx_details=True) == \
                await twin.get_blocks(1, 24, tx_details=True)
            a = await pruned.get_address_transactions(addr, limit=50)
            b = await twin.get_address_transactions(addr, limit=50)
            assert [r["tx_hash"] for r in a] == [r["tx_hash"] for r in b]
        sqlite_state.close()

    with deterministic_world(9):
        run(main())


# ------------------------------------------------------------- endpoints ----

def test_archive_endpoints_serve_fresh_without_cache_bypass():
    """Satellite regression: /archive/* must never be hot-cache
    entries — a recompaction is visible on the very next request with
    NO X-Upow-Cache-Bypass header."""
    async def main():
        swarm = await Swarm(1, seed=3).start(topology="isolated")
        tmp = tempfile.mkdtemp(prefix="archive-endpoints-")
        try:
            _, addr = _wallet(3, "shared")
            node = swarm.nodes[0]
            node.config.snapshot.dir = os.path.join(tmp, "snap")
            node.config.snapshot.blocks_tail = 2
            acfg = node.config.archive
            acfg.dir = os.path.join(tmp, "archive")
            acfg.segment_blocks = 2
            acfg.safety_window = 2
            node.state.archive = ArchiveReader(acfg.dir)

            # nothing published yet -> 404, not an empty cache hit
            doc = await swarm.get(0, "archive/manifest")
            assert doc == {"ok": False, "error": "no archive available"}

            for _ in range(8):
                assert (await swarm.mine(0, addr, push_to=[0]))["ok"]
            assert (await node.build_snapshot()) is not None
            stats = await node.compact_archive()
            assert stats["ok"] and stats["archived_through"] == 4

            m1 = (await swarm.get(0, "archive/manifest"))["result"]
            assert [s["hi"] for s in m1["segments"]] == [2, 4]
            seg = await swarm.get(0, "archive/segment/0")
            data = bytes.fromhex(seg["result"]["data"])
            from upow_tpu.snapshot.layout import sha256_hex

            assert sha256_hex(data) == m1["segments"][0]["payload_sha256"]
            # hardened params: non-integer and out-of-range indexes
            assert not (await swarm.get(0, "archive/segment/zzz"))["ok"]
            bad = await swarm.get(
                0, f"archive/segment/{len(m1['segments'])}")
            assert bad == {"ok": False, "error": "no such segment"}

            # advance the chain, rebuild, recompact: the next manifest
            # read (same driver, no bypass header) must see the new
            # archived_through
            for _ in range(4):
                assert (await swarm.mine(0, addr, push_to=[0]))["ok"]
            assert (await node.build_snapshot()) is not None
            stats2 = await node.compact_archive()
            assert stats2["archived_through"] > stats["archived_through"]
            m2 = (await swarm.get(0, "archive/manifest"))["result"]
            assert m2["archived_through"] == stats2["archived_through"]

            # /debug/archive reports the seam's health
            dbg = (await swarm.get(0, "debug/archive"))["result"]
            assert dbg["last_compaction"]["ok"]
            assert dbg["reader"]["segments"] == len(m2["segments"])

            # the explicit archive families and the sanitized trace
            # counters must not render duplicate exposition lines
            _, body = await swarm.hub.request(
                swarm.driver, swarm.urls[0], "GET", "/metrics")
            text = body.decode() if isinstance(body, bytes) else body
            names = [ln.split(" ")[0] for ln in text.splitlines()
                     if ln.startswith("upow_archive")]
            assert len(names) == len(set(names)), sorted(names)
        finally:
            await swarm.close()
            shutil.rmtree(tmp, ignore_errors=True)

    with deterministic_world(3):
        run(main())


def test_snapshot_rebuild_arms_compactor_on_block_cadence():
    """Satellite: with rebuild_interval_blocks set, committed blocks
    arm a background snapshot rebuild (and the archive compaction it
    enables) without any operator call."""
    tmp = tempfile.mkdtemp(prefix="archive-cadence-")

    def hook(i, cfg):
        cfg.snapshot.dir = os.path.join(tmp, f"snap{i}")
        cfg.snapshot.blocks_tail = 2
        cfg.snapshot.rebuild_interval_blocks = 4
        cfg.snapshot.rebuild_jitter_blocks = 0
        cfg.archive.dir = os.path.join(tmp, f"archive{i}")
        cfg.archive.segment_blocks = 2
        cfg.archive.safety_window = 2

    async def main():
        swarm = await Swarm(1, seed=5, cfg_hook=hook).start(
            topology="isolated")
        try:
            _, addr = _wallet(5, "shared")
            node = swarm.nodes[0]
            assert node._rebuild_target == 4  # jitter 0 -> exact
            for _ in range(9):
                assert (await swarm.mine(0, addr, push_to=[0]))["ok"]
            for _ in range(200):
                await swarm.settle()
                if node.archive_compact.get("ok"):
                    break
                await asyncio.sleep(0.01)
            assert node.archive_compact.get("ok"), node.archive_compact
            from upow_tpu.snapshot import layout

            assert layout.current_manifest(
                node.config.snapshot.dir) is not None
            cov = await node.state.archive.coverage()
            assert cov is not None and cov[0] == 1
        finally:
            await swarm.close()
            shutil.rmtree(tmp, ignore_errors=True)

    with deterministic_world(5):
        run(main())


def test_rebuild_jitter_varies_by_identity():
    """The cadence jitter is a deterministic function of node identity
    so a fleet started together does not rebuild in lockstep."""
    import hashlib

    def target(ident, interval=64, jitter=16):
        return interval + int.from_bytes(
            hashlib.sha256(ident.encode()).digest()[:4], "big") % (
                jitter + 1)

    targets = {target(f"127.0.0.1:{3000 + i}") for i in range(8)}
    assert len(targets) > 1  # not in lockstep
    assert all(64 <= t <= 80 for t in targets)
    assert target("127.0.0.1:3000") == target("127.0.0.1:3000")


# -------------------------------------------------------------- scenario ----

def test_archive_prune_scenario_green_and_deterministic():
    a = run_scenario("archive_prune", seed=7)
    assert core_ok(a["core"]), {
        k: v for k, v in a["core"].items()
        if isinstance(v, bool) and not v}
    assert a["core"]["hot_blocks_after"] < a["core"]["hot_blocks_before"]
    b = run_scenario("archive_prune", seed=7)
    assert a["fingerprint"] == b["fingerprint"]


# ------------------------------------------------------ CLI exit code ----

@pytest.mark.parametrize("case", ["clean", "parity_false", "resume_failed",
                                  "fingerprint_moved"])
def test_archive_cli_exit_code_holds_parity(monkeypatch, capsys, case):
    """``make archive-smoke`` is ``python -m upow_tpu.archive
    --check-determinism``: its exit code is what holds the pruned-vs-twin
    parity of the ``archive_prune`` scenario (every core boolean), the
    resume leg and the determinism double-run."""
    from upow_tpu.archive import __main__ as cli

    def artifact(fp):
        return {"scenario": "archive_prune", "nodes": 3, "seed": 7,
                "fingerprint": fp, "observed": {"elapsed_s": 0.5,
                                                "probes": 40},
                "core": {"archived_reads_match_twin": case != "parity_false",
                         "archived_through": 192, "hot_blocks_before": 260,
                         "hot_blocks_after": 68}}

    runs = iter([artifact("aa" * 32),
                 artifact(("bb" if case == "fingerprint_moved" else "aa")
                          * 32)])

    async def resume(seed, blocks):
        return ["journal survived"] if case == "resume_failed" else []

    monkeypatch.setattr(cli, "_differential", lambda seed, blocks: True)
    monkeypatch.setattr(cli, "_drive_resume", resume)
    monkeypatch.setattr(cli, "run_scenario", lambda name, seed: next(runs))
    rc = cli.main(["--check-determinism"])
    assert rc == (0 if case == "clean" else 1)
    captured = capsys.readouterr()
    if case == "parity_false":
        assert "core failed: archived_reads_match_twin" in captured.err
