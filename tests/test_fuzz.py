"""Adversarial-input robustness: the codec and node intake must reject
malformed bytes with clean errors, never crash or accept garbage.

(The reference's decoder runs on anything peers POST at it —
transaction.py:520-592 behind /push_tx; a parser crash there is a
remote DoS.)"""

import asyncio
import random

import pytest

from upow_tpu.core import curve, point_to_string
from upow_tpu.core.codecs import OutputType
from upow_tpu.core.tx import Tx, TxInput, TxOutput, tx_from_hex


def _valid_tx_hex() -> str:
    d, pub = curve.keygen(rng=0xF722)
    tx = Tx([TxInput("ab" * 32, 0)],
            [TxOutput(point_to_string(pub), 5_0000_0000)])
    tx.sign([d], lambda i: pub)
    return tx.hex()


def test_random_bytes_never_crash():
    rng = random.Random(1234)
    for length in (0, 1, 2, 7, 33, 64, 105, 300):
        for _ in range(40):
            blob = bytes(rng.randrange(256) for _ in range(length)).hex()
            try:
                tx = tx_from_hex(blob, check_signatures=False)
            except (ValueError, IndexError, KeyError, AssertionError,
                    NotImplementedError):
                continue  # clean rejection (NotImplementedError matches
                # the reference's version>3 raise, transaction.py:525)
            try:
                rt = tx.hex()
            except ValueError:
                continue  # parsed but unserializable (e.g. unsigned)
            assert rt != ""


def test_mutated_valid_tx_never_crashes():
    base = bytes.fromhex(_valid_tx_hex())
    rng = random.Random(99)
    for _ in range(300):
        mutated = bytearray(base)
        for _ in range(rng.randrange(1, 4)):
            mutated[rng.randrange(len(mutated))] = rng.randrange(256)
        try:
            tx = tx_from_hex(bytes(mutated).hex(), check_signatures=False)
        except (ValueError, IndexError, KeyError, AssertionError,
                NotImplementedError):
            continue
        assert tx is not None


def test_truncations_rejected():
    base = _valid_tx_hex()
    for cut in range(2, len(base), 14):
        blob = base[:cut]
        if len(blob) % 2:
            blob += "0"
        if blob == base:
            continue
        try:
            tx = tx_from_hex(blob, check_signatures=False)
        except (ValueError, IndexError, AssertionError,
                NotImplementedError):
            continue  # clean rejection
        # parsers may tolerate truncation only by consuming less — the
        # result must still be serializable or cleanly refuse
        try:
            tx.hex()
        except ValueError:
            pass


def test_push_tx_endpoint_survives_garbage(tmp_path):
    """Garbage at the HTTP boundary: every request answers ok:false —
    no 500s, node keeps serving."""
    from aiohttp.test_utils import TestClient, TestServer

    from upow_tpu.node.app import Node
    from test_node import make_config

    async def main():
        node = Node(make_config(tmp_path, "fuzz"))
        server = TestServer(node.app)
        await server.start_server()
        client = TestClient(server)
        node.started = True
        try:
            cases = ["", "zz", "00", "ff" * 500, _valid_tx_hex()[:-10],
                     "03" + "00" * 200]
            for blob in cases:
                resp = await client.get("/push_tx", params={"tx_hex": blob})
                body = await resp.json()
                assert body["ok"] is False, blob[:40]
            # node still healthy afterwards
            resp = await client.get("/get_mining_info")
            assert (await resp.json())["ok"]
        finally:
            await node.close()
            await client.close()
            await server.close()

    asyncio.run(main())


def test_read_endpoints_survive_garbage_params(tmp_path):
    """Garbage query params on every read endpoint: the node must
    answer JSON (ok:false, an error status, or an empty result) — never
    a 500 — and keep serving afterwards."""
    from aiohttp.test_utils import TestClient, TestServer

    from upow_tpu.node.app import Node
    from test_node import make_config

    async def main():
        node = Node(make_config(tmp_path, "fuzz-read"))
        server = TestServer(node.app)
        await server.start_server()
        client = TestClient(server)
        node.started = True
        node.rate_limiter.enabled = False
        try:
            garbage = ["", "zz", "-1", "1e9", "None", "🜏", "0x10",
                       "9" * 40, "9" * 5000, "' OR 1=1 --"]
            cases = [
                # the page*limit PRODUCT must not overflow int64 either
                ("/get_address_transactions",
                 {"address": "x", "page": str(2 ** 63 - 1),
                  "limit": "1000"}),
            ]
            for g in garbage:
                cases += [
                    ("/get_block", {"block": g}),
                    ("/get_block_details", {"block": g}),
                    ("/get_blocks", {"offset": g, "limit": g}),
                    ("/get_blocks_details", {"offset": g, "limit": g}),
                    ("/get_transaction", {"tx_hash": g}),
                    ("/get_address_info", {"address": g}),
                    ("/get_address_transactions", {"address": g,
                                                   "limit": g}),
                    ("/get_validators_info", {"inode": g, "offset": g,
                                              "limit": g}),
                    ("/get_delegates_info", {"validator": g, "offset": g,
                                             "limit": g}),
                ]
            for path, params in cases:
                resp = await client.get(path, params=params)
                assert resp.status < 500, (path, params, resp.status)
                if resp.content_type == "application/json":
                    await resp.json()  # parseable, whatever the verdict
                # else: aiohttp itself refused the request (e.g. an
                # oversized query string answers 400 text/plain before
                # our handlers run) — still not a 500
            # POST JSON ints get the same treatment (push_block's
            # block_no from a garbage miner)
            for bad_no in ("zz", "", None, "9" * 5000, -4, [1], {"a": 1}):
                resp = await client.post("/push_block", json={
                    "block_content": "00", "txs": [], "block_no": bad_no})
                assert resp.status < 500, (bad_no, resp.status)
                body = await resp.json()
                assert body["ok"] is False
            resp = await client.get("/get_mining_info")
            assert (await resp.json())["ok"]
        finally:
            await node.close()
            await client.close()
            await server.close()

    asyncio.run(main())


def test_push_tx_rejects_coinbase_and_unsigned(tmp_path):
    """A pushed coinbase would pass every input-based check vacuously and
    poison the mempool (reference database.py:93-96 rejects it); a blob
    with a zeroed signature section parses with signature=None inputs
    and must reject cleanly, not 500 in serialization."""
    from aiohttp.test_utils import TestClient, TestServer

    from upow_tpu.core.tx import CoinbaseTx
    from upow_tpu.node.app import Node
    from test_node import make_config

    async def main():
        node = Node(make_config(tmp_path, "fuzz2"))
        server = TestServer(node.app)
        await server.start_server()
        client = TestClient(server)
        node.started = True
        try:
            d, pub = curve.keygen(rng=0xF723)
            coinbase_hex = CoinbaseTx("cd" * 32, point_to_string(pub),
                                      6_0000_0000).hex()
            valid = bytes.fromhex(_valid_tx_hex())
            unsigned = valid[:-65] + b"\x00"  # zero the signature count
            for blob in (coinbase_hex, unsigned.hex()):
                resp = await client.get("/push_tx", params={"tx_hex": blob})
                assert resp.status == 200, blob[:40]
                body = await resp.json()
                assert body["ok"] is False, blob[:40]
            assert await node.state.get_pending_transactions_count() == 0
        finally:
            await node.close()
            await client.close()
            await server.close()

    asyncio.run(main())


def test_sig_checks_survive_hung_device(monkeypatch):
    """A device dispatch that hangs (lost device) must not wedge
    block verification: the call times out, the device path is poisoned,
    and the host path produces the verdicts."""
    import time as _time

    from upow_tpu.core import curve
    from upow_tpu.crypto import p256
    from upow_tpu.verify import txverify

    d, pub = curve.keygen(rng=808)
    import hashlib

    checks = []
    for i in range(10):
        m = bytes([i]) * 9
        r, s = curve.sign(m, d)
        if i % 3 == 2:
            s = (s + 1) % curve.CURVE_N if hasattr(curve, "CURVE_N") else s + 1
        digest = hashlib.sha256(m).digest()
        checks.append((digest, hashlib.sha256(m.hex().encode()).digest(),
                       (r, s), pub))

    monkeypatch.setattr(p256, "verify_batch_prehashed",
                        lambda *a, **k: _time.sleep(600))
    from upow_tpu.resilience.degrade import DegradeManager

    monkeypatch.setattr(txverify, "DEGRADE", DegradeManager())
    t0 = _time.monotonic()
    out = txverify.run_sig_checks(checks, backend="device",
                                  device_timeout=1.5)
    assert _time.monotonic() - t0 < 30
    assert txverify.DEGRADE.state == "poisoned"
    # use_cache=False throughout: each assertion below claims a specific
    # BACKEND ROUTING behavior — a verdict-cache hit would satisfy the
    # equality without exercising the routing at all
    want = txverify.run_sig_checks(checks, backend="host", use_cache=False)
    assert out == want
    # and auto now routes straight to host
    assert txverify.run_sig_checks(checks, backend="auto",
                                   use_cache=False) == want
    # an explicitly configured device backend honors the poison flag too
    # (no 240 s re-pay per block): instant, correct verdicts
    t1 = _time.monotonic()
    assert txverify.run_sig_checks(checks, backend="device",
                                   device_timeout=120.0,
                                   use_cache=False) == want
    assert _time.monotonic() - t1 < 10


def test_sig_verdict_cache_thread_churn(monkeypatch):
    """Hammer the verdict cache from concurrent threads with the LRU cap
    shrunk so eviction races every lookup: every verdict must stay
    correct and no OrderedDict mutation may raise (intake and block
    verify really do run on different executor threads)."""
    import concurrent.futures
    import hashlib

    from upow_tpu.core import curve
    from upow_tpu.verify import txverify

    d, pub = curve.keygen(rng=909)
    checks, want = [], []
    for i in range(60):
        m = bytes([i % 251]) * 7
        r, s = curve.sign(m, d)
        ok = i % 4 != 3
        if not ok:
            s = (s + 1) % curve.CURVE_N
        checks.append((hashlib.sha256(m).digest(),
                       hashlib.sha256(m.hex().encode()).digest(), (r, s), pub))
        want.append(txverify._host_verify_digest(
            checks[-1][0], (r, s), pub) or txverify._host_verify_digest(
            checks[-1][1], (r, s), pub))

    monkeypatch.setattr(txverify, "_SIG_VERDICTS_MAX", 16)  # force eviction
    txverify.clear_sig_verdicts()

    def worker(seed):
        import random as _r

        rng = _r.Random(seed)
        for _ in range(30):
            idx = rng.sample(range(len(checks)), rng.randint(1, 12))
            got = txverify.run_sig_checks([checks[i] for i in idx],
                                          backend="host")
            assert got == [want[i] for i in idx]
        return True

    with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
        assert all(pool.map(worker, range(8)))
