#!/usr/bin/env python3
"""A stand-in for the node child, for tests of ``drivers/block_accept.py``
without a chip or JAX: it serves ``push_block``, ``get_mining_info``,
``/metrics`` and ``/debug/events`` as the driver reads them, judges a
block with the plain reference (a test's node may lean on it: the
driver's compare is what is under test), and keeps the chain in the
sqlite file under the node's table names, so that a second stand-in
started on the file goes on where the first stopped.  ``--fault``:

    unverified   acknowledges and applies whatever is pushed, with the
                 proof of work, signatures and inputs unlooked at
    no_lanes     its /metrics count no P-256 lane (``--sig-backend host``
                 does the same)
    host_fell    its /metrics count one device verify fallen to the host
    forget_last  acknowledges the last block it takes and never writes it
    die          exits inside the third push it gets

It answers the launcher's signals like ``fake_miner.py``.
"""

import argparse
import json
import os
import signal
import sqlite3
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from child_signals import answer_signals  # noqa: E402
from harness import chainref  # noqa: E402

SCHEMA = """
CREATE TABLE IF NOT EXISTS blocks (id INTEGER PRIMARY KEY, hash TEXT);
CREATE TABLE IF NOT EXISTS unspent_outputs (tx_hash TEXT, idx INTEGER,
    address TEXT, amount INTEGER, PRIMARY KEY (tx_hash, idx));
CREATE TABLE IF NOT EXISTS fake_pushes (id INTEGER PRIMARY KEY,
    content TEXT, txs TEXT);
"""


class Everything(chainref.Chain):
    """``unverified``: a chain that takes any block onto its tip."""

    def judge(self, content_hex, tx_hexes, now):
        head = chainref.powref.parse_header(content_hex)
        txs = [chainref.parse_tx(bytes.fromhex(t)) for t in tx_hexes]
        spent = {o for tx in txs for o in tx["inputs"] if o in self.utxo}
        created = [((tx["txid"], k), key, amount) for tx in txs
                   for k, (key, amount) in enumerate(tx["outputs"])]
        return {"hash": chainref.powref.digest_hex(content_hex),
                "head": head, "spent": spent, "created": created}


class Node:
    def __init__(self, db: str, fault: str):
        self.fault, self.lock = fault, threading.Lock()
        self.chain = (Everything if fault == "unverified"
                      else chainref.Chain)()
        self.db = sqlite3.connect(db, check_same_thread=False)
        self.db.executescript(SCHEMA)
        for content, txs in self.db.execute(
                "SELECT content, txs FROM fake_pushes ORDER BY id"):
            self.chain.push(content, json.loads(txs), time.time())
        self.lanes = {"real": 0, "padded": 0, "dispatches": 0}
        self.first = []
        self.pushes = 0

    def push_block(self, params: dict) -> dict:
        with self.lock:
            self.pushes += 1
            if self.fault == "die" and self.pushes == 3:
                os._exit(7)
            txs = params["txs"]
            if int(params["block_no"]) != self.chain.height + 1:
                return {"ok": False, "error": "Too old block"}
            ok, why = self.chain.push(params["block_content"], txs,
                                      time.time())
            if txs and "not unspent" not in why:
                padded = 128
                while padded < len(txs) + 2:
                    padded *= 2
                if not self.lanes["dispatches"]:
                    self.first.append({"fields": {
                        "padded": padded, "real": len(txs) + 2,
                        "status": "ok", "seconds": 1.5}})
                self.lanes["dispatches"] += 1
                self.lanes["real"] += len(txs) + 2
                self.lanes["padded"] += padded
            if not ok:
                return {"ok": False, "error": why}
            if self.fault != "forget_last" or \
                    self.chain.height < FORGET_AT[0]:
                self._write(params["block_content"], txs)
            return {"ok": True}

    def _write(self, content: str, txs: list) -> None:
        self.db.execute("INSERT INTO fake_pushes (content, txs) VALUES "
                        "(?, ?)", (content, json.dumps(txs)))
        self.db.execute("INSERT INTO blocks VALUES (?, ?)",
                        (self.chain.height, self.chain.tip))
        self.db.execute("DELETE FROM unspent_outputs")
        self.db.executemany(
            "INSERT INTO unspent_outputs VALUES (?, ?, ?, ?)",
            [(h, i, a, v) for (h, i), (a, v) in self.chain.utxo.items()])
        self.db.commit()

    def metrics(self) -> str:
        lanes = dict(self.lanes)
        if self.fault == "no_lanes":
            lanes = {"real": 0, "padded": 0, "dispatches": 0}
        samples = {
            "upow_kernel_p256_verify_lanes_real_total": lanes["real"],
            "upow_kernel_p256_verify_lanes_padded_total": lanes["padded"],
            "upow_verify_canary_pass_total": lanes["dispatches"],
            "upow_verify_canary_fail_total": 0,
            "upow_resilience_device_fallback_total":
                int(self.fault == "host_fell"),
            "upow_kernel_p256_verify_pallas_fallbacks_total": 0,
            "upow_device_verify_health": 0,
            "upow_compile_count_total": 2}
        return "".join(f"# TYPE {k} counter\n{k} {v}\n"
                       for k, v in samples.items())


FORGET_AT = [1 << 30]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--db", required=True)
    ap.add_argument("--fault", default="-")
    ap.add_argument("--sig-backend", default="auto")
    ap.add_argument("--forget-at", type=int, default=1 << 30)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--armed", default=None,
                    help="what the runtime's own event says; default: "
                         "the platform of the device line")
    ap.add_argument("--memory", default="4096")
    a = ap.parse_args()
    FORGET_AT[0] = a.forget_at
    answer_signals(a.memory)
    for sig, what in ((signal.SIGUSR1, "started"),
                      (signal.SIGUSR2, "stopped")):
        signal.signal(sig, lambda *_a, what=what: os.write(
            1, f"trace: {what} unix={time.time():.6f}\n".encode()))
    node = Node(a.db, "no_lanes" if a.sig_backend == "host" else a.fault)

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *_a):
            pass

        def _reply(self, body, kind="application/json"):
            data = body.encode() if isinstance(body, str) \
                else json.dumps(body).encode()
            self.send_response(200)
            self.send_header("Content-Type", kind)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            url = urlparse(self.path)
            if url.path == "/get_mining_info":
                self._reply({"ok": True, "result": {"last_block": {
                    "id": node.chain.height, "hash": node.chain.tip}}})
            elif url.path == "/metrics":
                self._reply(node.metrics(), "text/plain")
            elif url.path == "/debug/events":
                kind = parse_qs(url.query).get("kind", [""])[0]
                self._reply({"ok": True, "result": {
                    "verify_first_dispatch": node.first,
                    "device_runtime_armed": [
                        {"fields": {"platform": a.armed or a.platform}}],
                }.get(kind, [])})
            else:
                self._reply({"ok": False, "error": "no such route"})

        def do_POST(self):
            size = int(self.headers.get("Content-Length", 0))
            params = json.loads(self.rfile.read(size))
            self._reply(node.push_block(params))

    server = ThreadingHTTPServer(("127.0.0.1", a.port), Handler)
    if a.platform != "none":
        print(f"device: platform={a.platform} kind=TPU v5 lite count=1 "
              "compile_cache=-", flush=True)
    print(f"======== Running on http://127.0.0.1:{a.port} ========",
          flush=True)
    server.serve_forever()


if __name__ == "__main__":
    main()
