#!/usr/bin/env python3
"""``fake_node.py`` with a resident UTXO index to speak of, for tests of
``drivers/utxo_accept.py`` without a chip or JAX: it keeps whatever
rows the file's ``unspent_outputs`` table already holds (the filler),
writes a block as its delta, and says of an index what the node's
``/metrics`` and ``/debug/events`` say.  ``--config <file>`` is the
node's own: ``device.utxo_index`` false is a node without the index.
``--fault`` as ``fake_node.py``, and:

    no_delta_index   /metrics export no index.apply_rows: a program from
                     before the index was kept resident
    stale_index      a block's delta never reaches the index: no row is
                     counted as applied and the entries stand still
    consulted        one probe of every block is answered by the host
"""

import argparse
import json
import os
import signal
import sqlite3
import sys
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import fake_node  # noqa: E402
from child_signals import answer_signals  # noqa: E402


class IndexNode(fake_node.Node):
    def __init__(self, db: str, fault: str, indexed: bool):
        con = sqlite3.connect(db)
        con.executescript(fake_node.SCHEMA)
        self.rows0 = con.execute(
            "SELECT COUNT(*) FROM unspent_outputs").fetchone()[0]
        con.close()
        super().__init__(db, fault)
        self.written = set(self.chain.utxo)   # the chain's rows in the file
        self.indexed = indexed
        self.index = {"apply_rows": 0, "upload_bytes": 0, "relayouts": 0,
                      "probe_outpoints": 0, "shadow_consults": 0,
                      "entries": self.rows0 if indexed else 0}

    def push_block(self, params: dict) -> dict:
        before = dict(self.chain.utxo)
        reply = super().push_block(params)
        txs = len(params["txs"])
        if self.indexed and txs:
            self.index["probe_outpoints"] += txs
            self.index["upload_bytes"] += 16 * txs
            self.index["shadow_consults"] += self.fault == "consulted"
        if self.indexed and reply["ok"] and self.fault != "stale_index":
            gone = len(set(before) - set(self.chain.utxo))
            new = len(set(self.chain.utxo) - set(before))
            self.index["apply_rows"] += gone + new
            self.index["upload_bytes"] += 24 * new + 20 * gone
            self.index["entries"] += new - gone
        return reply

    def _write(self, content: str, txs: list) -> None:
        """The block as its delta: the rows of the file that are not
        this chain's (the filler) stay."""
        self.db.execute("INSERT INTO fake_pushes (content, txs) VALUES "
                        "(?, ?)", (content, json.dumps(txs)))
        self.db.execute("INSERT INTO blocks VALUES (?, ?)",
                        (self.chain.height, self.chain.tip))
        self.db.executemany(
            "DELETE FROM unspent_outputs WHERE tx_hash = ? AND idx = ?",
            [o for o in self.written if o not in self.chain.utxo])
        self.db.executemany(
            "INSERT OR REPLACE INTO unspent_outputs (tx_hash, idx, address,"
            " amount) VALUES (?, ?, ?, ?)",
            [(h, i, a, v) for (h, i), (a, v) in self.chain.utxo.items()
             if (h, i) not in self.written])
        self.written = set(self.chain.utxo)
        self.db.commit()

    def metrics(self) -> str:
        text = super().metrics()
        if self.fault == "no_delta_index":
            return text
        samples = {f"upow_index_{k}_total": v for k, v in self.index.items()
                   if k != "entries"}
        samples["upow_utxo_index_entries"] = self.index["entries"]
        return text + "".join(f"{k} {v}\n" for k, v in samples.items())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--db", required=True)
    ap.add_argument("--fault", default="-")
    ap.add_argument("--sig-backend", default="auto")
    ap.add_argument("--config", default=None)
    ap.add_argument("--platform", default="tpu")
    a = ap.parse_args()
    indexed = True
    if a.config:
        with open(a.config) as f:
            indexed = bool(json.load(f).get("device", {}).get("utxo_index"))
    answer_signals("4096")
    for sig, what in ((signal.SIGUSR1, "started"),
                      (signal.SIGUSR2, "stopped")):
        signal.signal(sig, lambda *_a, what=what: os.write(
            1, f"trace: {what} unix={time.time():.6f}\n".encode()))
    node = IndexNode(a.db, "no_lanes" if a.sig_backend == "host"
                     else a.fault, indexed)
    capacity = 1 << max(0, (node.rows0 - 1).bit_length())

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
                        {"fields": {"platform": a.platform}}],
                    "index_built": [{"fields": {
                        "table": "unspent_outputs", "entries": node.rows0,
                        "capacity": capacity,
                        "resident_bytes": 24 * capacity,
                        "seconds": 0.25}}] if indexed else [],
                }.get(kind, [])})
            else:
                self._reply({"ok": False, "error": "no such route"})

        def do_POST(self):
            size = int(self.headers.get("Content-Length", 0))
            params = json.loads(self.rfile.read(size))
            self._reply(node.push_block(params))

    server = ThreadingHTTPServer(("127.0.0.1", a.port), Handler)
    print(f"device: platform={a.platform} kind=TPU v5 lite count=1 "
          "compile_cache=-", flush=True)
    print(f"======== Running on http://127.0.0.1:{a.port} ========",
          flush=True)
    server.serve_forever()


if __name__ == "__main__":
    main()
