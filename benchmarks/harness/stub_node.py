"""A stub node for the miner CLI: ``get_mining_info`` and ``push_block``
in the node's wire format (``node/app.py`` ``_mining_info_result``,
``h_push_block``), tips and pending hashes from the seed.

It serves one warm job at each of ``warm_difficulties``, in turn, each
until a block for it has been pushed, then jobs at ``difficulty`` (the
window), and, once the driver has called ``begin_after``, one job at
each of ``after_difficulties`` in the same way: jobs that are mined to
a hit so that the reference can judge the answer, and whose seconds,
a matter of luck, are kept out of the set-up.  A tip lasts
``tip_interval_s`` seconds from when it was first served (the protocol's
block target: someone else found a block), or until this miner's own
valid block.  Every pushed block is judged by the plain reference
(``powref.check_block``) against ``check_difficulty`` — the served one,
unless a test tightens it to see ``correct`` fail.
"""

from __future__ import annotations

import hashlib
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from . import powref


def seeded_hashes(seed: int, label: str, n: int) -> list:
    """n 64-char hex strings that depend on (seed, label) only."""
    return [hashlib.sha256(f"{seed}:{label}:{i}".encode()).hexdigest()
            for i in range(n)]


class StubNode:
    def __init__(self, seed: int, address: str, address_bytes: bytes,
                 traffic: dict, tighten_check: int = 0):
        self.address, self.address_bytes = address, address_bytes
        self.warm_difficulties = list(traffic["warm_difficulties"])
        self.after_difficulties = list(traffic.get("after_difficulties",
                                                   []))
        self.difficulty = traffic["difficulty"]
        self.tip_interval_s = float(traffic["tip_interval_s"])
        self.tighten_check = tighten_check
        self.pending = seeded_hashes(seed, "pending",
                                     int(traffic["pending_txs"]))
        self._tips = seeded_hashes(seed, "tip", 4096)
        self._lock = threading.Lock()
        self.tip_index = 0
        self.tip_first_served = None
        self.warm_index = 0       # warm jobs answered so far
        self.after_index = 0      # jobs after the window answered so far
        self._after_begun = self._after_served = False
        self.pushes: list = []    # {"t", "content", "job", "faults"}
        self.window_start = None  # first serve at ``difficulty``
        self._server = None
        self._thread = None

    # ------------------------------------------------------------ wire ---

    @property
    def warm_done(self) -> bool:
        return self.warm_index >= len(self.warm_difficulties)

    @property
    def after_done(self) -> bool:
        return self.after_index >= len(self.after_difficulties)

    def begin_after(self) -> None:
        """The window has closed: from the miner's next fetch on, serve
        the jobs of ``after_difficulties``."""
        with self._lock:
            self._after_begun = True

    def _phase(self) -> str:
        if not self.warm_done:
            return "warm"
        if self._after_begun and not self.after_done:
            return "after"
        return "window"

    def _job(self) -> dict:
        phase = self._phase()
        d = {"warm": lambda: self.warm_difficulties[self.warm_index],
             "after": lambda: self.after_difficulties[self.after_index],
             "window": lambda: self.difficulty}[phase]()
        return {"phase": phase, "previous_hash": self._tips[self.tip_index],
                "difficulty": d, "check_difficulty": d + self.tighten_check,
                "pending_hashes": self.pending,
                "address_bytes": self.address_bytes,
                "height": self.tip_index + 1}

    def mining_info(self) -> dict:
        now = time.time()
        with self._lock:
            phase = self._phase()
            if phase == "window" and self.tip_first_served is not None \
                    and now - self.tip_first_served >= self.tip_interval_s:
                self._advance()
            if phase == "after" and not self._after_served:
                self._after_served = True   # a tip of their own
                self._advance()
            job = self._job()
            if self.tip_first_served is None:
                self.tip_first_served = now
            if phase == "window" and self.window_start is None:
                self.window_start = now
        return {"ok": True, "result": {
            "difficulty": job["difficulty"],
            "last_block": {"id": job["height"],
                           "hash": job["previous_hash"],
                           "address": self.address, "random": 0,
                           "difficulty": job["difficulty"],
                           "reward": 0, "timestamp": int(now) - 1},
            "pending_transactions": [],
            "pending_transactions_hashes": job["pending_hashes"],
            "merkle_root": powref.miner_merkle(job["pending_hashes"])}}

    def _advance(self) -> None:
        self.tip_index += 1
        self.tip_first_served = None

    def push_block(self, body: dict) -> dict:
        now = time.time()
        content = str(body.get("block_content", ""))
        with self._lock:
            job = self._job()
            faults = powref.check_block(content, job)
            if body.get("block_no") != job["height"] + 1:
                faults.append(f"block_no {body.get('block_no')} is not "
                              f"{job['height'] + 1}")
            if list(body.get("txs") or []) != job["pending_hashes"]:
                faults.append("txs are not the served pending hashes")
            self.pushes.append({"t": now, "content": content, "job": job,
                                "faults": faults, "phase": job["phase"]})
            if faults and job["phase"] == "window":
                return {"ok": False, "error": "; ".join(faults)[:300]}
            # a refused block still ends a job that is mined to a hit, so
            # that a run with a fault in it reaches its verdict
            if job["phase"] == "warm":
                self.warm_index += 1
            elif job["phase"] == "after":
                self.after_index += 1
            self._advance()
        if faults:
            return {"ok": False, "error": "; ".join(faults)[:300]}
        return {"ok": True}

    # ---------------------------------------------------------- server ---

    def start(self) -> str:
        stub = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def _send(self, obj: dict) -> None:
                data = json.dumps(obj).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                if self.path.startswith("/get_mining_info"):
                    self._send(stub.mining_info())
                else:
                    self.send_error(404)

            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                try:
                    body = json.loads(self.rfile.read(n) or b"{}")
                except ValueError:
                    body = {}
                if self.path.startswith("/push_block"):
                    self._send(stub.push_block(body))
                else:
                    self.send_error(404)

            def log_message(self, *_a):
                pass

        self._server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True, name="stub-node")
        self._thread.start()
        return f"http://127.0.0.1:{self._server.server_address[1]}/"

    def stop(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._thread.join(timeout=10)
            self._server = None
