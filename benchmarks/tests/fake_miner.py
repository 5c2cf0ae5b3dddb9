#!/usr/bin/env python3
"""A stand-in for the miner child, for tests of the driver without a
chip or JAX: it speaks the miner CLI's lines and its protocol against
the stub node, searches the warm job with hashlib, and "sweeps" the
window's jobs by printing their round lines.  ``--fault`` breaks one
answer where it is produced:

    second_hit   pushes the second-lowest hit of the warm round
    short_sweep  ends every job one round early ('template expired')
    bad_nonce    pushes a nonce that does not meet the target

Like the launcher it answers SIGUSR1 and SIGUSR2 with ``trace: started``
and ``trace: stopped`` (at its next round; it writes no trace), and
with ``--mute-stop`` never answers the second; the memory request and
SIGTERM it answers from a thread of its own (``child_signals.py``:
``--memory`` and ``--ignore-term`` are its ways to go wrong).
"""

import argparse
import hashlib
import json
import signal
import sys
import time
import urllib.request

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from child_signals import answer_signals  # noqa: E402
from harness import powref  # noqa: E402


def http(url, payload=None):
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(
        url, data=data,
        headers={"Content-Type": "application/json"} if data else {})
    with urllib.request.urlopen(req, timeout=20) as resp:
        return json.loads(resp.read().decode())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("address_hex")       # 33 bytes, hex
    ap.add_argument("--node", required=True)
    ap.add_argument("--batch", type=int, required=True)
    ap.add_argument("--range", type=int, required=True)
    ap.add_argument("--fault", default="")
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--mute-stop", action="store_true")
    ap.add_argument("--memory", default="4096")
    ap.add_argument("--ignore-term", action="store_true")
    a = ap.parse_args()
    answer_signals(a.memory, a.ignore_term)
    out = lambda s: print(s, flush=True)  # noqa: E731
    signalled = {}     # what -> unix, said from the loop, not the handler
    signal.signal(signal.SIGUSR1,
                  lambda *_a: signalled.setdefault("started", time.time()))
    signal.signal(
        signal.SIGUSR2, signal.SIG_IGN if a.mute_stop else
        lambda *_a: signalled.setdefault("stopped", time.time()))
    out(f"upow_tpu miner: backend=fake shard=0/1 nonces=[0, {a.range}) "
        f"node={a.node}")
    out(f"device: platform={a.platform} kind=TPU v5 lite count=1 "
        "compile_cache=-")
    while True:
        info = http(a.node + "get_mining_info")["result"]
        tip, diff = info["last_block"]["hash"], info["difficulty"]
        hashes = info["pending_transactions_hashes"]
        block_no = info["last_block"]["id"] + 1
        out(f"difficulty: {diff}  block: {block_no}  confirming "
            f"{len(hashes)} transactions")
        prefix = (bytes([2]) + bytes.fromhex(tip)
                  + bytes.fromhex(a.address_hex)
                  + bytes.fromhex(powref.miner_merkle(hashes))
                  + int(time.time()).to_bytes(4, "little")
                  + int(diff * 10).to_bytes(2, "little"))
        want, allowed = powref.target(tip, diff)
        t0, tried, hit = time.time(), 0, None
        rounds = a.range // a.batch - (a.fault == "short_sweep")
        for r in range(rounds):
            if diff < 8:   # a searchable job: really hash it
                hits = [n for n in range(r * a.batch, (r + 1) * a.batch)
                        if powref.satisfies(hashlib.sha256(
                            prefix + n.to_bytes(4, "little")).hexdigest(),
                            want, allowed)]
                if hits:
                    hit = hits[0]
                    if a.fault == "second_hit" and len(hits) > 1:
                        hit = hits[1]
                    if a.fault == "bad_nonce":
                        hit = next(n for n in range(r * a.batch,
                                                    (r + 1) * a.batch)
                                   if n not in hits)
                    tried += a.batch
                    break
            else:
                time.sleep(0.002)
            for what in sorted(signalled):
                out(f"trace: {what} unix={signalled.pop(what):.6f}")
            tried += a.batch
            out(f"{tried / max(time.time() - t0, 1e-6) / 1e6:.2f} MH/s "
                f"({tried} hashes)")
        if hit is None:
            out(f"template expired after {tried} hashes; refreshing")
            continue
        dt = max(time.time() - t0, 1e-6)
        out(f"found nonce {hit} at {tried / dt / 1e6:.2f} MH/s ({tried} "
            f"hashes in {dt:.2f}s, first dispatch 0.00s)")
        reply = http(a.node + "push_block", {
            "block_content": (prefix + hit.to_bytes(4, "little")).hex(),
            "txs": hashes, "block_no": block_no})
        out(str(reply))
        if reply.get("ok"):
            out("BLOCK MINED\n")


if __name__ == "__main__":
    main()
