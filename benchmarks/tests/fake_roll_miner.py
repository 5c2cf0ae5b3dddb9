#!/usr/bin/env python3
"""A stand-in for the miner child that knows the header's timestamp
(``fake_miner.py`` stamps the clock and prints no ``header:`` line): it
speaks the miner CLI's lines against the stub, searches a warm job with
hashlib and "sweeps" the others by printing their round lines, and
stamps each job as ``--stamp`` says:

    roll    the newest second of (last_block.timestamp, now] not yet
            swept on this tip, else now with repeat=1: a sound miner
    clock   now, whatever was swept (the miner before it could roll),
            and says repeat as it is
    silent  as ``clock``, and prints no ``header:`` line
    future  now + 5: outside the node's rule
    lie     as ``roll``, but the line says one second less than the
            header carries
"""

import argparse
import hashlib
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from child_signals import answer_signals  # noqa: E402
from fake_miner import http  # noqa: E402
from harness import powref  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("address_hex")       # 33 bytes, hex
    ap.add_argument("--node", required=True)
    ap.add_argument("--batch", type=int, required=True)
    ap.add_argument("--range", type=int, required=True)
    ap.add_argument("--stamp", default="roll")
    a = ap.parse_args()
    answer_signals()
    out = lambda s: print(s, flush=True)  # noqa: E731
    out(f"upow_tpu miner: backend=fake shard=0/1 nonces=[0, {a.range}) "
        f"node={a.node}")
    out("device: platform=tpu kind=TPU v5 lite count=4 compile_cache=-")
    swept_tip, swept = None, set()
    while True:
        info = http(a.node + "get_mining_info")["result"]
        tip, diff = info["last_block"]["hash"], info["difficulty"]
        prev_ts = info["last_block"]["timestamp"]
        hashes = info["pending_transactions_hashes"]
        block_no = info["last_block"]["id"] + 1
        if tip != swept_tip:
            swept_tip, swept = tip, set()
        now = int(time.time())
        fresh = [s for s in range(prev_ts + 1, now + 1) if s not in swept]
        ts = max(fresh) if fresh and a.stamp in ("roll", "lie") else now
        if a.stamp == "future":
            ts = now + 5
        repeat = int(ts in swept)
        swept.add(ts)
        out(f"difficulty: {diff}  block: {block_no}  confirming "
            f"{len(hashes)} transactions")
        if a.stamp != "silent":
            out(f"header: timestamp={ts - (a.stamp == 'lie')} "
                f"behind={now - ts} window={now - prev_ts} repeat={repeat}")
        prefix = (bytes([2]) + bytes.fromhex(tip)
                  + bytes.fromhex(a.address_hex)
                  + bytes.fromhex(powref.miner_merkle(hashes))
                  + ts.to_bytes(4, "little")
                  + int(diff * 10).to_bytes(2, "little"))
        want, allowed = powref.target(tip, diff)
        t0, tried, hit = time.time(), 0, None
        for r in range(a.range // a.batch):
            tried += a.batch
            if diff < 8:   # a searchable job: really hash it
                hit = next((n for n in range(r * a.batch, (r + 1) * a.batch)
                            if powref.satisfies(hashlib.sha256(
                                prefix + n.to_bytes(4, "little")).hexdigest(),
                                want, allowed)), None)
                if hit is not None:
                    break
            else:
                time.sleep(0.002)
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
