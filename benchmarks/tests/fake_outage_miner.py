#!/usr/bin/env python3
"""A stand-in for the miner child whose node goes away
(``fake_roll_miner.py`` dies at the first refused connection): it speaks
the miner CLI's lines against the stub, rolls the header's timestamp,
and where a fetch fails builds the next job from the template in hand
while that is younger than ``--ttl``, says ``node unreachable`` a try,
and pushes a found block until the node gives a verdict.  One thread: a
held request holds it, as it holds the miner's feed.  ``--fault`` is how
it goes wrong:

    idle_late       mines nothing while the node is away (no held job)
                    and, once a fetch succeeds again, waits 1.5 s more
                    before it starts
    drop_block      one try a push; a block that meets a dead node is
                    said lost and forgotten
    stale_template  holds the template in hand whatever its age
    odd_error       says 'node unreachable' once while the node answers
"""

import argparse
import hashlib
import sys
import time
import urllib.error

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from child_signals import answer_signals  # noqa: E402
from fake_miner import http  # noqa: E402
from harness import powref  # noqa: E402

AWAY = (urllib.error.URLError, OSError, ValueError, KeyError)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("address_hex")       # 33 bytes, hex
    ap.add_argument("--node", required=True)
    ap.add_argument("--batch", type=int, required=True)
    ap.add_argument("--range", type=int, required=True)
    ap.add_argument("--ttl", type=float, default=90.0)
    ap.add_argument("--fault", default="")
    a = ap.parse_args()
    answer_signals()
    out = lambda s: print(s, flush=True)  # noqa: E731
    out(f"upow_tpu miner: backend=fake shard=0/1 nonces=[0, {a.range}) "
        f"node={a.node}")
    out("device: platform=tpu kind=TPU v5 lite count=1 compile_cache=-")
    swept_tip, swept = None, set()
    info = fetched = None
    jobs, away = 0, False
    while True:
        held = 1
        try:
            info = http(a.node + "get_mining_info")["result"]
            fetched, held = time.time(), 0
            if a.fault == "idle_late" and away:
                time.sleep(1.5)
            away = False
        except AWAY as e:
            away = True
            out(f"node unreachable: {e}; retrying")
            if a.fault == "idle_late" or info is None or (
                    time.time() - fetched >= a.ttl
                    and a.fault != "stale_template"):
                info = None
                time.sleep(0.05)
                continue
        jobs += 1
        if a.fault == "odd_error" and jobs == 3:
            out("node unreachable: made up; retrying")
        tip, diff = info["last_block"]["hash"], info["difficulty"]
        prev_ts = info["last_block"]["timestamp"]
        hashes = info["pending_transactions_hashes"]
        block_no = info["last_block"]["id"] + 1
        if tip != swept_tip:
            swept_tip, swept = tip, set()
        now = int(time.time())
        fresh = [s for s in range(prev_ts + 1, now + 1) if s not in swept]
        ts = max(fresh) if fresh else now
        repeat = int(ts in swept)
        swept.add(ts)
        out(f"difficulty: {diff}  block: {block_no}  confirming "
            f"{len(hashes)} transactions")
        out(f"header: timestamp={ts} behind={now - ts} "
            f"window={now - prev_ts} repeat={repeat} held={held} "
            f"age={time.time() - fetched:.1f}")
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
                time.sleep(0.004)
            out(f"{tried / max(time.time() - t0, 1e-6) / 1e6:.2f} MH/s "
                f"({tried} hashes)")
        if hit is None:
            out(f"template expired after {tried} hashes; refreshing")
            continue
        dt = max(time.time() - t0, 1e-6)
        out(f"found nonce {hit} at {tried / dt / 1e6:.2f} MH/s ({tried} "
            f"hashes in {dt:.2f}s, first dispatch 0.00s)")
        body = {"block_content": (prefix + hit.to_bytes(4, "little")).hex(),
                "txs": hashes, "block_no": block_no}
        while True:
            try:
                reply = http(a.node + "push_block", body)
                break
            except AWAY as e:
                out(f"push_block failed: {e}; retrying")
                reply = {"ok": False}
                if a.fault == "drop_block":
                    break
                time.sleep(0.05)
        out(str(reply))
        if reply.get("ok"):
            out("BLOCK MINED\n")
        info = None     # the tip may have moved


if __name__ == "__main__":
    main()
