#!/usr/bin/env python3
"""chip_smoke.py — the served path, once, on the chip.

    python3 chip_smoke.py                 one TPU chip (what the driver runs)
    python3 chip_smoke.py --rehearse-cpu  same two phases, tiny, children on
                                          the CPU; ALWAYS ends "ok": false
    python3 chip_smoke.py --chips 4       the mining pod over four chips and
                                          what it is compared with, only

A chip belongs to one process at a time and a node and a miner are two
processes, so this parent never initialises a JAX backend (it imports
``upow_tpu`` for keys, transactions and the sqlite state view — that
imports jax, which is fine; it never calls ``jax.devices()`` nor
dispatches) and the run is two phases:

* **Phase A — the miner owns the chip.**  Node A (``device=cpu``, host
  verify, fresh sqlite file) is fed through the wire: ``push_tx`` of a
  1 -> 255 -> 8,160 fan-out, then 8,160 signed one-input spends, keys
  and amounts from ``--seed``.  Each block is mined at the protocol's
  unpatched START_DIFFICULTY 6.0 by ``python -m upow_tpu.mine.miner
  <addr> --node A --device tpu --once``.  A's chain is the reference.
* **Phase B — the node owns the chip.**  Every miner has exited.  Node B
  (``device=tpu``) gets A's blocks over ``push_block`` with full
  transaction hex (its mempool is empty: every signature is unseen),
  then a few wallet requests.  Pass only if B's tip hash and UTXO
  fingerprint equal A's and B's own /metrics say the work was the
  device's: >= 8,160 real P-256 lanes dispatched, every canary passed,
  no host fallback, no Pallas->jnp fallback, degrade state healthy.

To fit the driver's 1,200 s with an empty compile cache, the number of
first dispatches is cut, never the width: B by default starts from a
copy of A's database at height 3, verifies the 8,160-tx block as ONE
8,192-lane dispatch and hashes txids on the host (two P-256 shapes at
about a minute each since PR 46, 5-7 before, instead of four, and no
sha256 crossover measurement).
``--b-start blank --b-microbatch 1024 --b-txid auto`` restores the full
replay at the node's defaults; PERF.md has what that costs.

The LAST line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``
— device as reported by the process that held the chip.  Any failed
phase prints ``"ok": false`` there and exits non-zero.
"""

from __future__ import annotations

import argparse
import asyncio
import glob
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from decimal import Decimal

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

N_FAN, N_PER = 255, 32          # upstream's full block: 8,160 one-input txs


class SmokeFailure(Exception):
    """A phase failed; the text says which and why."""


def say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------ processes ----

_CHILDREN: list = []


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env(extra=None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


def _http(url: str, payload=None, timeout: float = 60.0):
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(
        url, data=data,
        headers={"Content-Type": "application/json"} if data else {})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read().decode()


def _get(url: str, payload=None, timeout: float = 60.0) -> dict:
    return json.loads(_http(url, payload, timeout))


class NodeProc:
    """One ``python -m upow_tpu.node.run`` child on a sqlite file."""

    def __init__(self, name: str, work: str, device: str, extra_device=None):
        self.name, self.work = name, work
        self.port = _free_port()
        self.url = f"http://127.0.0.1:{self.port}/"
        self.db = os.path.join(work, "db", f"{name}.db")
        self.log = os.path.join(work, f"node_{name}.log")
        self.cfg_path = os.path.join(work, f"node_{name}.json")
        self.proc = None
        cfg = {
            "node": {"host": "127.0.0.1", "port": self.port,
                     "db_path": self.db, "seed_url": "",
                     "peers_file": os.path.join(work, "db",
                                                f"{name}.nodes.json"),
                     "ip_config_file": "",
                     # the smoke pushes 16k requests from one address
                     "rate_limits_enabled": False},
            "device": dict({"device": device}, **(extra_device or {})),
            "ws": {"enabled": False},
            "log": {"path": os.path.join(work, f"node_{name}.app.log"),
                    "console": False},
        }
        with open(self.cfg_path, "w") as f:
            json.dump(cfg, f, indent=1)

    def start(self, boot_timeout: float = 180.0) -> float:
        t0 = time.time()
        sink = open(self.log, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "upow_tpu.node.run", "--config",
             self.cfg_path], stdout=sink, stderr=subprocess.STDOUT,
            cwd=self.work, env=_child_env())
        sink.close()
        _CHILDREN.append(self.proc)
        while time.time() - t0 < boot_timeout:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"node {self.name} exited rc={self.proc.returncode} at "
                    f"start-up: {self.tail()}")
            try:
                _get(self.url + "get_mining_info", timeout=5)
                return time.time() - t0
            except (urllib.error.URLError, OSError, ValueError):
                time.sleep(0.3)
        raise SmokeFailure(f"node {self.name} never answered: {self.tail()}")

    def stop(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self.proc = None

    def tail(self, n: int = 1500) -> str:
        try:
            with open(self.log, "rb") as f:
                return f.read().decode(errors="replace")[-n:]
        except OSError:
            return "<no log>"


def _stop_all() -> None:
    for p in _CHILDREN:
        if p.poll() is None:
            p.kill()
    for p in _CHILDREN:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass


_MINER_LINE = re.compile(
    r"found nonce (\d+) at ([\d.]+) MH/s \((\d+) hashes in ([\d.]+)s, "
    r"first dispatch ([\d.]+)s\)")
_DEVICE_LINE = re.compile(
    r"^device: platform=(\S+) kind=(.*) count=(\d+) compile_cache=")


def mine_block(node: NodeProc, address: str, device: str, work: str,
               label: str, env=None, timeout: float = 600.0) -> dict:
    """One ``miner --once`` child; returns what it printed, parsed."""
    t0 = time.time()
    cmd = [sys.executable, "-m", "upow_tpu.mine.miner", address,
           "--node", node.url, "--device", device, "--once"]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, cwd=work,
                            env=_child_env(env))
    _CHILDREN.append(proc)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, err = proc.communicate()
        raise SmokeFailure(f"miner for {label} still running after "
                           f"{timeout:.0f}s: {err[-800:]}")
    with open(os.path.join(work, "miner.log"), "a") as f:
        f.write(f"--- {label}: {' '.join(cmd[3:])}\n{out}\n{err}\n")
    if proc.returncode != 0:
        raise SmokeFailure(
            f"miner for {label} exited rc={proc.returncode}: "
            f"{(err or out).strip()[-800:]}")
    rec = {"label": label, "seconds": round(time.time() - t0, 2),
           "device": None, "mesh": None, "counters": None}
    for line in out.splitlines():
        m = _MINER_LINE.search(line)
        if m:
            rec.update(nonce=int(m.group(1)), mhs=float(m.group(2)),
                       hashes=int(m.group(3)), search_s=float(m.group(4)),
                       first_dispatch_s=float(m.group(5)))
        m = _DEVICE_LINE.match(line)
        if m:
            rec["device"] = {"platform": m.group(1), "kind": m.group(2),
                             "count": int(m.group(3))}
        if line.startswith("upow_tpu miner: backend="):
            rec["backend"] = line.split("backend=")[1].split()[0]
        if line.startswith("mesh: "):
            rec["mesh"] = json.loads(line[len("mesh: "):])
        if line.startswith("telemetry: "):   # UPOW_PROFILE_ENABLED only
            rec["counters"] = json.loads(
                line[len("telemetry: "):])["counters"]
    if "nonce" not in rec or "BLOCK MINED" not in out:
        raise SmokeFailure(f"miner for {label} found no block: {out[-800:]}")
    return rec


# -------------------------------------------------------------- fixture ----

class Wallet:
    """Keys, amounts and transactions from ``--seed`` (host only)."""

    def __init__(self, seed: int):
        from upow_tpu.core import curve, point_to_string

        self.d, self.pub = curve.keygen(rng=0x5EED0000 + seed)
        self.address = point_to_string(self.pub)

    def tx(self, inputs, outputs):
        from upow_tpu.core.tx import Tx, TxInput, TxOutput

        return Tx([TxInput(h, i) for h, i in inputs],
                  [TxOutput(self.address, a) for a in outputs]) \
            .sign([self.d], lambda _i: self.pub)

    @staticmethod
    def split(amount: int, n: int) -> list:
        per = amount // n
        return [per] * (n - 1) + [amount - per * (n - 1)]


async def _push_all(url: str, tx_hexes: list, concurrency: int = 64) -> None:
    """push_tx every transaction; any refusal is a failure."""
    import aiohttp

    sem = asyncio.Semaphore(concurrency)
    bad: list = []

    async def one(session, tx_hex):
        async with sem:
            async with session.post(url + "push_tx",
                                    json={"tx_hex": tx_hex}) as resp:
                body = await resp.json()
                if not body.get("ok"):
                    bad.append(body)

    timeout = aiohttp.ClientTimeout(total=600)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        await asyncio.gather(*(one(session, h) for h in tx_hexes))
    if bad:
        raise SmokeFailure(f"push_tx refused {len(bad)} of {len(tx_hexes)}: "
                           f"{bad[0]}")


def push_txs(node: NodeProc, txs: list) -> float:
    t0 = time.time()
    asyncio.run(_push_all(node.url, [t.hex() for t in txs]))
    return time.time() - t0


def tip(node: NodeProc) -> dict:
    info = _get(node.url + "get_mining_info")["result"]
    return {"id": info["last_block"].get("id", 0),
            "hash": info["last_block"].get("hash"),
            "timestamp": int(info["last_block"].get("timestamp", 0)),
            "difficulty": info["difficulty"],
            "pending": len(info["pending_transactions_hashes"])}


def wait_next_second(after_ts: int) -> None:
    """A block's timestamp must exceed its parent's (whole seconds)."""
    while int(time.time()) <= after_ts:
        time.sleep(0.1)


def fetch_blocks(node: NodeProc, first: int, last: int) -> list:
    """[(block_no, content_hex, [tx_hex...]) ...] without coinbases."""
    from upow_tpu.core.tx import CoinbaseTx, tx_from_hex

    page = _get(node.url + f"get_blocks?offset={first}"
                f"&limit={last - first + 1}", timeout=120)["result"]
    out = []
    for entry in page:
        txs = [t for t in entry["transactions"] if not isinstance(
            tx_from_hex(t, check_signatures=False), CoinbaseTx)]
        out.append((entry["block"]["id"], entry["block"]["content"], txs))
    if [b[0] for b in out] != list(range(first, last + 1)):
        raise SmokeFailure(f"node {node.name} served blocks "
                           f"{[b[0] for b in out]}, wanted {first}..{last}")
    return out


def db_view(db_path: str) -> dict:
    """Tip hash and UTXO fingerprint straight from a stopped node's
    sqlite file (state/views.py — the fingerprint has no HTTP route)."""
    from upow_tpu.state import ChainState

    async def read():
        state = ChainState(db_path)
        last = await state.get_last_block()
        return {"height": last["id"], "tip": last["hash"],
                "utxo_fingerprint": await state.get_unspent_outputs_hash()}

    return asyncio.run(read())


def copy_db(work: str, src: str, dst: str) -> None:
    """Every file of STOPPED node ``src``'s database (db, wal, sidecars)
    under node ``dst``'s name."""
    for path in glob.glob(os.path.join(work, "db", src + ".db*")):
        shutil.copyfile(path, os.path.join(
            work, "db", dst + os.path.basename(path)[len(src):]))


# -------------------------------------------------------------- metrics ----

def scrape(node: NodeProc) -> dict:
    """{sample name: value} of the node's Prometheus scrape (label-free
    samples; histogram series keep their suffix + labels as the key)."""
    out = {}
    for line in _http(node.url + "metrics").splitlines():
        if not line or line.startswith("#"):
            continue
        line = line.split(" # ")[0]  # exemplar suffix
        name, _, value = line.rpartition(" ")
        try:
            out[name.strip()] = float(value)
        except ValueError:
            pass
    return out


def metric(samples: dict, dotted: str) -> float:
    """One sample by its internal dotted name (counters are exported
    with a ``_total`` suffix).  A name the node does not export at all
    is a failure, not a zero: every family read here is preregistered."""
    name = "upow_" + re.sub(r"[^a-zA-Z0-9_]", "_", dotted)
    for key in (name, name + "_total"):
        if key in samples:
            return samples[key]
    raise SmokeFailure(f"node exports no metric {dotted!r}")


def events(node: NodeProc) -> list:
    return _get(node.url + "debug/events?since=0")["result"]


# ----------------------------------------------------------- the phases ----

def phase_a(args, work: str, wallet: Wallet, miner_device: str):
    """Build and mine the chain on node A.  Returns (A, leaves, blocks
    records, set-up seconds excluding mining)."""
    n_fan, n_per = (N_FAN, N_PER) if not args.rehearse_cpu else (8, 4)
    a = NodeProc("A", work, "cpu")
    boot = a.start()
    say(f"[A] node A up in {boot:.1f}s (device=cpu, host verify) {a.url}")
    records, setup_s = [], boot

    def mine(label):
        t = tip(a)
        wait_next_second(t["timestamp"])
        rec = mine_block(a, wallet.address, miner_device, work, label,
                         timeout=args.miner_timeout)
        after = tip(a)
        if after["id"] != t["id"] + 1:
            raise SmokeFailure(f"{label}: node A still at height "
                               f"{after['id']} after the miner said ok")
        rec.update(height=after["id"], difficulty=t["difficulty"],
                   pending_before=t["pending"], pending_after=after["pending"])
        records.append(rec)
        say(f"[A] block {after['id']}: backend={rec.get('backend')} "
            f"difficulty={t['difficulty']} txs={t['pending'] - after['pending']}"
            f" nonce={rec['nonce']} reported={rec['mhs']} MH/s "
            f"({rec['hashes']} hashes in {rec['search_s']}s, first dispatch "
            f"{rec['first_dispatch_s']}s) process={rec['seconds']}s "
            f"device={rec['device']}")
        return rec

    from upow_tpu.core.constants import SMALLEST

    mine("block 1 (coinbase)")
    info = _get(a.url + f"get_address_info?address={wallet.address}")["result"]
    coin = info["spendable_outputs"][0]
    reward = int(Decimal(coin["amount"]) * SMALLEST)

    t0 = time.time()
    fan = wallet.tx([(coin["tx_hash"], coin["index"])],
                    wallet.split(reward, n_fan))
    push_txs(a, [fan])
    setup_s += time.time() - t0
    mine("block 2 (1 -> %d)" % n_fan)

    t0 = time.time()
    mids = [wallet.tx([(fan.hash(), j)],
                      wallet.split(fan.outputs[j].amount, n_per))
            for j in range(n_fan)]
    push_txs(a, mids)
    setup_s += time.time() - t0
    mine("block 3 (%d -> %d)" % (n_fan, n_fan * n_per))

    if args.b_start == "height3":
        # a consistent copy needs a stopped writer: sqlite WAL
        t0 = time.time()
        a.stop()
        copy_db(work, "A", "B")
        a.start()
        setup_s += time.time() - t0

    t0 = time.time()
    leaves = [wallet.tx([(m.hash(), k)], [o.amount])
              for m in mids for k, o in enumerate(m.outputs)]
    sign_s = time.time() - t0
    push_s = push_txs(a, leaves)
    setup_s += sign_s + push_s
    say(f"[A] {len(leaves)} one-input spends signed in {sign_s:.1f}s and "
        f"pushed over push_tx in {push_s:.1f}s (host verify)")
    rec = mine("block 4 (%d spends)" % len(leaves))
    packed = rec["pending_before"] - rec["pending_after"]
    if rec["pending_after"]:
        say(f"[A] the mining template packed {packed} of {len(leaves)} "
            f"pending spends into block 4 (get_mining_info serves what "
            f"fits MAX_BLOCK_SIZE_HEX); {rec['pending_after']} left for a "
            f"fifth block")
        mine("block 5 (the rest)")
        if tip(a)["pending"]:
            raise SmokeFailure("spends still pending after a fifth block")
    return a, leaves, records, setup_s


def phase_b(args, work: str, wallet: Wallet, a: NodeProc, leaves: list,
            b_device: str):
    """Replay A's blocks into node B over push_block; wallet requests;
    returns (B, report dict) with B still running."""
    height = tip(a)["id"]
    first = 4 if args.b_start == "height3" else 1
    blocks = fetch_blocks(a, first, height)
    extra = {"verify_microbatch": args.b_microbatch,
             "txid_backend": args.b_txid}
    b = NodeProc("B", work, b_device, extra_device=extra)
    boot = b.start(boot_timeout=300)
    armed = [e for e in events(b) if e.get("kind") == "device_runtime_armed"]
    device = None
    if armed:
        f = armed[-1].get("fields", armed[-1])
        device = {"platform": f.get("platform"),
                  "kind": f.get("device_kind"),
                  "count": int(f.get("device_count") or 0)}
    say(f"[B] node B up in {boot:.1f}s (device={b_device}, starts at height "
        f"{first - 1}, verify_microbatch={args.b_microbatch}, "
        f"txid_backend={args.b_txid}) armed={device}")
    report = {"device": device, "boot_s": boot, "accept_s": {}}
    for block_no, content, txs in blocks:
        t0 = time.time()
        reply = _get(b.url + "push_block",
                     {"block_content": content, "txs": txs,
                      "block_no": block_no}, timeout=args.accept_timeout)
        dt = time.time() - t0
        if not reply.get("ok"):
            raise SmokeFailure(f"node B refused block {block_no}: {reply} "
                               f"| {b.tail(600)}")
        report["accept_s"][block_no] = round(dt, 2)
        say(f"[B] push_block {block_no} ({len(txs)} txs, every signature "
            f"unseen): accepted in {dt:.2f}s client-side")
    # what a wallet asks next
    info = _get(b.url + f"get_address_info?address={wallet.address}")["result"]
    mining = _get(b.url + "get_mining_info")["result"]
    spend = wallet.tx([(leaves[0].hash(), 0)], [leaves[0].outputs[0].amount])
    pushed = _get(b.url + "push_tx", {"tx_hex": spend.hex()})
    if not pushed.get("ok"):
        raise SmokeFailure(f"node B refused a fresh spend: {pushed}")
    if mining["last_block"]["id"] != height or \
            len(info["spendable_outputs"]) < 1:
        raise SmokeFailure("node B's wallet view is wrong: "
                           f"{mining['last_block']}")
    say(f"[B] wallet requests ok: get_address_info ({len(info['spendable_outputs'])}"
        f" spendable), get_mining_info (height {height}), push_tx "
        f"(pending {tip(b)['pending']})")
    return b, report


def device_check(b: NodeProc, report: dict, n_real: int) -> list:
    """What B's own /metrics and events say about who did the work.
    Returns the list of failed conditions (empty = the device did it)."""
    m = scrape(b)
    firsts = [e.get("fields", e) for e in events(b)
              if e.get("kind") == "verify_first_dispatch"]
    for f in firsts:
        say(f"[B] first dispatch at {f['padded']} lanes ({f['real']} real): "
            f"{f['status']} in {f['seconds']}s")
    # what each of B's programs cost to make (compile_cache.listen)
    compiles = [e.get("fields", e) for e in events(b)
                if e.get("kind") == "compile"]
    for c in compiles:
        say(f"[B] compile {c.get('fun_name')}: trace {c.get('trace_s')}s "
            f"lower {c.get('lower_s')}s cache retrieval "
            f"{c.get('cache_retrieval_s')}s backend {c.get('backend_s')}s")
    lanes_real = metric(m, "kernel.p256_verify.lanes_real")
    lanes_padded = metric(m, "kernel.p256_verify.lanes_padded")
    hits = metric(m, "compile_cache.persistent_hits")
    misses = metric(m, "compile_cache.persistent_misses")
    say(f"[B] p256_verify lanes real={lanes_real:.0f} padded="
        f"{lanes_padded:.0f}; canary pass="
        f"{metric(m, 'verify.canary_pass'):.0f} fail="
        f"{metric(m, 'verify.canary_fail'):.0f}; device_fallback="
        f"{metric(m, 'resilience.device_fallback'):.0f}; pallas_fallbacks="
        f"{metric(m, 'kernel.p256_verify.pallas_fallbacks'):.0f}; "
        f"device_verify_health={metric(m, 'device_verify_health'):.0f}; "
        f"persistent compile cache hits={hits:.0f} misses={misses:.0f}")
    report.update(first_dispatches=firsts, cache_hits=hits,
                  cache_misses=misses, lanes_real=lanes_real)
    device = report["device"] or {}
    checks = [
        (device.get("platform") == "tpu",
         f"node B armed on {device.get('platform')!r}, not 'tpu'"),
        (lanes_real >= n_real,
         f"{lanes_real:.0f} real P-256 lanes dispatched, wanted >= {n_real}"),
        (metric(m, "verify.canary_pass") > 0
         and metric(m, "verify.canary_fail") == 0,
         "canaries did not all pass"),
        (metric(m, "resilience.device_fallback") == 0,
         "a device verify fell back to the host"),
        (metric(m, "kernel.p256_verify.pallas_fallbacks") == 0,
         "a Pallas program fell back to jnp"),
        (metric(m, "device_verify_health") == 0, "degrade state not healthy"),
    ]
    return [why for ok, why in checks if not ok]


def run_one_chip(args, work: str) -> dict:
    wallet = Wallet(args.seed)
    miner_device = "cpu" if args.rehearse_cpu else "tpu"
    b_device = "cpu" if args.rehearse_cpu else "tpu"
    t_all = time.time()
    a, leaves, records, setup_s = phase_a(args, work, wallet, miner_device)
    miner_device_seen = records[-1]["device"]
    b, report = phase_b(args, work, wallet, a, leaves, b_device)
    failed = device_check(b, report, len(leaves))
    a.stop()
    b.stop()
    va, vb = db_view(a.db), db_view(b.db)
    say(f"[=] A: height {va['height']} tip {va['tip']} utxo "
        f"{va['utxo_fingerprint']}")
    say(f"[=] B: height {vb['height']} tip {vb['tip']} utxo "
        f"{vb['utxo_fingerprint']}")
    if va != vb:
        raise SmokeFailure("node B's chain differs from host-verify node A's")
    say(f"[=] equal.  set-up {setup_s:.1f}s (node boots, signing, push_tx); "
        f"mining {sum(r['seconds'] for r in records):.1f}s; B accepts "
        f"{report['accept_s']}; whole run {time.time() - t_all:.1f}s")
    if args.rehearse_cpu:
        failed = failed or ["rehearsal: the children ran on the CPU"]
    if failed:
        raise SmokeFailure("device check: " + "; ".join(failed))
    if miner_device_seen != report["device"]:
        raise SmokeFailure(f"miner saw {miner_device_seen}, node B "
                           f"{report['device']}")
    return report["device"]


def run_four_chips(args, work: str) -> dict:
    """The mining pod over the chips of one host, and what it is compared
    with: ``miner --device mesh --once`` on 4 devices, then on 1, on jobs
    of the same difficulty, both accepted by a host-verify node."""
    wallet = Wallet(args.seed)
    device = "cpu" if args.rehearse_cpu else "tpu"
    # profile.enabled: the miner prints its counters at exit
    env = {"UPOW_DEVICE_DEVICE": device, "UPOW_PROFILE_ENABLED": "1"}
    if args.rehearse_cpu:
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    a = NodeProc("A", work, "cpu")
    a.start()
    recs = {}
    for n in (4, 1):
        before = tip(a)
        wait_next_second(before["timestamp"])
        rec = mine_block(a, wallet.address, "mesh", work, f"mesh x{n}",
                         env=dict(env, UPOW_DEVICE_MESH_DEVICES=str(n)),
                         timeout=args.miner_timeout)
        if tip(a)["id"] != before["id"] + 1:
            raise SmokeFailure(f"mesh x{n}: block not accepted")
        mesh = rec["mesh"] or {}
        say(f"[4] mesh x{n}: difficulty={before['difficulty']} nonce="
            f"{rec['nonce']} reported={rec['mhs']} MH/s ({rec['hashes']} "
            f"hashes in {rec['search_s']}s, first dispatch "
            f"{rec['first_dispatch_s']}s) device={rec['device']} mesh={mesh}")
        shards = mesh.get("last_round_shards", [])
        devices = mesh.get("devices", [])
        if len(devices) != n or len(set(devices)) != n:
            raise SmokeFailure(f"mesh x{n} ran on devices {devices}")
        if len(shards) != n or any(hi <= lo for lo, hi in shards) or any(
                shards[i][1] != shards[i + 1][0] for i in range(n - 1)):
            raise SmokeFailure(f"mesh x{n} shards not disjoint: {shards}")
        want_body = "jnp" if args.rehearse_cpu else "pallas"
        if mesh.get("body") != want_body:
            raise SmokeFailure(f"mesh x{n} ran the {mesh.get('body')} body "
                               f"of the resident program, not {want_body}")
        counters = rec["counters"] or {}
        seen_rounds = {k: counters.get(k) for k in (
            "mine.mesh.rounds_pallas", "mine.rounds", "mine.mesh.job_layouts",
            "kernel.mine_mesh.compile_cache_misses")}
        say(f"[4] mesh x{n}: dispatches={mesh.get('dispatches')} "
            f"job_layouts={mesh.get('job_layouts')} "
            f"jit_entries={mesh.get('jit_entries')} {seen_rounds}")
        # every dispatched round ran the kernel (none on the CPU), and
        # the resident program was one compile key
        if seen_rounds["mine.mesh.rounds_pallas"] != (
                0 if args.rehearse_cpu else mesh.get("dispatches")) \
                or seen_rounds["kernel.mine_mesh.compile_cache_misses"] != 1:
            raise SmokeFailure(f"mesh x{n} counters {seen_rounds} against "
                               f"{mesh.get('dispatches')} dispatches")
        # the one job was laid over the mesh once, and met the program
        # the arm compiled: a second jit entry is a warm / job mismatch
        if mesh.get("jit_entries") != 1 or mesh.get("job_layouts") != 1 \
                or seen_rounds["mine.mesh.job_layouts"] != 1:
            raise SmokeFailure(
                f"mesh x{n} laid its job {mesh.get('job_layouts')} times "
                f"(counter {seen_rounds['mine.mesh.job_layouts']}) into "
                f"{mesh.get('jit_entries')} jit entries, wanted 1 and 1")
        recs[n] = rec
    a.stop()
    seen = recs[4]["device"]
    if args.rehearse_cpu:
        raise SmokeFailure("rehearsal: the children ran on the CPU")
    if not seen or seen["platform"] != "tpu" or seen["count"] != 4:
        raise SmokeFailure(f"the mesh miner saw {seen}, wanted 4 TPU devices")
    return seen


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny run with every child on the CPU; checks the "
                         "control flow and always ends ok:false")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--b-start", choices=("height3", "blank"),
                    default="height3",
                    help="node B starts from a copy of A's database at "
                         "height 3 (default) or blank, replaying blocks 1-4")
    ap.add_argument("--b-microbatch", type=int, default=0,
                    help="device.verify_microbatch for node B; 0 (default "
                         "here) = the block is one dispatch; 1024 is the "
                         "node's own default")
    ap.add_argument("--b-txid", choices=("host", "auto"), default="host",
                    help="device.txid_backend for node B; the node's own "
                         "default, auto, compiles three sha256 programs on "
                         "its first big block to measure a crossover")
    ap.add_argument("--miner-timeout", type=float, default=600.0)
    ap.add_argument("--accept-timeout", type=float, default=2400.0)
    ap.add_argument("--workdir", default=None,
                    help="scratch + logs (default <checkout>/chiprun_out/"
                         "smoke); the databases in it are removed at the end")
    args = ap.parse_args()
    work = os.path.abspath(args.workdir or os.path.join(
        ROOT, "chiprun_out", "smoke"))
    shutil.rmtree(os.path.join(work, "db"), ignore_errors=True)
    os.makedirs(os.path.join(work, "db"), exist_ok=True)
    device, error = None, None
    try:
        # build the C++ host library once, here, so A and the first
        # miner do not race to compile it (host only; no JAX backend)
        from upow_tpu import native

        native.load()
        device = (run_four_chips if args.chips == 4 else run_one_chip)(
            args, work)
    except SmokeFailure as e:
        error = str(e)
    except Exception as e:  # anything else is a failed phase too
        import traceback

        traceback.print_exc()
        error = f"{type(e).__name__}: {e}"
    finally:
        _stop_all()
        shutil.rmtree(os.path.join(work, "db"), ignore_errors=True)
    if error is not None:
        say(f"FAILED: {error}")
        print(json.dumps({"ok": False, "error": error[:500]}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
