"""The cell at the miner CLI's default range, ``mine-sweep-1chip-full``:
its files are found by name, and the driver's check of an expired job
holds a miner to the whole of ``[0, 2^32 - 1)``: 2^32 nonces less the
sentinel, so a last round of ``round_nonces - 1``.  The stand-in here
speaks the miner's lines over that range (``fake_miner.py`` sweeps whole
rounds only, and hashes every searchable round through)."""

import io
import json
import sys
import textwrap
from contextlib import redirect_stdout

import pytest

import run as bench_run
from harness import manifest

CELL = "mine-sweep-1chip-full"
ROUND = 1 << 28          # 16 rounds a job: the last has ROUND - 1
TINY = {"warm_difficulties": [2.0], "after_difficulties": [2.5],
        "difficulty": 11.0, "round_nonces": ROUND,
        "arm_timeout_s": 30, "warm_timeout_s": 60}

FULL_RANGE_MINER = textwrap.dedent("""
    import hashlib, json, sys, time, urllib.request
    sys.path[:0] = [{bench!r}, {bench!r} + "/tests"]
    from child_signals import answer_signals
    from harness import powref
    answer_signals()
    address_hex, node, batch, last = sys.argv[1], sys.argv[2], {batch}, sys.argv[3]
    out = lambda s: print(s, flush=True)

    def http(url, payload=None):
        data = json.dumps(payload).encode() if payload is not None else None
        req = urllib.request.Request(url, data=data, headers={{
            "Content-Type": "application/json"}} if data else {{}})
        with urllib.request.urlopen(req, timeout=20) as resp:
            return json.loads(resp.read().decode())

    out(f"upow_tpu miner: backend=fake shard=0/1 nonces=[0, {{1 << 32}}) node={{node}}")
    out("device: platform=tpu kind=TPU v5 lite count=1 compile_cache=-")
    while True:
        info = http(node + "get_mining_info")["result"]
        tip, diff = info["last_block"]["hash"], info["difficulty"]
        hashes = info["pending_transactions_hashes"]
        block_no = info["last_block"]["id"] + 1
        out(f"difficulty: {{diff}}  block: {{block_no}}  confirming {{len(hashes)}} transactions")
        prefix = (bytes([2]) + bytes.fromhex(tip) + bytes.fromhex(address_hex)
                  + bytes.fromhex(powref.miner_merkle(hashes))
                  + int(time.time()).to_bytes(4, "little")
                  + int(diff * 10).to_bytes(2, "little"))
        if diff < 8:      # a searchable job: the lowest hit, by hashlib
            want, allowed = powref.target(tip, diff)
            hit = next(n for n in range(1 << 20) if powref.satisfies(
                hashlib.sha256(prefix + n.to_bytes(4, "little")).hexdigest(),
                want, allowed))
            out(f"found nonce {{hit}} at 1.00 MH/s ({{batch}} hashes in 0.01s, first dispatch 0.00s)")
            reply = http(node + "push_block", {{
                "block_content": (prefix + hit.to_bytes(4, "little")).hex(),
                "txs": hashes, "block_no": block_no}})
            out(str(reply))
            out("BLOCK MINED\\n")
            continue
        sizes = [batch] * ((1 << 32) // batch - 1)
        sizes += {{"short": [batch - 1], "missing": [], "whole": [batch]}}[last]
        tried = 0
        for size in sizes:
            time.sleep(0.002)
            tried += size
            out(f"1.00 MH/s ({{tried}} hashes)")
        out(f"template expired after {{tried}} hashes; refreshing")
""")


def test_the_full_range_cell_finds_its_files():
    mf = manifest.load_manifest()
    cell = manifest.find_cell(mf, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("miner-solo", "mine-sweep-full", 1)
    config = manifest.load_config(mf, cell)
    assert set(config["children"]) == set(config["rehearse_children"]) == {"1"}
    assert "--shard" not in config["children"]["1"]["argv"]
    assert "nonce_range" not in config["reduced"] and \
        set(config["reduced"]) == {"node", "competing_miners"}
    assert "sentinel" in config["assumed"]
    assert config["reference"] == "benchmarks/harness/powref.py"
    # the traffic is mine-sweep's in all but the miner's arguments
    traffic = manifest.load_traffic(cell["traffic"])
    base = manifest.load_traffic("mine-sweep")
    assert traffic["miner_args"] == [] and base["miner_args"]
    assert "traced_window_s" not in traffic
    own = {"what", "miner_args", "why_shard"}
    assert {k: v for k, v in traffic.items() if k not in own} == \
        {k: v for k, v in base.items() if k not in own}
    # fifteen per-layer metrics: the old cells' thirteen and the two new
    layer = manifest.layer_metrics_for(mf, CELL)
    old = manifest.layer_metrics_for(mf, "mine-sweep-1chip")
    assert len(layer) == 15 and len(old) == 13
    new = {e["name"]: spec for e, spec in layer
           if e["name"] not in {e["name"] for e, _s in old}}
    assert set(new) == {"tail_issue_ms.mine", "job_sweep_s.mine"}
    assert [(s["reader"], s["span"], s["stat"], s["scale"])
            for s in (new["tail_issue_ms.mine"], new["job_sweep_s.mine"])] == \
        [("span_stat", "mine.round.tail", "max", 1e-6),
         ("span_stat", "mine.job", "mean", 1e-9)]
    assert [m["name"] for m in manifest.end_to_end_for(mf, CELL)] == \
        ["search_mhs", "setup_s"]


@pytest.mark.parametrize("last,correct", [
    ("short", True),      # 15 rounds of 2^28 and one of 2^28 - 1
    ("missing", False),   # the last round never claimed
    ("whole", False),     # the sentinel claimed as searched
])
def test_an_expired_job_is_held_to_the_range_less_the_sentinel(
        tmp_path, last, correct):
    from upow_tpu.core import curve
    from upow_tpu.core.codecs import point_to_string, string_to_bytes

    seed = 11
    _d, pub = curve.keygen(rng=0x5EED0000 + seed)
    script = tmp_path / "full_range_miner.py"
    script.write_text(FULL_RANGE_MINER.format(bench=manifest.BENCH,
                                              batch=ROUND))
    argv = [sys.executable, str(script),
            string_to_bytes(point_to_string(pub)).hex(), "{node}", last]
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(
            ["--workload", CELL, "--seed", str(seed), "--seconds", "1.5",
             "--trace", "0"],
            faults={"child_argv": argv, "traffic": TINY})
    lines = out.getvalue().strip().splitlines()
    assert rc == 0, lines[-5:]
    result = json.loads(lines[-1])
    check = next(ln for ln in lines if ln.startswith(
        "[check] expired_jobs_short_of_their_nonce_range"))
    assert "add up to 4294967295" in check
    assert ("FAILED" not in check) == correct, check
    assert result["correct"] is correct, [
        ln for ln in lines if "FAILED" in ln]
