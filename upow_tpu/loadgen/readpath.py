"""Read-path cache scenario: Zipfian wallets under block cadence.

Boots a real node over the funded fixture and replays the SAME
deterministic request schedule twice — once with the
``X-Upow-Cache-Bypass`` header on every request (every response
computed fresh from state) and once through the hot-state cache —
while mining blocks at a fixed cadence so each pass pays the same
invalidation churn.  The headline is the p99 speedup of the cached
pass over the bypassed one.

The differential is built in and runs FIRST: at every chain-mutation
stage (initial, post-block, forced reorg via ``remove_blocks``,
re-accept) each sampled endpoint is fetched twice through the cache
and once bypassed, and all three bodies must be byte-identical.  Any
mismatch means the cache returned something state would not have — the
scenario then refuses to report performance: latency sections are
omitted and ``speedup_p99`` is zeroed.
"""

from __future__ import annotations

import random
import time
from dataclasses import asdict, dataclass
from typing import Dict, List, Tuple

from ..logger import get_logger
from .runner import summarize_latencies

log = get_logger("loadgen")

_BYPASS_HEADER = "X-Upow-Cache-Bypass"

# (endpoint tag, path, params) — tag groups latencies per endpoint
Request = Tuple[str, str, Dict[str, str]]


@dataclass
class ReadpathSpec:
    """Sizing knobs.  ``block_every`` sets the invalidation cadence:
    every window of that many requests starts with a fresh generation,
    so the first touch of each distinct key after the bump is a miss —
    keep the window two orders of magnitude above the distinct-key
    count or the cached p99 lands on recompute latency, not hits."""

    seed: int = 0xC0FFEE
    n_wallets: int = 12       # address universe; rank 0 = funded hot wallet
    zipf_s: float = 1.2
    n_requests: int = 3000    # per pass
    block_every: int = 1500   # mine (→ invalidate) every N requests
    n_fan: int = 12           # fixture fanout: n_fan * n_per leaf UTXOs
    n_per: int = 48           # (the hot wallet is BIG — that's the point)
    history_limit: int = 25   # per-row get_nice_transaction queries
    blocks_limit: int = 60    # tx-detailed block pages

    @classmethod
    def smoke(cls) -> "ReadpathSpec":
        # same per-request weight as the default; just fewer of them
        return cls(n_wallets=6, n_requests=1200, block_every=600)

    def to_dict(self) -> dict:
        return asdict(self)


def build_readpath_schedule(spec: ReadpathSpec, addresses: List[str],
                            tx_hash: str) -> List[Request]:
    """Deterministic request mix: Zipf-ranked wallet reads (the heavy
    funded account is the hot spot), miner template polling, and the
    public chain/browser queries the cache fronts."""
    rng = random.Random(spec.seed)
    ranks = list(range(len(addresses)))
    weights = [1.0 / (r + 1) ** spec.zipf_s for r in ranks]

    def wallet() -> str:
        return addresses[rng.choices(ranks, weights)[0]]

    events: List[Request] = []
    for _ in range(spec.n_requests):
        roll = rng.random()
        if roll < 0.40:
            events.append(("address_info", "/get_address_info",
                           {"address": wallet(), "show_pending": "true",
                            "verify": "true"}))
        elif roll < 0.60:
            events.append(("history", "/get_address_transactions",
                           {"address": wallet(),
                            "limit": str(spec.history_limit)}))
        elif roll < 0.75:
            events.append(("mining_info", "/get_mining_info", {}))
        elif roll < 0.85:
            events.append(("blocks_details", "/get_blocks_details",
                           {"offset": "0",
                            "limit": str(spec.blocks_limit)}))
        elif roll < 0.93:
            events.append(("supply", "/get_supply_info", {}))
        else:
            events.append(("tx", "/get_transaction", {"tx_hash": tx_hash}))
    return events


def _differential_requests(hot_addr: str, cold_addr: str,
                           tx_hash: str) -> List[Tuple[str, Dict[str, str]]]:
    """One probe per cached entry class (plus variants that share a
    class but must not share a key)."""
    return [
        ("/get_address_info", {"address": hot_addr, "show_pending": "true",
                               "verify": "true"}),
        ("/get_address_info", {"address": cold_addr}),
        ("/get_address_transactions", {"address": hot_addr, "limit": "8"}),
        ("/get_pending_transactions", {}),
        ("/get_supply_info", {}),
        ("/get_blocks", {"offset": "0", "limit": "10"}),
        ("/get_blocks_details", {"offset": "0", "limit": "5"}),
        ("/get_block", {"block": "2", "full_transactions": "true"}),
        ("/get_block", {"block": "2"}),
        ("/get_block_details", {"block": "2"}),
        ("/get_transaction", {"tx_hash": tx_hash}),
        ("/get_validators_info", {}),
        ("/get_delegates_info", {}),
    ]


async def _fetch(client, path: str, params: Dict[str, str],
                 bypass: bool) -> Tuple[int, bytes, float]:
    headers = {_BYPASS_HEADER: "1"} if bypass else {}
    t0 = time.perf_counter()
    resp = await client.get(path, params=params, headers=headers)
    body = await resp.read()
    return resp.status, body, time.perf_counter() - t0


async def _diff_stage(client, reqs, stage: str, diff: dict) -> None:
    """cached-populate, cached-hit, bypass — all three byte-identical
    or the stage records a mismatch (and the run refuses to report)."""
    mismatches = []
    for path, params in reqs:
        s1, b1, _ = await _fetch(client, path, params, bypass=False)
        s2, b2, _ = await _fetch(client, path, params, bypass=False)
        s3, b3, _ = await _fetch(client, path, params, bypass=True)
        diff["checks"] += 1
        if not (s1 == s2 == s3 and b1 == b2 == b3):
            diff["mismatches"] += 1
            diff["ok"] = False
            mismatches.append({
                "path": path, "params": params,
                "status": [s1, s2, s3],
                "cached_first": b1[:160].decode("utf-8", "replace"),
                "cached_hit": b2[:160].decode("utf-8", "replace"),
                "bypass": b3[:160].decode("utf-8", "replace")})
    diff["stages"].append({"stage": stage, "probes": len(reqs),
                           "mismatches": mismatches})


async def _run_pass(client, schedule: List[Request], mine_block,
                    block_every: int, bypass: bool) -> Dict[str, List[float]]:
    lat: Dict[str, List[float]] = {}
    for i, (tag, path, params) in enumerate(schedule):
        if block_every and i and i % block_every == 0:
            await mine_block([])
        status, _, dt = await _fetch(client, path, params, bypass)
        if status != 200:
            raise RuntimeError(
                f"readpath: {path} answered {status} (bypass={bypass})")
        lat.setdefault(tag, []).append(dt)
    return lat


async def run_readpath(spec: ReadpathSpec = None) -> dict:
    """Run differential + both passes; return the scenario artifact."""
    from aiohttp.test_utils import TestClient, TestServer

    from .fixtures import chain_with_utxo_fanout
    from ..config import Config
    from ..core import clock, curve, point_to_string
    from ..node.app import Node

    spec = spec or ReadpathSpec()
    state, fix_manager, _d, _pub, addr, mids, mine_block = \
        await chain_with_utxo_fanout(spec.n_fan, spec.n_per,
                                     spec.seed & 0xFFFF)
    addresses = [addr]
    for i in range(1, spec.n_wallets):
        _, pub_i = curve.keygen(rng=(spec.seed << 8) ^ (0xCA5E + i))
        addresses.append(point_to_string(pub_i))
    tx_hash = mids[0].hash()

    cfg = Config()
    cfg.node.db_path = ""
    cfg.node.seed_url = ""
    cfg.node.peers_file = ""
    cfg.node.ip_config_file = ""
    cfg.log.path = ""
    cfg.log.console = False
    # sole writer: the hooks, not the revalidation backstop, must keep
    # the cache honest — exactly what the differential interrogates
    cfg.cache.revalidate_interval = -1.0
    node = Node(cfg, state=state)
    if node.hotcache.enabled:
        # blocks here land through the FIXTURE's manager, not the
        # node's, so point its post-commit hook at the same bump (the
        # reorg path is already covered by state.on_blocks_removed)
        fix_manager.on_state_committed = node.hotcache.bump
    server = TestServer(node.app)
    await server.start_server()
    client = TestClient(server)
    node.started = True
    node.rate_limiter.enabled = False
    try:
        diff = {"ok": True, "checks": 0, "mismatches": 0, "stages": []}
        reqs = _differential_requests(addr, addresses[-1], tx_hash)
        await _diff_stage(client, reqs, "initial", diff)
        await mine_block([])
        await _diff_stage(client, reqs, "post_block", diff)
        last = await state.get_last_block()
        await state.remove_blocks(last["id"])  # forced reorg of the tip
        await _diff_stage(client, reqs, "post_reorg", diff)
        await mine_block([])
        await _diff_stage(client, reqs, "post_reaccept", diff)

        schedule = build_readpath_schedule(spec, addresses, tx_hash)
        bypass_lat = await _run_pass(client, schedule, mine_block,
                                     spec.block_every, bypass=True)
        stats0 = node.hotcache.stats()
        cached_lat = await _run_pass(client, schedule, mine_block,
                                     spec.block_every, bypass=False)
        stats1 = node.hotcache.stats()
    finally:
        await client.close()
        await server.close()
        await node.close()
        clock.reset()

    hits = stats1["hits"] - stats0["hits"]
    misses = stats1["misses"] - stats0["misses"]
    result = {
        "kind": "readpath",
        "spec": spec.to_dict(),
        "differential": diff,
        "cache": stats1,
        "cached_pass": {
            "hits": hits, "misses": misses,
            "hit_ratio": round(hits / (hits + misses), 4)
            if hits + misses else None},
    }
    if not diff["ok"]:
        log.warning("readpath differential FAILED (%d/%d probes) — "
                    "refusing to report latencies",
                    diff["mismatches"], diff["checks"])
        result["speedup_p99"] = 0.0
        return result

    flat_bypass = [v for vals in bypass_lat.values() for v in vals]
    flat_cached = [v for vals in cached_lat.values() for v in vals]
    result["bypass"] = summarize_latencies(flat_bypass)
    result["cached"] = summarize_latencies(flat_cached)
    result["per_endpoint"] = {
        tag: {"bypass": summarize_latencies(bypass_lat[tag]),
              "cached": summarize_latencies(cached_lat[tag])}
        for tag in sorted(bypass_lat) if tag in cached_lat}
    cached_p99 = result["cached"]["p99_ms"]
    result["speedup_p99"] = round(
        result["bypass"]["p99_ms"] / cached_p99, 2) if cached_p99 else None
    return result
