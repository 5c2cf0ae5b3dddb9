"""Peer book + HTTP RPC client (reference upow/node/nodes_manager.py:24-210).

Semantics replicated: a JSON peer file guarded by a file lock; peers are
"active" if they messaged us within 7 days, pruned after 90 days of
silence, capped at 100; the propagate set is a sample of up to 10 active
plus up to 10 never-seen peers; RPC requests carry a ``Sender-Node``
header as the return address and responses are capped at 20 MB.

Transport is aiohttp (the reference uses httpx) — one shared session per
process, created lazily on the running loop.

Resilience: every :class:`NodeInterface` RPC can run under a
:class:`~upow_tpu.resilience.ResilienceContext` — per-peer circuit
breaker gate, deterministic fault injection, then retry with jittered
backoff under a total deadline.  Without a context (standalone clients,
older tests) behaviour is exactly the single-attempt original.  The
:class:`PeerBook` carries the breaker registry so gossip/sync peer
selection can skip open circuits and prefer high-score peers.
"""

from __future__ import annotations

import asyncio
import json
import os
import random
import time
from typing import Dict, List, Optional

import aiohttp
from filelock import FileLock

from ..config import NodeConfig
from ..logger import get_logger
from .. import trace
from ..resilience import (BreakerRegistry, CircuitOpenError,
                          ResilienceContext, call_with_retry, faultinject)

# Exceptions worth retrying: transport-level trouble, not peer-side
# application errors (an HTTP error body parses fine and is NOT retried).
TRANSIENT_ERRORS = (aiohttp.ClientError, asyncio.TimeoutError,
                    ConnectionError, OSError)

log = get_logger("peers")


def _normalize(url: str) -> str:
    url = (url or "").strip().strip("/")
    if url and not url.startswith("http"):
        url = "http://" + url
    return url


class PeerBook:
    """Durable peer registry with active/unseen classes and pruning."""

    def __init__(self, cfg: Optional[NodeConfig] = None,
                 breakers: Optional[BreakerRegistry] = None):
        self.cfg = cfg or NodeConfig()
        # Health scores for selection; a default registry keeps
        # standalone PeerBooks working with every peer reading healthy.
        self.breakers = breakers if breakers is not None else \
            BreakerRegistry()
        self.path = self.cfg.peers_file
        self._lock = FileLock(self.path + ".lock") if self.path else None
        self._data: Dict[str, dict] = {}
        self._load()
        if not self._data and self.cfg.seed_url:
            self.add(self.cfg.seed_url)

    # ------------------------------------------------------- persistence --
    def _load(self) -> None:
        if self.path and os.path.exists(self.path):
            try:
                # RC001: a few KB read once, while the node is built and
                # before it serves traffic
                with open(self.path) as f:  # upowlint: disable=RC001
                    self._data = json.load(f).get("nodes", {})
            except (json.JSONDecodeError, OSError):
                self._data = {}

    def save(self) -> None:
        if not self.path:
            return
        with self._lock:
            tmp = self.path + ".tmp"
            # RC001: the peer book is a few KB; the synchronous
            # write-then-rename under the lock is what keeps add/prune
            # atomic against concurrent savers
            with open(tmp, "w") as f:  # upowlint: disable=RC001
                json.dump({"nodes": self._data}, f)
            os.replace(tmp, self.path)

    # ------------------------------------------------------------ updates --
    def add(self, url: str) -> bool:
        url = _normalize(url)
        if not url or url in self._data:
            return False
        if len(self._data) >= self.cfg.max_peers:
            self.prune()
            if len(self._data) >= self.cfg.max_peers:
                return False
        self._data[url] = {"added": int(time.time()), "last_message": 0}
        self.save()
        return True

    def update_last_message(self, url: str) -> None:
        url = _normalize(url)
        if url in self._data:
            self._data[url]["last_message"] = int(time.time())
            self.save()

    def remove(self, url: str) -> None:
        if self._data.pop(_normalize(url), None) is not None:
            self.save()

    def prune(self) -> None:
        """Drop peers silent for prune_after (but keep never-seen entries
        younger than that, by their added time)."""
        now = time.time()
        doomed = [
            u for u, meta in self._data.items()
            if now - max(meta.get("last_message", 0), meta.get("added", 0))
            > self.cfg.prune_after
        ]
        for u in doomed:
            del self._data[u]
        if doomed:
            self.save()

    # ------------------------------------------------------------- reads --
    def all_nodes(self) -> List[str]:
        return list(self._data)

    def recent_nodes(self) -> List[str]:
        """Peers that messaged us within the active window; falls back to
        everything known when nobody has (fresh node bootstrapping from
        the seed)."""
        now = time.time()
        active = [
            u for u, meta in self._data.items()
            if now - meta.get("last_message", 0) < self.cfg.active_within
            and meta.get("last_message", 0) > 0
        ]
        return active or list(self._data)

    def _healthy_sample(self, pool: List[str], k: int) -> List[str]:
        """Sample ``k`` peers, skipping open circuits and preferring the
        high-score tier.  With no breaker history every peer scores 1.0
        and this is exactly the reference's ``random.sample``."""
        pool = [u for u in pool if self.breakers.usable(u)]
        good = [u for u in pool if self.breakers.score(u) >= 0.5]
        weak = [u for u in pool if self.breakers.score(u) < 0.5]
        picks = random.sample(good, min(k, len(good)))
        if len(picks) < k:
            picks += random.sample(weak, min(k - len(picks), len(weak)))
        return picks

    def propagate_nodes(self) -> List[str]:
        """≤10 active + ≤10 never-seen (nodes_manager.py:144-149), healthy
        first.

        "Active" is the 7-day window (the reference samples
        get_recent_nodes here): a peer last heard from BEYOND the window
        is neither active nor never-seen and is not gossiped to.  On top
        of the reference semantics, peers whose circuit is open are
        skipped and degraded-score peers only fill leftover slots."""
        k = self.cfg.propagate_sample
        now = time.time()
        active = [
            u for u, meta in self._data.items()
            if meta.get("last_message", 0) > 0
            and now - meta["last_message"] < self.cfg.active_within
        ]
        unseen = [u for u, meta in self._data.items()
                  if meta.get("last_message", 0) == 0]
        picks = self._healthy_sample(active, k)
        picks += self._healthy_sample(unseen, k)
        # Health-ranked fan-out, consistent with sync_blockchain's
        # candidate ordering: the sampled set keeps the reference's
        # gossip diversity, but sends go to the healthiest peers first
        # so a degraded peer's slow/failing RPC is the last in line,
        # not an equal-odds first pick.
        return self.ranked(picks)

    def ranked(self, urls: List[str]) -> List[str]:
        """Sort candidate peers by descending health score with open
        circuits pushed to the back (sync source ordering / gossip
        fan-out order).  Equal-health peers tie-break on URL so the
        ordering is a pure function of breaker state — swarm scenarios
        and operators replaying a /debug/breakers snapshot see the
        same decision."""
        return sorted(urls, key=lambda u: (
            0 if self.breakers.usable(u) else 1,
            -self.breakers.score(u), u))

    def contains(self, url: str) -> bool:
        return _normalize(url) in self._data


class NodeInterface:
    """RPC client for one remote node (nodes_manager.py:174-210)."""

    def __init__(self, url: str, cfg: Optional[NodeConfig] = None,
                 session: Optional[aiohttp.ClientSession] = None,
                 resilience: Optional[ResilienceContext] = None):
        self.base_url = _normalize(url)
        self.url = self.base_url
        self.cfg = cfg or NodeConfig()
        self._session = session
        self._own_session = session is None  # close() only closes what we made
        self._resilience = resilience

    async def _get_session(self) -> aiohttp.ClientSession:
        if self._session is None or self._session.closed:
            self._session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=self.cfg.http_timeout))
            self._own_session = True
        return self._session

    async def close(self) -> None:
        if (self._own_session and self._session is not None
                and not self._session.closed):
            await self._session.close()

    async def _read_capped(self, resp: aiohttp.ClientResponse) -> dict:
        buf = b""
        async for chunk in resp.content.iter_chunked(64 * 1024):
            buf += chunk
            if len(buf) > self.cfg.response_cap:
                raise ValueError("response too large")
        return json.loads(buf or b"{}")

    async def _resilient(self, attempt, label: str,
                         site: Optional[str] = None,
                         site_key: Optional[str] = None):
        """Run one RPC attempt factory under the breaker → fault-injection
        → retry stack.  Without a ResilienceContext this is a transparent
        single attempt (standalone clients keep the original behaviour).
        ``site`` renames the fault-injection site away from the default
        ``rpc.<label>`` (the snapshot bootstrap fires ``snapshot.fetch``
        so chaos specs can target restore traffic without touching the
        ordinary RPC plane)."""
        ctx = self._resilience
        if ctx is None:
            return await attempt()
        breaker = ctx.breakers.get(self.base_url)
        if not breaker.available():
            trace.inc("resilience.breaker_rejected")
            raise CircuitOpenError(self.base_url)

        async def guarded():
            injector = faultinject.get_injector()
            if injector is not None:
                await injector.fire(site or f"rpc.{label}",
                                    site_key or self.base_url)
            return await attempt()

        def on_retry(exc, retry_no):
            trace.inc("resilience.rpc_retries")
            log.debug("retry %d for %s %s: %s", retry_no, self.base_url,
                      label, exc)

        try:
            out = await call_with_retry(
                guarded, ctx.policy, retry_on=TRANSIENT_ERRORS,
                rng=ctx.rng, on_retry=on_retry)
        except TRANSIENT_ERRORS:
            breaker.record_failure()
            raise
        breaker.record_success()
        return out

    async def request(self, path: str, args: dict,
                      sender_node: str = "") -> dict:
        """Wire-compatible RPC: POST json for push_block/push_tx, GET with
        query params for everything else (reference
        nodes_manager.py:192-209) — so e.g. gossiped ``add_node`` lands on
        peers' GET routes."""
        headers = self._rpc_headers(sender_node)

        async def attempt() -> dict:
            session = await self._get_session()
            if path in ("push_block", "push_tx"):
                async with session.post(f"{self.base_url}/{path}",
                                        json=args, headers=headers) as resp:
                    return await self._read_capped(resp)
            params = {k: str(v) for k, v in args.items()}
            async with session.get(f"{self.base_url}/{path}", params=params,
                                   headers=headers) as resp:
                return await self._read_capped(resp)

        return await self._resilient(attempt, path)

    @staticmethod
    def _rpc_headers(sender_node: str) -> dict:
        """Common outbound headers: peer identity plus the current trace
        ID, so a gossiped tx/block keeps one trace across nodes (the
        receiving middleware adopts X-Upow-Trace)."""
        headers = {"Sender-Node": sender_node} if sender_node else {}
        tid = trace.current_trace_id()
        if tid is not None:
            headers[trace.TRACE_HEADER] = tid
        return headers

    async def get(self, path: str, params: Optional[dict] = None,
                  sender_node: str = "", site: Optional[str] = None,
                  site_key: Optional[str] = None) -> dict:
        headers = self._rpc_headers(sender_node)

        async def attempt() -> dict:
            session = await self._get_session()
            async with session.get(f"{self.base_url}/{path}",
                                   params=params or {},
                                   headers=headers) as resp:
                return await self._read_capped(resp)

        return await self._resilient(attempt, path, site=site,
                                     site_key=site_key)

    @staticmethod
    def _result(res: dict):
        """Unwrap an RPC envelope; a peer's error/rate-limit body becomes
        a readable error instead of a bare KeyError."""
        if "result" not in res:
            raise RuntimeError(
                f"peer error: {res.get('error', res)!s:.200}")
        return res["result"]

    async def get_block(self, block_no: int) -> dict:
        return self._result(await self.get(
            "get_block", {"block": str(block_no),
                          "full_transactions": "false"}))

    async def get_blocks(self, offset: int, limit: int) -> list:
        return self._result(await self.get(
            "get_blocks", {"offset": str(offset), "limit": str(limit)}))

    async def get_nodes(self) -> list:
        return self._result(await self.get("get_nodes"))

    # ------------------------------------------------------- snapshots ----
    # Both run under the ordinary breaker/retry stack but fire the
    # dedicated ``snapshot.fetch`` site (keyed per document) so a chaos
    # spec can fault restore traffic — or one specific chunk — without
    # touching the rpc.* plane.

    async def snapshot_manifest(self) -> dict:
        return self._result(await self.get(
            "snapshot/manifest", site="snapshot.fetch",
            site_key=f"{self.base_url}#manifest"))

    async def snapshot_chunk(self, i: int) -> bytes:
        doc = self._result(await self.get(
            f"snapshot/chunk/{int(i)}", site="snapshot.fetch",
            site_key=f"{self.base_url}#chunk/{int(i)}"))
        return bytes.fromhex(doc["data"])
