"""The node: HTTP API + P2P gossip + chain sync (reference upow/node/main.py).

aiohttp implementation of the full 20-endpoint surface, the gossip
``propagate`` fan-out, the Sender-Node peer-learning middleware, tx intake
with a 100-entry dedup cache, push_block with sync-on-gap triggers, and
``sync_blockchain`` with the 500-block reorg window — all against one
:class:`~upow_tpu.state.storage.ChainState` + :class:`BlockManager`.

Request/response wire shapes match the reference endpoint-for-endpoint
(main.py:461-1102): every handler returns the ``{"ok": bool, ...}``
envelope, accepts both GET query params and POST JSON bodies where the
reference does, and reads/sets the ``Sender-Node`` header.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import os
import random
import re
import sys
import time
from collections import deque
from decimal import Decimal
from typing import List, Optional

from aiohttp import web

from .. import telemetry, trace
from ..config import Config
from ..core.constants import (ENDIAN, MAX_BLOCK_SIZE_HEX, MAX_SUPPLY,
                              SMALLEST, VERSION)
from ..core.clock import timestamp
from ..core.rewards import get_circulating_supply
from ..core.header import block_to_bytes, split_block_content
from ..core.merkle import merkle_root
from ..core.tx import AmbiguousSignatureError, CoinbaseTx, Tx, tx_from_hex
from ..logger import get_logger, setup_logging
from ..mempool import IntakeCoordinator, Mempool, MiningInfoCache, TTLSet
from ..resilience import (BreakerRegistry, ResilienceContext, faultinject)
from ..state.storage import ChainState
from ..verify.block import BlockManager
from ..verify.txverify import TxVerifier, run_sig_checks_async
from .ipfilter import IpFilter, is_local_ip
from .peers import NodeInterface, PeerBook, _normalize

log = get_logger("node")

GENESIS_PREV_HASH = (18_884_643).to_bytes(32, ENDIAN).hex()


class _BadParam(Exception):
    """Malformed query parameter — answered as a 422 validation error
    (the reference's FastAPI layer rejects type mismatches the same
    way; a raw int() here used to 500)."""


def _int_q(q, name: str, default: int, cap: int = None) -> int:
    raw = q.get(name)
    if raw is None or raw == "":
        return default
    try:
        value = int(raw)
    except ValueError:
        raise _BadParam(name) from None
    # clamp into [0, int64 max]: a 10^40 offset would overflow the
    # sqlite INTEGER binding into a 500, and NEGATIVE values are worse
    # than an error — sqlite treats LIMIT -1 as "no limit" (an
    # unbounded table dump) and postgres rejects it mid-handler
    value = max(0, min(value, 2 ** 63 - 1))
    return min(value, cap) if cap is not None else value

# the one banned address (main.py:426-430)
_BANNED_ADDRESSES = {"DgQKikeDqS2Fzue23KuA36L4eJSFh649zA9jJ6zwbzUMp"}

# any value in this header skips the hot-state cache for one request
# (the response is computed fresh and NOT stored) — the loadgen
# differential and operators diagnosing a suspected stale read use it;
# correctness never depends on it because entries are generation-keyed
_CACHE_BYPASS_HEADER = "X-Upow-Cache-Bypass"

# every get_address_info query flag that shapes the response — the
# cache key must carry all of them (a hit is only valid for the exact
# flag combination it was computed under)
_ADDRESS_INFO_FLAGS = (
    "show_pending", "verify", "stake_outputs", "delegate_spent_votes",
    "delegate_unspent_votes", "inode_registration_outputs",
    "validator_unspent_votes", "validator_spent_votes", "address_state")


def _fmt_amount(smallest_units: int) -> str:
    return "{:f}".format(Decimal(smallest_units) / SMALLEST)


class Node:
    """One node instance: state + manager + peers + HTTP app.

    In-process instantiable (the multi-node integration harness runs
    several against isolated sqlite files and wires their HTTP apps
    together via aiohttp's test utilities).
    """

    def __init__(self, config: Optional[Config] = None, state=None):
        self.config = config or Config()
        setup_logging(self.config.log)
        telemetry.configure(self.config.telemetry)
        # Instance-scoped registries (swarm fleets): every request this
        # node handles — and every task spawned underneath, contextvars
        # travel with ensure_future — reports into this node's private
        # metrics/events/traces instead of the process globals.  Default
        # (single-node) keeps the globals: scope stays None.
        self.telemetry_scope = None
        if self.config.telemetry.instance_scope:
            self.telemetry_scope = telemetry.TelemetryScope.from_config(
                self.config.telemetry)
        if state is not None:
            # injected backend (tests: the pg backend over the mock
            # driver; a live server would come through config instead)
            self.state = state
        elif self.config.node.db_backend == "postgres":
            # reference-ecosystem interop: run against an existing uPow
            # PostgreSQL database (schema.sql) via asyncpg
            from ..state.pg import PgChainState

            self.state = PgChainState(
                self.config.node.pg_dsn,
                # reference default sidecar filename (pickledb)
                emission_path="emission_details.json")
            self.state.ensure_schema()
            if self.config.device.utxo_index:
                self.state.enable_device_index()
        elif self.config.node.db_backend == "sqlite":
            self.state = ChainState(
                self.config.node.db_path or None,
                device_index=self.config.device.utxo_index)
        else:
            raise ValueError(
                f"node.db_backend must be 'sqlite' or 'postgres', not"
                f" {self.config.node.db_backend!r}")
        self.manager = BlockManager(
            self.state, sig_backend=self.config.device.sig_backend,
            verify_pad_block=self.config.device.verify_pad_block,
            verify_device_timeout=self.config.device.verify_device_timeout,
            verify_mesh_devices=self.config.device.mesh_devices,
            verify_microbatch=self.config.device.verify_microbatch,
            txid_backend=self.config.device.txid_backend,
            txid_min_batch=self.config.device.txid_min_batch)
        rcfg = self.config.resilience
        self.breakers = BreakerRegistry(
            failure_threshold=rcfg.breaker_failure_threshold,
            open_secs=rcfg.breaker_open_secs,
            half_open_max=rcfg.breaker_half_open_max)
        if rcfg.faults:
            faultinject.install(rcfg.faults, rcfg.faults_seed)
        self.resilience = ResilienceContext.from_config(
            rcfg, breakers=self.breakers)
        # device degradation knobs land on the process-wide manager the
        # verify dispatch consults (verify/txverify.py)
        from ..verify.txverify import DEGRADE

        DEGRADE.configure(rcfg.device_failure_limit, rcfg.device_cooldown)
        self.peers = PeerBook(self.config.node, breakers=self.breakers)
        self.ip_filter = IpFilter(self.config.node.ip_config_file)
        from .ratelimit import RateLimiter

        self.rate_limiter = RateLimiter(
            enabled=self.config.node.rate_limits_enabled)
        self.is_syncing = False
        self.started = False
        self.self_url = self.config.node.self_url
        # micro-batched mempool subsystem (docs/MEMPOOL.md): in-memory
        # fee-priority pool is the read authority, the SQL
        # pending_transactions table is demoted to write-behind journal
        mcfg = self.config.mempool
        self.pool = Mempool(max_bytes_hex=mcfg.max_pool_bytes_hex,
                            tx_ttl=mcfg.tx_ttl, allow_rbf=mcfg.allow_rbf)
        if mcfg.enabled:
            # block acceptance / mempool GC drop mined and doomed txs
            # from the pool directly — templates stop serving a mined
            # tx the moment its block commits, with the stamp-driven
            # sync() kept as the reconciliation backstop
            self.manager.on_pending_removed = self.pool.remove
        self.intake = IntakeCoordinator(self, _BANNED_ADDRESSES)
        self.mining_cache = MiningInfoCache()
        self.state.reinject_reorg_txs = bool(mcfg.enabled
                                             and mcfg.reinject_on_reorg)
        # generation-anchored hot-state read cache (state/hotcache.py,
        # docs/CACHING.md): read endpoints serve stored response BYTES
        # keyed by a generation the hooks below advance after every
        # committed write, so a hit never reflects a stale tip
        from ..state.hotcache import HotStateCache

        self.hotcache = HotStateCache(self.state, self.config.cache)
        if self.config.cache.enabled:
            self.manager.on_state_committed = self.hotcache.bump
            self.state.on_blocks_removed = \
                lambda _from_id: self.hotcache.bump("reorg")
            # chain the mempool hook: GC evictions and mined-tx removals
            # change the pending journal, which read responses (pending
            # tx lists, show_pending balances) depend on
            pool_remove = self.manager.on_pending_removed

            def _pending_removed(hashes, _base=pool_remove):
                if _base is not None:
                    _base(hashes)
                self.hotcache.bump("pending_removed")

            self.manager.on_pending_removed = _pending_removed
        # push_tx dedup: config-sized TTL set — the reference's 100-entry
        # deque cycles out in milliseconds at target intake rates,
        # reopening the duplicate-propagation window it exists to close
        self.tx_cache = (TTLSet(mcfg.tx_cache_size, mcfg.tx_cache_ttl)
                         if mcfg.enabled else deque(maxlen=100))
        self._last_mempool_clean: Optional[float] = None  # monotonic
        self._closing = False
        self._background: set = set()
        self._services: set = set()  # perpetual loops (watchtower)
        self._http_session = None  # shared gossip/RPC session, lazy
        self.ws_hub = None  # set by ws.attach(...) when enabled
        # Outbound RPC client seam: everything that talks to a peer
        # builds its client through this factory (signature-compatible
        # with NodeInterface).  The swarm harness swaps in a loopback
        # implementation that routes through the in-memory LinkMatrix,
        # so peer logic — breakers, retries, trace headers — runs
        # unmodified over a simulated network (upow_tpu/swarm/).
        self.iface_factory = NodeInterface
        # snapshot bootstrap progress (upow_tpu/snapshot/client.py
        # mutates it in place; /metrics exports it) + startup
        # housekeeping: bound on-disk generations and sweep staging
        # dirs a crashed builder left behind (never raises)
        self.snapshot_restore: dict = {}
        if self.config.snapshot.dir:
            from ..snapshot import layout as snapshot_layout

            snapshot_layout.prune_generations(self.config.snapshot.dir,
                                              keep=self.config.snapshot.keep)
        # cold-block archival tier (upow_tpu/archive/, docs/ARCHIVE.md):
        # attach the read-fallthrough seam to the storage backend.
        # Archived rows are immutable, so the hotcache generation is
        # untouched — cached responses are byte-identical either way.
        self.archive_compact: dict = {}
        if self.config.archive.dir:
            from ..archive import ArchiveReader

            self.state.archive = ArchiveReader(
                self.config.archive.dir,
                cache_segments=self.config.archive.reader_cache_segments)
        # background snapshot rebuild cadence (SnapshotConfig.
        # rebuild_interval_blocks): every committed block ticks a
        # counter; at interval + per-node jitter a rebuild (and the
        # archive compaction it arms) is spawned off the hook.  The
        # jitter is a deterministic hash of the node's identity so a
        # fleet started together doesn't rebuild in lockstep.
        self._snapshot_rebuild_inflight = False
        self._blocks_since_rebuild = 0
        scfg = self.config.snapshot
        if scfg.dir and scfg.rebuild_interval_blocks > 0:
            ident = (self.config.node.self_url
                     or f"{self.config.node.host}:{self.config.node.port}")
            jitter = max(0, scfg.rebuild_jitter_blocks)
            self._rebuild_target = scfg.rebuild_interval_blocks + (
                int.from_bytes(
                    hashlib.sha256(ident.encode()).digest()[:4], "big")
                % (jitter + 1))
            base_committed = self.manager.on_state_committed

            def _committed(_base=base_committed):
                if _base is not None:
                    _base()
                self._snapshot_rebuild_tick()

            self.manager.on_state_committed = _committed
        # Watchtower (docs/ALERTING.md): streaming anomaly detection +
        # SLO burn-rate alerting over this node's telemetry registries.
        # The engine holds direct registry references (scope or process
        # globals), so swarm nodes alert strictly independently; live
        # gauges the registries don't store come in through probes.
        self.watchtower = None
        if self.config.watchtower.enabled:
            from ..watchtower import WatchtowerEngine

            self.watchtower = WatchtowerEngine(
                self.config.watchtower, scope=self.telemetry_scope,
                name=(self.telemetry_scope.name
                      if self.telemetry_scope else "node"))
            self._register_watchtower_probes()
        self.app = self._build_app()

    def _register_watchtower_probes(self) -> None:
        wt = self.watchtower

        async def block_height() -> float:
            return float(await self.state.get_next_block_id() - 1)

        async def sync_lag() -> float:
            last = await self.state.get_last_block()
            return float(max(0, timestamp() - last["timestamp"])) \
                if last else 0.0

        wt.register_probe("block_height", block_height)
        wt.register_probe("sync_lag", sync_lag)
        if self.config.mempool.enabled:
            wt.register_probe("mempool_depth",
                              lambda: float(len(self.pool)))

        def ws_dropped() -> float:
            # ws_hub attaches later in _build_app; resolve per call
            hub = self.ws_hub
            return float(hub.get_stats()["dropped_messages"]) if hub else 0.0

        wt.register_probe("ws_dropped", ws_dropped)

    # ----------------------------------------------------------- plumbing --
    def _spawn(self, coro) -> None:
        """Fire-and-forget background task (FastAPI BackgroundTasks role).
        Refused once close() has begun — a request draining through the
        server during shutdown must not start work against a database
        that is about to be (or already is) closed."""
        if self._closing:
            coro.close()
            return
        task = asyncio.ensure_future(coro)
        self._background.add(task)
        task.add_done_callback(self._background.discard)

    def _spawn_service(self, coro) -> None:
        """Long-lived service loop (watchtower cadence).  Tracked apart
        from ``_background``: drain-style waiters (Swarm.settle) gather
        the background set and a loop that never returns would deadlock
        them.  Services only end via cancellation in close()."""
        if self._closing:
            coro.close()
            return
        task = asyncio.ensure_future(coro)
        self._services.add(task)
        task.add_done_callback(self._services.discard)

    async def close(self) -> None:
        self._closing = True
        # cancel AND await: a cancelled task only unwinds at its next
        # suspension point — closing the db before it does would hand a
        # still-running task a closed connection.  Bounded: a task stuck
        # inside run_in_executor (device verify) cannot be cancelled
        # until the executor call returns, and shutdown must not wait
        # out a 240 s device timeout.
        closing = list(self._background) + list(self._services)
        for task in closing:
            task.cancel()
        done, stragglers = set(), set()
        if closing:
            done, stragglers = await asyncio.wait(closing, timeout=5.0)
            for task in stragglers:
                log.warning("background task still running at close: %r",
                            task)
        if self._http_session is not None and not self._http_session.closed:
            await self._http_session.close()
        self.state.close()
        # A straggler that resumes after state.close() (e.g. a sync that
        # was blocked in the executor on a device verify) will hit
        # "Cannot operate on a closed database"; retrieve its exception
        # quietly instead of letting asyncio log it as never-retrieved.
        # `done` members may also have errored while unwinding their
        # cancellation (asyncio.wait never retrieves) — cover both.
        for task in done | stragglers:
            task.add_done_callback(
                lambda t: t.cancelled() or t.exception())

    def _session(self):
        """Shared aiohttp session for all outbound RPC (one connection
        pool per process, not one per gossip target per message)."""
        import aiohttp

        if self._http_session is None or self._http_session.closed:
            self._http_session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(
                    total=self.config.node.http_timeout))
        return self._http_session

    @staticmethod
    def _peer_ip(request: web.Request) -> str:
        peername = request.transport.get_extra_info("peername") if request.transport else None
        return peername[0] if peername else ""

    def _client_ip(self, request: web.Request) -> str:
        """Proxy headers are only trusted behind a proxy (config flag):
        the reference always honours X-Forwarded-For (main.py:375-390)
        because it assumes the NGINX.md deployment, which lets any direct
        client spoof its way past the IP filter."""
        if self.config.node.trust_proxy_headers:
            xff = request.headers.get("x-forwarded-for", "")
            if xff:
                # rightmost entry: the one OUR proxy appended.  Leftmost
                # is client-supplied under the standard append-style
                # proxy config and would let anyone spoof 127.0.0.1.
                return xff.split(",")[-1].strip()
            real = request.headers.get("x-real-ip")
            if real:
                return real
        return self._peer_ip(request)

    async def _params(self, request: web.Request) -> dict:
        """Merge query params with a JSON body (reference Body(False))."""
        params = dict(request.rel_url.query)
        if request.method == "POST" and request.can_read_body:
            try:
                body = await request.json()
                if isinstance(body, dict):
                    params.update(body)
            except (json.JSONDecodeError, UnicodeDecodeError):
                pass
        return params

    # ----------------------------------------------------------- gossip ---
    async def propagate(self, path: str, args: dict,
                        ignore_url: Optional[str] = None,
                        nodes: Optional[List[str]] = None) -> None:
        """Fan-out to the propagate set (main.py:79-94).

        Each peer send is bounded by ``propagate_deadline`` so one hung
        peer cannot stall gossip to everyone else; the fan-out itself
        stays fully concurrent and a timed-out send only marks THAT
        peer's breaker (via the RPC wrapper) and a counter."""
        self_base = _normalize(self.self_url)
        ignore_base = _normalize(ignore_url or "")
        deadline = self.config.resilience.propagate_deadline
        aws = []
        session = self._session()
        for node_url in nodes if nodes is not None else self.peers.propagate_nodes():
            iface = self.iface_factory(node_url, self.config.node,
                                       session=session,
                                       resilience=self.resilience)
            if iface.base_url in (self_base, ignore_base):
                continue
            aws.append(self._propagate_one(iface, path, args, self_base,
                                           deadline))
        await asyncio.gather(*aws)

    async def _propagate_one(self, iface: NodeInterface, path: str,
                             args: dict, self_base: str,
                             deadline: float) -> None:
        try:
            await asyncio.wait_for(iface.request(path, args, self_base),
                                   deadline or None)
        except asyncio.TimeoutError:
            trace.inc("resilience.propagate_timeouts")
            # the wrapper's breaker bookkeeping never ran (cancelled
            # mid-attempt) — a hang is the strongest failure signal
            self.breakers.record_failure(iface.base_url)
            log.debug("propagate to %s timed out after %.1fs",
                      iface.base_url, deadline)
        except Exception as e:
            log.debug("propagate error: %s", e)

    async def _propagate_old_transactions(self) -> None:
        txs = await self.state.get_need_propagate_transactions()
        for tx_hex in txs:
            tx_hash = hashlib.sha256(bytes.fromhex(tx_hex)).hexdigest()
            await self.state.update_pending_transaction_propagation(tx_hash)
            await self.propagate("push_tx", {"tx_hex": tx_hex})

    # -------------------------------------------------------- middleware --
    @web.middleware
    async def _middleware(self, request: web.Request, handler):
        # bind this node's telemetry scope around the WHOLE request —
        # including /metrics and /debug reads, so each node serves its
        # own registries even with 50 nodes in one process
        if self.telemetry_scope is not None:
            with self.telemetry_scope.activate():
                return await self._middleware_inner(request, handler)
        return await self._middleware_inner(request, handler)

    async def _middleware_inner(self, request: web.Request, handler):
        client_ip = self._client_ip(request)
        if not self.ip_filter.allowed(client_ip):
            return web.json_response(
                {"ok": False, "error": "Access forbidden."}, status=403)
        normalized = re.sub("/+", "/", request.path) or "/"
        if normalized != request.path:
            query = request.rel_url.query_string
            raise web.HTTPFound(normalized + ("?" + query if query else ""))
        if normalized != "/" and not self.ip_filter.allowed(
                client_ip, endpoint=normalized):
            return web.json_response(
                {"ok": False, "error": "Access forbidden temporarily."},
                status=403)
        if not self.rate_limiter.allow(client_ip, normalized):
            return web.json_response(
                {"ok": False, "error": "Rate limit exceeded"}, status=429)

        sender = request.headers.get("Sender-Node")
        if sender:
            self.peers.add(sender)

        host = request.host.split(":")[0] if request.host else ""
        # Hardening divergence: the reference gates this custodial endpoint
        # on the attacker-controlled Host header (main.py:315-322, safe
        # only behind the NGINX.md proxy).  Gate on the client IP — the
        # socket peer, or the proxy-reported address when
        # trust_proxy_headers says the proxy is trusted (otherwise a
        # proxied deployment would see every client as 127.0.0.1).
        if normalized == "/send_to_address" and not (
                client_ip and is_local_ip(client_ip)):
            return web.json_response(
                {"ok": False, "error": "Access forbidden. This endpoint can "
                 "only be accessed from localhost."}, status=403)

        # first-request bootstrap: learn peers-of-peers, discover self URL,
        # announce ourselves (main.py:324-361)
        if normalized != "/get_nodes" and not self.started and \
                not (is_local_ip(host) or host == "localhost"):
            self.started = True
            if not self.self_url:
                self.self_url = f"{request.scheme}://{request.host}"
            self._spawn(self._bootstrap())

        # request-scoped trace root: inbound gossip hops adopt the
        # peer's X-Upow-Trace ID so one push_tx/push_block is one trace
        # across nodes; scrape/debug/ws endpoints stay untraced (they
        # would drown the recency ring)
        traced = self.config.telemetry.trace_requests and not (
            normalized in ("/metrics", "/ws")
            or normalized.startswith("/debug"))
        # SLO latency capture: only registered routes (the fixed set
        # built in _build_app) get a series — deriving names from raw
        # paths would let a scanner consume the metric cardinality cap
        slo_t0 = time.perf_counter() if normalized in self._slo_paths \
            else None
        trace_id = None
        try:
            if traced:
                with telemetry.request_trace(
                        "http." + (normalized.strip("/") or "root"),
                        trace_id=request.headers.get(telemetry.TRACE_HEADER),
                        ) as troot:
                    trace_id = troot.trace_id
                    response = await handler(request)
            else:
                response = await handler(request)
        except web.HTTPException:
            raise
        except _BadParam as e:
            if slo_t0 is not None:
                telemetry.slo.observe_request(
                    normalized, time.perf_counter() - slo_t0, 422,
                    trace_id=trace_id)
            return web.json_response(
                {"ok": False, "error": f"Invalid integer parameter {e}"},
                status=422)
        except Exception as e:  # exception envelope (main.py:394-406)
            log.error("Error on %s, %s: %s", request.path, type(e).__name__,
                      e, exc_info=True)
            if slo_t0 is not None:
                telemetry.slo.observe_request(
                    normalized, time.perf_counter() - slo_t0, 500,
                    trace_id=trace_id)
            return web.json_response(
                {"ok": False, "error": f"Uncaught {type(e).__name__} exception"},
                status=500)
        if slo_t0 is not None:
            telemetry.slo.observe_request(
                normalized, time.perf_counter() - slo_t0, response.status,
                trace_id=trace_id)
        response.headers["Access-Control-Allow-Origin"] = "*"
        if trace_id is not None:
            response.headers[telemetry.TRACE_HEADER] = trace_id
        self._spawn(self._propagate_old_transactions())
        return response

    async def _bootstrap(self) -> None:
        try:
            seeds = self.peers.recent_nodes()
            if not seeds:
                return
            iface = self.iface_factory(seeds[0], self.config.node,
                                       session=self._session(),
                                       resilience=self.resilience)
            for url in await iface.get_nodes():
                self.peers.add(url)
            self.peers.remove(self.self_url)
            await self.propagate("add_node", {"url": self.self_url})
            # catch up immediately after (re)start instead of waiting for
            # a push_block to reveal the gap (the reference only syncs on
            # gap detection, main.py:551-579 — a restarted node there
            # serves a stale chain until the next block arrives)
            self._spawn(self.sync_blockchain())
        except Exception as e:
            log.debug("bootstrap failed: %s", e)

    # ------------------------------------------------------- tx intake ----
    def make_tx_verifier(self) -> TxVerifier:
        """One verifier wired with this node's device knobs (shared by
        the serial path and the batched intake)."""
        return TxVerifier(
            self.state,
            verify_pad_block=self.config.device.verify_pad_block,
            verify_device_timeout=self.config.device.verify_device_timeout,
            verify_mesh_devices=self.config.device.mesh_devices)

    async def accept_tx_effects(self, tx: Tx, tx_hash: str,
                                first_address: Optional[str],
                                sender: Optional[str]) -> None:
        """Post-acceptance side effects, shared by the serial path and
        the batched intake: peer bookkeeping, gossip fan-out, WS
        broadcast, dedup cache, log line."""
        if sender:
            self.peers.update_last_message(sender)
        # first-seen stamp for the fleet propagation tracker: one event
        # per node per accepted tx (duplicates are rejected upstream)
        telemetry.event("tx_seen", hash=tx_hash)
        self._spawn(self.propagate("push_tx", {"tx_hex": tx.hex()}))
        if self.ws_hub is not None:
            amount = sum(o.amount for o in tx.outputs)
            self._spawn(self.ws_hub.broadcast_new_transaction({
                "tx_hash": tx_hash,
                "from": first_address,
                "to": [o.address for o in tx.outputs],
                "amount": _fmt_amount(amount),
                "fees": _fmt_amount(await self.state.tx_fees(tx)),
            }))
        self.tx_cache.append(tx_hash)
        log.info("Transaction has been accepted: %s", tx_hash)

    async def _submit_tx(self, tx: Tx, sender: Optional[str]) -> dict:
        """Route one tx into admission: the coalescing intake when the
        mempool subsystem is on (this request joins the current
        micro-batch and shares its signature dispatch), else the serial
        reference path."""
        if self.config.mempool.enabled:
            result = await self.intake.submit(tx, sender)
        else:
            result = await self._verify_and_push_tx(tx, sender)
        if result.get("ok"):
            # the pending journal gained a row — cached pending-tx
            # lists and show_pending address views are now stale
            self.hotcache.bump("pending_added")
        return result

    async def _verify_and_push_tx(self, tx: Tx,
                                  sender: Optional[str]) -> dict:
        # a coinbase is only ever built by block acceptance — a pushed one
        # would pass every input-based check vacuously, poison the mempool
        # (no inputs -> GC never clears it) and break every mined block
        # (reference database.py:93-96 rejects it explicitly); unsigned
        # inputs would crash serialization below instead of rejecting
        if getattr(tx, "is_coinbase", False) or any(
                i.signature is None for i in tx.inputs):
            return {"ok": False, "error": "Transaction has not been added"}
        tx_hash = tx.hash()
        if tx_hash in self.tx_cache:
            return {"ok": False, "error": "Transaction just added"}
        first_address = None
        if tx.inputs:
            first_address = await self.state.resolve_output_address(
                tx.inputs[0].tx_hash, tx.inputs[0].index)
        if first_address in _BANNED_ADDRESSES:
            return {"ok": False, "error": "Access forbidden temporarily."}
        if await self.state.pending_transaction_exists(tx_hash):
            return {"ok": False, "error": "Transaction already present"}
        # full verification BEFORE the mempool (the reference's
        # add_pending_transaction(verify=True) → Transaction.verify_pending,
        # database.py:93-111): rules + signatures + pending double spend.
        # Without this, any parseable garbage enters the mempool and gets
        # handed to miners, whose blocks then fail acceptance.
        try:
            with telemetry.span("push_tx.verify"):
                ok = await self.make_tx_verifier().verify_pending(
                    tx, sig_backend=self.config.device.sig_backend)
        except Exception as e:
            log.info("tx verify error %s: %s", tx_hash, e)
            ok = False
        if not ok:
            return {"ok": False, "error": "Transaction has not been added"}
        try:
            with telemetry.span("push_tx.journal_write"):
                await self.state.add_pending_transaction(tx)
        except Exception as e:
            log.info("tx rejected %s: %s", tx_hash, e)
            return {"ok": False, "error": "Transaction has not been added"}
        with telemetry.span("push_tx.effects"):
            await self.accept_tx_effects(tx, tx_hash, first_address, sender)
        return {"ok": True, "result": "Transaction has been accepted",
                "tx_hash": tx_hash}

    # ------------------------------------------------------- mining info --
    async def _mining_info_result(self) -> dict:
        self.manager.invalidate_difficulty()
        difficulty, last_block = await self.manager.get_difficulty()
        # mempool-GC timer on the MONOTONIC clock: the consensus
        # timestamp() the reference keys this off tracks the wall
        # clock, so an NTP step either fires a clear per poll or
        # suppresses clears entirely
        now = time.monotonic()
        if (self._last_mempool_clean is None
                or now - self._last_mempool_clean
                > self.config.node.mempool_clean_interval):
            self._last_mempool_clean = now
            self._spawn(self.manager.clear_pending_transactions())
        last_json = _json_block(last_block)
        key = None
        if self.config.mempool.enabled:
            await self.pool.sync(self.state)
            key = (self.pool.generation, (last_json or {}).get("hash"),
                   float(difficulty))
            cached = self.mining_cache.get(key)
            if cached is not None:
                return cached
            # the pool slice IS the reference query (pool.py docstring);
            # no SQL on the miner polling hot path
            pending = sorted(self.pool.select_hex(MAX_BLOCK_SIZE_HEX))
        else:
            pending = sorted(await self.state.get_pending_transactions_limit(
                hex_only=True))
        result = {
            "difficulty": float(difficulty),
            "last_block": last_json,
            "pending_transactions": pending[:10],
            "pending_transactions_hashes": [
                hashlib.sha256(bytes.fromhex(t)).hexdigest() for t in pending],
            "merkle_root": merkle_root(
                [tx_from_hex(t, check_signatures=False) for t in pending[:10]]),
        }
        if key is not None:
            self.mining_cache.put(key, result)
        return result

    # ------------------------------------------------------- read cache ---
    async def _cached(self, request: web.Request, entry_class: str,
                      key: tuple, build, dumps=json.dumps) -> web.Response:
        """Serve a read endpoint through the hot-state cache.

        ``build()`` produces the JSON-clean payload; what gets cached is
        the ENCODED body (``dumps(payload).encode("utf-8")`` — exactly
        the bytes ``web.json_response`` would have sent), so a hit skips
        both SQL and encoding and is byte-identical to the uncached
        response by construction.  Disabled cache or a
        ``X-Upow-Cache-Bypass`` header fall through to the plain path
        without touching the store."""
        if not self.hotcache.enabled or \
                _CACHE_BYPASS_HEADER in request.headers:
            return web.json_response(await build(), dumps=dumps)

        async def produce() -> bytes:
            return dumps(await build()).encode("utf-8")

        body = await self.hotcache.get_bytes(entry_class, key, produce)
        return web.Response(body=body, content_type="application/json",
                            charset="utf-8")

    # --------------------------------------------------------- handlers ---
    async def h_root(self, request: web.Request) -> web.Response:
        """Health probe (reference main.py:266-275) + additive timing
        stats from the span registry (trace.py) — same shape the
        reference's required keys take, extra key ignored by peers."""
        fingerprint = await self.state.get_unspent_outputs_hash()
        return web.json_response({
            "ok": True, "version": VERSION,
            "unspent_outputs_hash": fingerprint,
            "timings": trace.stats(),
            "counters": trace.counters(),
        })

    async def h_metrics(self, request: web.Request) -> web.Response:
        """Prometheus text exposition — beyond-reference observability
        (SURVEY §5 notes the reference has "No Prometheus/StatsD").
        Gauges for chain/mempool/peer/WS state plus the span registry as
        per-section count/total/max series, resilience event counters
        (``upow_<name>_total``), per-state breaker counts, kernel
        occupancy/compile telemetry, and the device-verify health gauge.
        Rendering and name sanitization live in telemetry/exposition.py;
        the format is pinned by tests/test_telemetry.py's validator."""
        from ..compile_cache import entry_count
        from ..verify.txverify import sig_verdict_stats

        e = telemetry.exposition.Exposition()
        e.gauge("block_height", await self.state.get_next_block_id() - 1,
                "Height of the last accepted block")
        e.gauge("mempool_transactions",
                await self.state.get_pending_transactions_count(),
                "Transactions waiting in the mempool")
        last_block = await self.state.get_last_block()
        lag = max(0, timestamp() - last_block["timestamp"]) \
            if last_block else 0
        e.gauge("sync_lag_seconds", lag,
                "Seconds since the tip block's consensus timestamp")
        if self.config.mempool.enabled:
            e.gauge("mempool_pool_depth", len(self.pool),
                    "Transactions in the in-memory fee-priority pool")
            e.gauge("mempool_pool_bytes_hex", self.pool.total_bytes_hex,
                    "Total hex chars held by the in-memory pool")
            e.counter("mining_info_cache_hits", self.mining_cache.hits,
                      "Mining-info requests served from the"
                      " generation-keyed cache")
            e.counter("mining_info_cache_misses", self.mining_cache.misses,
                      "Mining-info requests that rebuilt the template")
        e.gauge("peers_known", len(self.peers.all_nodes()),
                "Peers in the peer book")
        e.gauge("peers_active", len(self.peers.recent_nodes()),
                "Peers messaged within the activity window")
        e.gauge("node_syncing", int(bool(self.is_syncing)),
                "1 while a chain sync is in progress")
        snapshot_gen = self._snapshot_gen()
        if snapshot_gen is not None:
            m = snapshot_gen[1]
            e.gauge("snapshot_published_height", m["anchor_height"],
                    "Anchor height of the published snapshot generation")
            e.gauge("snapshot_published_chunks", len(m["chunks"]),
                    "Chunks in the published snapshot generation")
            e.gauge("snapshot_published_bytes", m["payload_bytes"],
                    "Payload bytes of the published snapshot generation")
        if self.snapshot_restore:
            sr = self.snapshot_restore
            e.gauge("snapshot_restore_chunks_total",
                    sr.get("total", 0),
                    "Chunks the in-progress/last snapshot restore needs")
            e.gauge("snapshot_restore_chunks_verified",
                    sr.get("verified", 0),
                    "Chunks verified by the current restore pass")
            e.gauge("snapshot_restore_chunks_reused",
                    sr.get("reused", 0),
                    "Verified chunks reused from the journal (not"
                    " re-downloaded) by the current restore pass")
        # archive families are emitted unconditionally (zeros when the
        # tier is disabled) so make metrics-check can pin their names
        ast = self.state.archive.stats() if self.state.archive else {}
        e.gauge("archive_segments", ast.get("segments", 0),
                "Published cold-archive segments")
        e.gauge("archive_archived_blocks", ast.get("archived_blocks", 0),
                "Blocks held by the published archive manifest")
        e.gauge("archive_archived_txs", ast.get("archived_txs", 0),
                "Transactions held by the published archive manifest")
        e.counter("archive_hot_rows_pruned",
                  (self.archive_compact.get("pruned_blocks", 0)
                   + self.archive_compact.get("pruned_txs", 0))
                  if self.archive_compact.get("ok") else 0,
                  "Hot block+tx rows deleted by the last compaction")
        e.counter("archive_fallthrough_reads",
                  ast.get("fallthrough_reads", 0),
                  "Reads served from archive segments after a hot miss")
        sig = sig_verdict_stats()
        e.gauge("sig_cache_entries", sig["size"],
                "Entries in the signature-verdict cache")
        e.counter("sig_cache_hits", sig["hits"],
                  "Signature checks answered from the verdict cache")
        e.counter("sig_cache_misses", sig["misses"],
                  "Signature checks that required verification")
        if self.hotcache.enabled:
            cs = self.hotcache.stats()
            e.counter("hotcache_hits", cs["hits"],
                      "Read responses served from the hot-state cache")
            e.counter("hotcache_misses", cs["misses"],
                      "Read responses rebuilt from storage")
            e.counter("hotcache_evictions", cs["evictions"],
                      "Entries evicted by per-class LRU byte caps")
            e.counter("hotcache_singleflight_coalesced",
                      cs["singleflight_coalesced"],
                      "Concurrent identical misses that shared one"
                      " storage trip")
            e.counter("hotcache_generation_bumps", cs["bumps"],
                      "Local generation advances (block accept, reorg,"
                      " pending-journal change)")
            e.counter("hotcache_foreign_bumps", cs["foreign_bumps"],
                      "Generation advances forced by another worker's"
                      " write (journal-stamp revalidation)")
            e.gauge("hotcache_generation", cs["generation"],
                    "Current read-cache generation epoch")
            e.gauge("hotcache_generation_age_seconds",
                    cs["generation_age_seconds"],
                    "Seconds since the generation last advanced")
            e.gauge("hotcache_entries",
                    sum(c["entries"] for c in cs["classes"].values()),
                    "Entries across all hot-state cache classes")
            e.gauge("hotcache_bytes",
                    sum(c["bytes"] for c in cs["classes"].values()),
                    "Encoded response bytes held by the hot-state cache")
        if self.ws_hub is not None:
            ws = self.ws_hub.get_stats()
            e.gauge("ws_connections", ws["total_connections"],
                    "Open WebSocket push connections")
            e.gauge("ws_messages_out", ws["messages_out"],
                    "WebSocket messages delivered")
            e.counter("ws_connects_total", ws["connects_total"],
                      "WebSocket connections accepted since start")
            e.counter("ws_disconnects_total", ws["disconnects_total"],
                      "WebSocket connections dropped since start")
            e.counter("ws_dropped_messages", ws["dropped_messages"],
                      "Broadcast messages shed by per-subscriber bounded"
                      " send queues (drop-slowest policy)")
            e.gauge("ws_send_queue_hwm", ws["send_queue_hwm"],
                    "Deepest any subscriber send queue has ever been"
                    " (high-watermark, including reaped connections)")
        for state_name, count in sorted(self.breakers.state_counts().items()):
            e.gauge(f"breaker_{state_name}_peers", count,
                    f"Peers whose circuit breaker is {state_name}")
        e.gauge("device_verify_health",
                self.manager.device_health()["gauge"],
                "Device verify path: 0=ok 1=degraded(CPU) 2=poisoned")
        wal_pages = getattr(self.state, "wal_pages", None)
        if wal_pages is not None:
            e.gauge("state_wal_pages", wal_pages,
                    "Frames in sqlite's write-ahead log at the last"
                    " commit (the keeper folds them behind it)")
        index_stats = getattr(self.state, "index_stats", lambda: None)()
        if index_stats is not None:
            e.gauge("utxo_index_entries", index_stats["entries"],
                    "Live outpoints across the HBM-resident UTXO"
                    " index tables")
            e.gauge("utxo_index_resident_bytes",
                    index_stats["resident_bytes"],
                    "Device bytes held by the resident UTXO index")
            e.gauge("utxo_index_twin_fingerprints",
                    index_stats["twin_fingerprints"],
                    "Fingerprints that ever held two live outpoints"
                    " (forces shadow consult on hit)")
            e.counter("utxo_index_probes", index_stats["probes"],
                      "Resident-index membership probe dispatches")
            e.counter("utxo_index_shadow_consults",
                      index_stats["shadow_consults"],
                      "Probes answered by the host shadow map"
                      " (ambiguity; steady-state target is zero)")
        # mesh_engine (via crypto.sha256) imports jax — a host-path node
        # must not pay that on a scrape, so read stats only when the
        # mining subsystem already loaded the module itself
        mesh_mod = sys.modules.get("upow_tpu.mine.mesh_engine")
        mesh_stats = mesh_mod.engine_stats() if mesh_mod else None
        if mesh_stats is not None:
            e.gauge("mine_mesh_shards", mesh_stats["devices"],
                    "Devices in the resident mesh search program"
                    " (0 = engine built but not yet armed)")
            e.gauge("mine_mesh_batch_per_shard",
                    mesh_stats["batch_per_device"],
                    "Nonces per shard per round in the resident"
                    " search program")
            e.gauge("mine_mesh_armed", int(mesh_stats["armed"]),
                    "Resident mesh engine armed (compiled + warm)")
            e.counter("mine_mesh_rounds", mesh_stats["dispatches"],
                      "Mesh search rounds dispatched through the"
                      " device runtime")
        e.gauge("mine_mesh_configured_devices",
                self.config.device.mesh_devices,
                "config.device.mesh_devices (0 = all visible)")
        cache_entries = entry_count()
        if cache_entries >= 0:
            e.gauge("compile_cache_persistent_entries", cache_entries,
                    "Entries in the persistent jit compile cache")
        for label, mem in sorted(telemetry.device.device_memory().items()):
            for key, value in sorted(mem.items()):
                e.gauge(f"device_{label}_{key}", value,
                        "Best-effort device memory_stats() value")
        # alert families are emitted unconditionally (zeros when the
        # watchtower is off) so make metrics-check can pin their names
        wt = self.watchtower
        wrow = wt.metric_rows() if wt is not None else {}
        e.gauge("alert_firing", wrow.get("firing", 0),
                "Alerts currently firing (docs/ALERTING.md)")
        e.gauge("alert_pending", wrow.get("pending", 0),
                "Alert conditions inside their for-duration")
        e.gauge("alert_silenced", wrow.get("silenced", 0),
                "Active alerts suppressed by an operator silence")
        e.gauge("alert_exemplars_firing",
                wrow.get("firing_with_exemplars", 0),
                "Firing alerts carrying at least one exemplar trace id")
        e.gauge("alert_eval_lag_seconds",
                wrow.get("eval_lag_seconds", 0.0),
                "Wall seconds the last watchtower evaluation tick took")
        e.counter("alert_evaluations", wrow.get("evaluations", 0),
                  "Watchtower evaluation ticks since start")
        e.counter("alert_fired", wrow.get("fired_total", 0),
                  "pending->firing transitions since start")
        e.counter("alert_resolved", wrow.get("resolved_total", 0),
                  "firing->resolved transitions since start")
        if wt is not None:
            by_rule: dict = {}
            for a in wt.alerts.active():
                d = by_rule.setdefault(a.rule.name,
                                       {"firing": 0, "pending": 0})
                if a.state in d:
                    d[a.state] += 1
            for rname, rule in sorted(wt.rules.items()):
                d = by_rule.get(rname, {"firing": 0, "pending": 0})
                e.gauge(f"alert_rule_{rname}_{rule.severity}_firing",
                        d["firing"],
                        f"Firing alerts for rule {rname}")
                e.gauge(f"alert_rule_{rname}_{rule.severity}_pending",
                        d["pending"],
                        f"Pending alerts for rule {rname}")
        for name, value in sorted(trace.counters().items()):
            e.counter(name, value)
        for name, s in sorted(trace.stats().items()):
            e.span_stats(name, s)
        for name, h in sorted(trace.histograms().items()):
            e.histogram(name, h["bounds"], h["counts"],
                        h["count"], h["sum"],
                        exemplars=h.get("exemplars"))
        resp = web.Response(text=e.render())
        # full 0.0.4 content type (Prometheus requires the version
        # parameter; aiohttp's ctor only takes the bare mime type)
        resp.headers["Content-Type"] = telemetry.exposition.CONTENT_TYPE
        return resp

    # cap on debug ``limit`` params: far above any configurable ring
    # size, so a clamped value never truncates a legitimate request
    _DEBUG_LIMIT_CAP = 100_000

    @classmethod
    def _debug_limit(cls, params, default: int = 0):
        """Parse a debug endpoint's ``limit``: (value, None) or
        (None, 400 response).  Unlike ``_int_q`` (422 via middleware),
        debug endpoints answer bad input directly with a 400 — they are
        operator surface, not reference wire surface.  Negative values
        clamp to 0 (= everything) and oversized ones to the cap, so no
        raw user integer ever reaches a slice."""
        raw = params.get("limit")
        if raw is None or raw == "":
            return default, None
        try:
            value = int(raw)
        except ValueError:
            return None, web.json_response(
                {"ok": False, "error": "limit must be an integer"},
                status=400)
        return max(0, min(value, cls._DEBUG_LIMIT_CAP)), None

    @staticmethod
    def _page_param(q, name: str, default: int, cap: int):
        """Public pagination param, hardened the same way as
        ``_debug_limit``: (value, None) or (None, 400 response).
        Negative values clamp to 0 and oversized ones to ``cap`` — an
        unclamped limit on the uncached SQL path is an easy self-DoS —
        and non-integers answer a clean 400 instead of the generic
        ``_int_q`` 422, naming the offending parameter."""
        raw = q.get(name)
        if raw is None or raw == "":
            return default, None
        try:
            value = int(raw)
        except ValueError:
            return None, web.json_response(
                {"ok": False, "error": f"{name} must be an integer"},
                status=400)
        return max(0, min(value, cap)), None

    async def h_debug_traces(self, request: web.Request) -> web.Response:
        """Completed trace trees: recency ring + slowest top-N
        (telemetry/tracing.py TraceBuffer).  ``limit`` bounds both
        lists (0 = all)."""
        limit, err = self._debug_limit(request.rel_url.query)
        if err is not None:
            return err
        result = telemetry.traces()
        if limit:
            result = {"recent": result.get("recent", [])[-limit:],
                      "slowest": result.get("slowest", [])[:limit]}
        return web.json_response({"ok": True, "result": result})

    async def h_debug_events(self, request: web.Request) -> web.Response:
        """Structured event ring: reorgs, breaker trips, degrade
        transitions, fault injections, alerts — oldest first, each
        stamped with the trace ID active when it fired and a monotonic
        ``seq``.  ``since=<seq>`` turns the poll incremental: only
        records beyond the cursor return, plus ``next_seq`` (the next
        cursor) and ``missed`` (records that rotated out of the ring
        before this cursor saw them; also counted into the
        ``telemetry.events.rotated_unseen`` counter)."""
        params = request.rel_url.query
        limit, err = self._debug_limit(params)
        if err is not None:
            return err
        kind = params.get("kind")
        since_raw = params.get("since")
        if since_raw is not None and since_raw != "":
            try:
                since_v = int(since_raw)
            except ValueError:
                return web.json_response(
                    {"ok": False, "error": "since must be an integer"},
                    status=400)
            got = telemetry.events.since(since_v, limit=limit or None,
                                         kind=kind)
            return web.json_response({
                "ok": True, "result": got["events"],
                "next_seq": got["next_seq"], "missed": got["missed"]})
        return web.json_response({
            "ok": True,
            "result": telemetry.events.snapshot(limit=limit or None,
                                                kind=kind)})

    async def h_debug_alerts(self, request: web.Request) -> web.Response:
        """Watchtower surface (docs/ALERTING.md), read-only: the rule
        pack, active alert states with exemplar trace ids, the
        firing/resolved history ring, and burn-rate readings.
        ``{"enabled": false}`` when the watchtower is off
        (UPOW_WATCHTOWER_ENABLED=1 turns it on).  The operator knobs
        (silence/unsilence/ack) live on POST — a side-effecting GET
        could be triggered by any prefetcher or dashboard refresh."""
        wt = self.watchtower
        if wt is None:
            return web.json_response(
                {"ok": True, "result": {"enabled": False}})
        result = wt.snapshot()
        result["enabled"] = True
        return web.json_response({"ok": True, "result": result})

    async def h_debug_alerts_post(self,
                                  request: web.Request) -> web.Response:
        """Watchtower operator knobs: ``silence=<key>&seconds=<s>``,
        ``unsilence=<key>``, ``ack=<key>`` — as query parameters or a
        JSON body (body wins).  Answers the post-action snapshot plus
        an ``actions`` record of what was applied."""
        wt = self.watchtower
        if wt is None:
            return web.json_response(
                {"ok": True, "result": {"enabled": False}})
        q = dict(request.rel_url.query)
        if request.can_read_body:
            try:
                body = await request.json()
            except ValueError:
                return web.json_response(
                    {"ok": False, "error": "body must be JSON"},
                    status=400)
            if not isinstance(body, dict):
                return web.json_response(
                    {"ok": False, "error": "body must be a JSON object"},
                    status=400)
            q.update({k: v for k, v in body.items() if v is not None})
        actions = {}
        key = q.get("silence")
        if key:
            try:
                secs = float(q.get("seconds", 300))
            except (TypeError, ValueError):
                return web.json_response(
                    {"ok": False, "error": "seconds must be a number"},
                    status=400)
            wt.silence(str(key), secs)
            actions["silenced"] = key
        key = q.get("unsilence")
        if key:
            wt.alerts.unsilence(str(key))
            actions["unsilenced"] = key
        key = q.get("ack")
        if key:
            actions["acked"] = wt.ack(str(key))
        result = wt.snapshot()
        result["enabled"] = True
        if actions:
            result["actions"] = actions
        return web.json_response({"ok": True, "result": result})

    async def h_debug_cache(self, request: web.Request) -> web.Response:
        """Hot-state read cache introspection: per-class entry counts
        and byte usage, hit/miss/eviction/coalesce counters, and the
        current generation + its age — everything an operator needs to
        size the ``UPOW_CACHE_*`` caps or confirm invalidations fire."""
        return web.json_response(
            {"ok": True, "result": self.hotcache.stats()})

    async def h_debug_breakers(self, request: web.Request) -> web.Response:
        """Per-peer circuit state + EWMA health score, exactly what
        gossip/sync peer ranking reads (PeerBook.ranked /
        propagate_nodes) — so an operator (or a swarm assertion) can see
        WHY a peer was skipped or tried last."""
        return web.json_response({"ok": True, "result": {
            "peers": self.breakers.snapshot(),
            "state_counts": self.breakers.state_counts(),
        }})

    async def h_debug_profile(self, request: web.Request) -> web.Response:
        """Opt-in jax.profiler capture control (ProfilingConfig):
        ``?action=start|stop|status``.  Route exists only when both
        telemetry.debug_endpoints and profile.enabled say so.

        The profiling calls run in an executor: a cold
        ``jax.profiler.start_trace`` initializes the profiler plugin and
        can block for seconds, which would stall every other request on
        this loop (caught by the concurrency sanitizer)."""
        from .. import profiling

        pcfg = self.config.profile
        action = request.rel_url.query.get("action", "status")
        loop = asyncio.get_running_loop()
        if action == "start":
            result = await loop.run_in_executor(
                None, profiling.start, pcfg.trace_dir,
                pcfg.max_capture_seconds)
        elif action == "stop":
            result = await loop.run_in_executor(None, profiling.stop)
        elif action == "status":
            result = await loop.run_in_executor(None, profiling.status)
        else:
            return web.json_response(
                {"ok": False,
                 "error": "action must be start, stop or status"},
                status=400)
        return web.json_response({"ok": "error" not in result,
                                  "result": result})

    # ------------------------------------------------------- snapshots ---
    # Serving reads ONLY the published on-disk generation (manifest +
    # chunk files) — never the database: a restoring peer hammering
    # /snapshot/chunk must not contend with block accept.  Deliberately
    # NOT routed through _cached (tests pin this): the chunk bytes are
    # already static files, and a cache-bypass header must never be
    # needed to get authoritative snapshot bytes.

    def _snapshot_gen(self):
        """(gen dir, manifest) of the published generation, or None."""
        from ..snapshot import layout as snapshot_layout

        root = self.config.snapshot.dir
        if not root:
            return None
        gen = snapshot_layout.current_gen_dir(root)
        if gen is None:
            return None
        manifest = snapshot_layout.read_manifest(
            os.path.join(gen, snapshot_layout.MANIFEST_NAME))
        if manifest is None:
            return None
        return gen, manifest

    @staticmethod
    async def _snapshot_serve_fault(key: str):
        """Fire the ``snapshot.serve`` chaos site; a 503 keeps an
        injected serve fault inside ordinary peer-error handling."""
        injector = faultinject.get_injector()
        if injector is not None:
            try:
                await injector.fire("snapshot.serve", key)
            except faultinject.FaultInjected:
                return web.json_response(
                    {"ok": False,
                     "error": "snapshot temporarily unavailable"},
                    status=503)
        return None

    async def h_snapshot_manifest(self,
                                  request: web.Request) -> web.Response:
        fault = await self._snapshot_serve_fault("manifest")
        if fault is not None:
            return fault
        gen = self._snapshot_gen()
        if gen is None:
            return web.json_response(
                {"ok": False, "error": "no snapshot available"},
                status=404)
        trace.inc("snapshot.manifest_served")
        return web.json_response({"ok": True, "result": gen[1]})

    async def h_snapshot_chunk(self, request: web.Request) -> web.Response:
        from ..snapshot import layout as snapshot_layout

        try:
            i = int(request.match_info["i"])
        except (KeyError, ValueError):
            return web.json_response(
                {"ok": False, "error": "chunk index must be an integer"},
                status=422)
        fault = await self._snapshot_serve_fault(f"chunk/{i}")
        if fault is not None:
            return fault
        gen = self._snapshot_gen()
        if gen is None or not 0 <= i < len(gen[1]["chunks"]):
            return web.json_response(
                {"ok": False, "error": "no such chunk"}, status=404)
        try:
            # chunks are up to 16 MiB; a loop-thread read would stall
            # every other handler while the disk seeks
            chunk_file = os.path.join(gen[0], snapshot_layout.chunk_name(i))
            data = await asyncio.get_running_loop().run_in_executor(
                None, lambda: open(chunk_file, "rb").read())
        except OSError:
            return web.json_response(
                {"ok": False, "error": "no such chunk"}, status=404)
        injector = faultinject.get_injector()
        if injector is not None:  # corrupt-kind rules rewrite payloads
            data = injector.fire_mutate("snapshot.serve", f"chunk/{i}",
                                        data)
        trace.inc("snapshot.chunks_served")
        return web.json_response(
            {"ok": True, "result": {"i": i, "data": data.hex()}})

    async def build_snapshot(self):
        """Build + publish a generation under config.snapshot.dir
        (None when the subsystem is disabled or the chain is empty)."""
        scfg = self.config.snapshot
        if not scfg.dir:
            return None
        from ..snapshot.builder import build_snapshot as _build

        return await _build(self.state, scfg.dir,
                            chunk_bytes=scfg.chunk_bytes,
                            blocks_tail=scfg.blocks_tail, keep=scfg.keep)

    async def bootstrap_from_snapshot(self, sources=None) -> dict:
        """Onboard this node from a peer snapshot, falling back to full
        block replay (sync_blockchain) with a structured reason when
        snapshot restore cannot complete.  ``sources`` overrides peer
        selection; by default peers are ordered by the same breaker/
        health rank sync_blockchain uses."""
        from ..snapshot.client import (SnapshotError,
                                       bootstrap_from_snapshot)

        scfg = self.config.snapshot
        if sources is None:
            sources = self.peers.ranked(self.peers.recent_nodes())
        reason = detail = ""
        if not scfg.dir:
            reason = "snapshot_disabled"
        elif not sources:
            reason = "no_sources"
        else:
            ifaces = [self.iface_factory(url, self.config.node,
                                         session=self._session(),
                                         resilience=self.resilience)
                      for url in sources]
            try:
                result = await bootstrap_from_snapshot(
                    self.state, ifaces, scfg.dir,
                    chunk_retries=scfg.chunk_retries,
                    progress=self.snapshot_restore,
                    max_chunks=scfg.max_chunks,
                    max_chunk_bytes=scfg.max_chunk_bytes,
                    max_payload_bytes=scfg.max_payload_bytes)
                # restored state invalidates everything derived from it
                self.hotcache.bump("snapshot_restore")
                self.manager.invalidate_difficulty()
                return {"ok": True, **result}
            except SnapshotError as e:
                reason, detail = e.reason, e.detail
                if reason == "restored_state_mismatch":
                    # the client wiped the committed-but-unproven
                    # restore back to a blank chain — derived caches
                    # must not outlive it
                    self.hotcache.bump("snapshot_restore")
                    self.manager.invalidate_difficulty()
            finally:
                for iface in ifaces:
                    await iface.close()
        trace.inc("snapshot.fallbacks")
        telemetry.event("snapshot_fallback", reason=reason,
                        detail=detail or None)
        log.warning("snapshot bootstrap failed (%s); falling back to"
                    " full replay", reason)
        sync = await self.sync_blockchain()
        return {"ok": bool(sync.get("ok")), "method": "replay_fallback",
                "reason": reason, "sync": sync}

    # --------------------------------------------------------- archive ---
    # Disk-only serving, mirroring /snapshot/*: authoritative bytes
    # come straight from the published manifest + segment files (NOT
    # routed through _cached — peers verifying content hashes need the
    # store's truth, and tests pin the no-cache-bypass property).

    async def _archive_manifest(self) -> Optional[dict]:
        reader = self.state.archive
        if not self.config.archive.dir or reader is None:
            return None
        return await asyncio.get_running_loop().run_in_executor(
            None, reader.store.current_manifest)

    async def h_archive_manifest(self,
                                 request: web.Request) -> web.Response:
        manifest = await self._archive_manifest()
        if manifest is None:
            return web.json_response(
                {"ok": False, "error": "no archive available"},
                status=404)
        trace.inc("archive.manifest_served")
        return web.json_response({"ok": True, "result": manifest})

    async def h_archive_segment(self, request: web.Request) -> web.Response:
        try:
            i = int(request.match_info["i"])
        except (KeyError, ValueError):
            return web.json_response(
                {"ok": False, "error": "segment index must be an integer"},
                status=422)
        manifest = await self._archive_manifest()
        if manifest is None or not 0 <= i < len(manifest["segments"]):
            return web.json_response(
                {"ok": False, "error": "no such segment"}, status=404)
        record = manifest["segments"][i]
        try:
            # segments can be tens of MB; a loop-thread read would
            # stall every other handler while the disk seeks
            data = await asyncio.get_running_loop().run_in_executor(
                None, self.state.archive.store.read_payload,
                record["name"])
        except OSError:
            return web.json_response(
                {"ok": False, "error": "no such segment"}, status=404)
        trace.inc("archive.segments_served")
        return web.json_response(
            {"ok": True, "result": {"i": i, "name": record["name"],
                                    "data": data.hex()}})

    async def h_debug_archive(self, request: web.Request) -> web.Response:
        reader = self.state.archive
        if reader is None:
            return web.json_response(
                {"ok": False, "error": "archive disabled"}, status=404)
        await reader.coverage()  # stats() reads the cached manifest
        return web.json_response({"ok": True, "result": {
            "reader": reader.stats(),
            "last_compaction": self.archive_compact,
            "hot_rows": await self.state.archive_hot_row_counts(),
        }}, dumps=_json_dumps)

    async def compact_archive(self) -> dict:
        """One compaction cycle against the newest published snapshot
        generation (archive/compactor.py; crash-safe, idempotent)."""
        acfg = self.config.archive
        if not acfg.dir or not self.config.snapshot.dir:
            return {"ok": False, "reason": "archive_disabled"}
        from ..archive import compactor

        stats = await compactor.compact(self.state, acfg.dir,
                                        self.config.snapshot.dir, acfg,
                                        reader=self.state.archive)
        self.archive_compact = stats
        return stats

    async def fetch_archive_from_peer(self, source: str) -> dict:
        """Mirror a peer's archive (deep-history sync/replay feed)."""
        acfg = self.config.archive
        if not acfg.dir:
            return {"ok": False, "reason": "archive_disabled"}
        from ..archive.reader import ArchiveFetchError, fetch_archive

        iface = self.iface_factory(source, self.config.node,
                                   session=self._session(),
                                   resilience=self.resilience)
        try:
            result = await fetch_archive(
                iface, acfg.dir,
                max_segment_bytes=acfg.max_segment_bytes,
                max_segments=acfg.max_segments)
        except (ArchiveFetchError, ConnectionError, asyncio.TimeoutError,
                OSError) as e:
            return {"ok": False, "reason": str(e)}
        finally:
            await iface.close()
        if self.state.archive is not None:
            self.state.archive.invalidate()
        return result

    def _snapshot_rebuild_tick(self) -> None:
        """Committed-block hook: arm a background snapshot rebuild (and
        the archive compaction it enables) every rebuild_interval_blocks
        + jitter blocks."""
        self._blocks_since_rebuild += 1
        if (self._blocks_since_rebuild >= self._rebuild_target
                and not self._snapshot_rebuild_inflight):
            self._blocks_since_rebuild = 0
            self._snapshot_rebuild_inflight = True
            self._spawn(self._snapshot_rebuild())

    async def _snapshot_rebuild(self) -> None:
        try:
            manifest = await self.build_snapshot()
            if manifest is not None:
                trace.inc("snapshot.auto_rebuilds")
            if self.config.archive.dir:
                await self.compact_archive()
        except asyncio.CancelledError:
            raise
        except Exception as e:
            log.warning("background snapshot rebuild failed: %s", e)
        finally:
            self._snapshot_rebuild_inflight = False

    async def h_push_tx(self, request: web.Request) -> web.Response:
        if self.is_syncing:
            return web.json_response(
                {"ok": False, "error": "Node is already syncing"})
        params = await self._params(request)
        tx_hex = params.get("tx_hex")
        if not tx_hex:
            return web.json_response(
                {"ok": False, "error": "Missing tx_hex"}, status=422)
        try:
            tx = await self._parse_tx(tx_hex)
        except Exception as e:
            log.debug("push_tx: rejecting unparseable tx: %s", e)
            return web.json_response(
                {"ok": False, "error": f"Invalid transaction: {e}"})
        result = await self._submit_tx(
            tx, request.headers.get("Sender-Node"))
        return web.json_response(result)

    async def _parse_tx(self, tx_hex: str, overlay: Optional[dict] = None):
        """Decode with the ambiguous-signature relink resolved against state
        (core/tx.py tx_from_hex needs a sync resolver).  The resolver is
        only consulted when the signature count matches neither 1 nor the
        input count, so the common case is ONE parse; only the ambiguous
        layout pays the signature-free pre-parse that gathers input
        addresses.  ``overlay`` maps tx_hash -> parsed Tx for sources not
        yet in state (earlier blocks of the same sync page)."""
        try:
            return tx_from_hex(tx_hex, check_signatures=True)
        except AmbiguousSignatureError:
            pass
        tx = tx_from_hex(tx_hex, check_signatures=False)
        addrs = {}
        for i in tx.inputs:
            src = overlay.get(i.tx_hash) if overlay else None
            if src is not None and 0 <= i.index < len(src.outputs):
                addrs[(i.tx_hash, i.index)] = src.outputs[i.index].address
            else:
                addrs[(i.tx_hash, i.index)] = (
                    await self.state.resolve_output_address(i.tx_hash, i.index))
        return tx_from_hex(
            tx_hex, check_signatures=True,
            resolve_address=lambda h, idx: addrs.get((h, idx)))

    async def h_push_block(self, request: web.Request) -> web.Response:
        if self.is_syncing:
            return web.json_response(
                {"ok": False, "error": "Node is already syncing"})
        params = await self._params(request)
        if "id" in params:
            return web.json_response({"ok": False, "error": "Deprecated"})
        block_content = params.get("block_content", "")
        txs = params.get("txs", "")
        block_no = params.get("block_no")
        sender = request.headers.get("Sender-Node")
        if isinstance(txs, str):
            txs = txs.split(",")
            if txs == [""]:
                txs = []
        try:
            previous_hash = split_block_content(block_content)[0]
        except Exception as e:
            log.debug("push_block: malformed block content from %s: %s",
                      sender, e)
            return web.json_response(
                {"ok": False, "error": f"malformed block content: {e}"})
        next_block_id = await self.state.get_next_block_id()
        if block_no is None:
            previous_block = await self.state.get_block(previous_hash)
            if previous_block is None:
                if sender:
                    self._spawn(self.sync_blockchain(sender))
                    return web.json_response({
                        "ok": False,
                        "error": "Previous hash not found, had to sync "
                                 "according to sender node, block may have "
                                 "been accepted"})
                return web.json_response(
                    {"ok": False, "error": "Previous hash not found"})
            block_no = previous_block["id"] + 1
        else:
            try:
                block_no = int(block_no)
                if not (0 <= block_no <= 2 ** 63 - 1):
                    raise ValueError
            except (ValueError, TypeError):
                # a miner sending garbage must get a clean rejection,
                # not a 500 (same class as the _int_q GET hardening)
                return web.json_response(
                    {"ok": False, "error": "Invalid block_no"}, status=422)
        if next_block_id < block_no:
            self._spawn(self.sync_blockchain(sender))
            return web.json_response({
                "ok": False,
                "error": "Blocks missing, had to sync according to sender "
                         "node, block may have been accepted"})
        if next_block_id > block_no:
            return web.json_response({"ok": False, "error": "Too old block"})

        final_transactions: List[Tx] = []
        hashes: List[str] = []
        for tx_hex in txs:
            if len(tx_hex) == 64:
                hashes.append(tx_hex)
            else:
                final_transactions.append(await self._parse_tx(tx_hex))
        if hashes:
            found = await self.state.get_pending_transactions_by_hash(hashes)
            if len(found) < len(hashes):
                if sender:
                    self._spawn(self.sync_blockchain(sender))
                    return web.json_response({
                        "ok": False,
                        "error": "Transaction hash not found, had to sync "
                                 "according to sender node, block may have "
                                 "been accepted"})
                return web.json_response(
                    {"ok": False, "error": "Transaction hash not found"})
            for h in found:
                final_transactions.append(await self._parse_tx(h))

        errors: list = []
        if not await self.manager.create_block(
                block_content, final_transactions, errors=errors):
            return web.json_response(
                {"ok": False, "error": errors[0]} if errors else {"ok": False})

        if self.ws_hub is not None:
            block_hash = hashlib.sha256(bytes.fromhex(block_content)).hexdigest()
            info = await self._mining_info_result()
            self._spawn(self.ws_hub.broadcast_new_block({
                "block_no": block_no,
                "block_hash": block_hash,
                "transactions_count": len(final_transactions),
                "timestamp": timestamp(),
                **info,
            }))
        if sender:
            self.peers.update_last_message(sender)
        self._spawn(self.propagate("push_block", {
            "block_content": block_content,
            "txs": ([tx.hex() for tx in final_transactions]
                    if len(final_transactions) < 10 else txs),
            "block_no": block_no,
        }))
        return web.json_response({"ok": True})

    async def h_sync_blockchain(self, request: web.Request) -> web.Response:
        if self.is_syncing:
            return web.json_response(
                {"ok": False, "error": "Node is already syncing"})
        node_url = request.rel_url.query.get("node_url")
        resp = await self.sync_blockchain(node_url)
        body = {"ok": resp["ok"]}
        if not resp["ok"]:
            body["error"] = resp["error"]
        if resp["peer"]:
            body["peer"] = resp["peer"]  # additive: which source was used
        return web.json_response(body)

    async def h_get_mining_info(self, request: web.Request) -> web.Response:
        return web.json_response(
            {"ok": True, "result": await self._mining_info_result()})

    async def h_get_validators_info(self, request: web.Request) -> web.Response:
        """Inode ballot grouped by voting validator (main.py:698-725)."""
        q = request.rel_url.query
        inode = q.get("inode")
        offset = _int_q(q, "offset", 0)
        limit = _int_q(q, "limit", 100, cap=1000)

        async def build():
            rows = await self.state.get_ballots(
                "inodes_ballot", inode, offset=offset, limit=limit)
            by_validator: dict = {}
            stakes: dict = {}  # one stake computation per validator
            for row in rows:
                ent = by_validator.setdefault(row["voter"], {
                    "validator": row["voter"], "vote": []})
                ent["vote"].append({
                    "wallet": row["recipient"],
                    "vote_count": str(row["vote"]),
                    "tx_hash": row["tx_hash"],
                    "index": row["index"],
                })
                if row["voter"] not in stakes:
                    stakes[row["voter"]] = str(
                        await self.state.get_validators_stake(
                            row["voter"], check_pending_txs=True))
                ent["totalStake"] = stakes[row["voter"]]
            return list(by_validator.values())

        return await self._cached(request, "governance",
                                  ("validators", inode, offset, limit),
                                  build)

    async def h_get_delegates_info(self, request: web.Request) -> web.Response:
        """Validator ballot grouped by voting delegate, batch stake
        (main.py:727-764)."""
        q = request.rel_url.query
        validator = q.get("validator")
        offset = _int_q(q, "offset", 0)
        limit = _int_q(q, "limit", 100, cap=1000)

        async def build():
            rows = await self.state.get_ballots(
                "validators_ballot", validator, offset=offset, limit=limit)
            stakes = await self.state.get_multiple_address_stakes(
                {row["voter"] for row in rows if row["voter"]},
                check_pending_txs=True)
            by_delegate: dict = {}
            for row in rows:
                ent = by_delegate.setdefault(row["voter"], {
                    "delegate": row["voter"], "vote": [],
                    "totalStake": "0"})
                ent["vote"].append({
                    "wallet": row["recipient"],
                    "vote_count": str(row["vote"]),
                    "tx_hash": row["tx_hash"],
                    "index": row["index"],
                })
                ent["totalStake"] = str(stakes.get(row["voter"],
                                                   Decimal(0)))
            return list(by_delegate.values())

        return await self._cached(request, "governance",
                                  ("delegates", validator, offset, limit),
                                  build)

    async def h_get_address_info(self, request: web.Request) -> web.Response:
        q = request.rel_url.query
        address = q.get("address")
        if not address:
            return web.json_response(
                {"ok": False, "error": "Missing address"}, status=422)

        def flag(name):
            return q.get(name, "false").lower() in ("1", "true", "yes")

        async def build():
            outputs = await self.state.get_spendable_outputs(address)
            stake = await self.state.get_address_stake(address)
            balance = sum(o.amount for o in outputs)

            def out_list(rows):
                return [{"amount": _fmt_amount(r["amount"]),
                         "tx_hash": r["tx_hash"], "index": r["index"]}
                        for r in rows]

            result = {
                "balance": _fmt_amount(balance),
                "stake": str(stake),
                "spendable_outputs": [
                    {"amount": _fmt_amount(o.amount), "tx_hash": o.tx_hash,
                     "index": o.index} for o in outputs],
                "pending_transactions": None,
                "pending_spent_outputs": None,
                "stake_outputs": None,
                "delegate_spent_votes": None,
                "delegate_unspent_votes": None,
                "inode_registration_outputs": None,
                "validator_unspent_votes": None,
                "validator_spent_votes": None,
                "is_inode": None,
                "is_inode_active": None,
                "is_validator": None,
            }

            def vote_list(rows):
                return [{"amount": str(r["vote"]), "tx_hash": r["tx_hash"],
                         "index": r["index"]} for r in rows]

            if flag("show_pending"):
                pending = await self.state.get_address_pending_transactions(address)
                result["pending_transactions"] = [
                    await self.state.get_nice_transaction(
                        tx.hash(), address if flag("verify") else None)
                    for tx in pending
                ]
                result["pending_spent_outputs"] = [
                    {"tx_hash": h, "index": i}
                    for h, i in await self.state.get_address_pending_spent_outpoints(address)
                ]
            if flag("stake_outputs"):
                result["stake_outputs"] = out_list(
                    await self.state.get_outputs_by_address(
                        "unspent_outputs", address, is_stake=True))
            if flag("delegate_spent_votes"):
                result["delegate_spent_votes"] = vote_list(
                    await self.state.get_delegates_spent_votes(address))
            if flag("delegate_unspent_votes"):
                result["delegate_unspent_votes"] = out_list(
                    await self.state.get_outputs_by_address(
                        "delegates_voting_power", address))
            if flag("inode_registration_outputs"):
                result["inode_registration_outputs"] = out_list(
                    await self.state.get_outputs_by_address(
                        "inode_registration_output", address))
            if flag("validator_unspent_votes"):
                result["validator_unspent_votes"] = out_list(
                    await self.state.get_outputs_by_address(
                        "validators_voting_power", address))
            if flag("validator_spent_votes"):
                result["validator_spent_votes"] = vote_list(
                    await self.state.get_validators_spent_votes(address))
            if flag("address_state"):
                is_inode = await self.state.is_inode_registered(address)
                result["is_inode"] = is_inode
                if is_inode:
                    active = await self.manager.get_active_inodes_cached()
                    result["is_inode_active"] = any(
                        e.get("wallet") == address for e in active)
                else:
                    result["is_inode_active"] = False
                result["is_validator"] = await self.state.is_validator_registered(address)
            return {"ok": True, "result": result}

        key = (address,) + tuple(flag(n) for n in _ADDRESS_INFO_FLAGS)
        return await self._cached(request, "address", key, build)

    async def h_get_address_transactions(self, request: web.Request) -> web.Response:
        q = request.rel_url.query
        address = q.get("address")
        page, err = self._page_param(q, "page", 1, 2 ** 63 - 1)
        if err is None:
            limit, err = self._page_param(q, "limit", 5, 1000)
        if err is not None:
            return err
        page = max(page, 1)
        # the PRODUCT can overflow int64 even with both factors clamped
        offset = min((page - 1) * limit, 2 ** 63 - 1)

        async def build():
            rows = await self.state.get_address_transactions(
                address, limit=limit, offset=offset)
            return {"ok": True, "result": {
                "transactions": [
                    await self.state.get_nice_transaction(r["tx_hash"])
                    for r in rows]
            }}

        return await self._cached(request, "history",
                                  (address, limit, offset), build)

    async def h_add_node(self, request: web.Request) -> web.Response:
        url = request.rel_url.query.get("url", "").strip("/")
        if not url:
            return web.json_response(
                {"ok": False, "error": "Missing url"}, status=422)
        if _normalize(url) == _normalize(self.self_url):
            return web.json_response(
                {"ok": False, "error": "Recursively adding node"})
        if self.peers.contains(url):
            return web.json_response(
                {"ok": False, "error": "Node already present"})
        # no resilience ctx: the probe of a candidate peer should stay a
        # quick single attempt and not seed a breaker entry for a URL we
        # may never admit to the book
        iface = self.iface_factory(url, self.config.node,
                                   session=self._session())
        try:
            await iface.get("")
        except Exception as e:
            log.debug("add_node: probe of %s failed: %s", url, e)
            return web.json_response(
                {"ok": False, "error": "Could not add node"})
        self._spawn(self.propagate("add_node", {"url": url}, ignore_url=url))
        self.peers.add(url)
        return web.json_response({"ok": True, "result": "Node added"})

    async def h_get_nodes(self, request: web.Request) -> web.Response:
        return web.json_response(
            {"ok": True, "result": self.peers.recent_nodes()[:100]})

    async def h_get_pending_transactions(self, request: web.Request) -> web.Response:
        async def build():
            txs = await self.state.get_pending_transactions_limit(
                hex_only=True)
            return {"ok": True, "result": txs}

        return await self._cached(request, "pending", (), build)

    async def h_get_transaction(self, request: web.Request) -> web.Response:
        tx_hash = request.rel_url.query.get("tx_hash", "")

        async def build():
            tx = await self.state.get_nice_transaction(tx_hash)
            if tx is None:
                return {"ok": False, "error": "Transaction not found"}
            return {"ok": True, "result": tx}

        return await self._cached(request, "tx", (tx_hash,), build)

    async def _block_lookup(self, block: str) -> Optional[dict]:
        if block.isdecimal():
            # length gate first: int() itself raises past ~4300 digits
            # (python 3.12 conversion limit); int64 max has 19 digits
            if len(block) > 19 or int(block) > 2 ** 63 - 1:
                return None  # beyond any storable id (the sqlite
                # INTEGER binding would otherwise overflow into a 500)
            return await self.state.get_block_by_id(int(block))
        return await self.state.get_block(block)

    async def h_get_block(self, request: web.Request) -> web.Response:
        q = request.rel_url.query
        block = q.get("block", "")
        full = q.get("full_transactions", "false").lower() in ("1", "true")

        async def build():
            info = await self._block_lookup(block)
            if not info:
                return {"ok": False, "error": "Block not found"}
            block_hash = info["hash"]
            return {"ok": True, "result": {
                "block": _json_block(info),
                "transactions": (
                    await self.state.get_block_transactions(block_hash,
                                                            hex_only=True)
                    if not full else None),
                "full_transactions": (
                    await self.state.get_block_nice_transactions(block_hash)
                    if full else None),
            }}

        return await self._cached(request, "block", ("block", block, full),
                                  build)

    async def h_get_block_details(self, request: web.Request) -> web.Response:
        block = request.rel_url.query.get("block", "")

        async def build():
            info = await self._block_lookup(block)
            if not info:
                return {"ok": False, "error": "Block not found"}
            # the views helper drops reorg-raced Nones (never embed null)
            txs = await self.state.get_block_nice_transactions(info["hash"])
            return {"ok": True, "result": {
                "block": _json_block(info),
                "transactions": txs,
            }}

        return await self._cached(request, "block", ("details", block),
                                  build)

    async def h_get_blocks(self, request: web.Request) -> web.Response:
        q = request.rel_url.query
        offset, err = self._page_param(q, "offset", 0, 2 ** 63 - 1)
        if err is None:
            limit, err = self._page_param(q, "limit", 100, 1000)
        if err is not None:
            return err

        async def build():
            blocks = await self.state.get_blocks(offset, limit,
                                                 size_capped=True)
            return {"ok": True, "result": blocks}

        return await self._cached(request, "blocks",
                                  ("blocks", offset, limit), build)

    async def h_get_blocks_details(self, request: web.Request) -> web.Response:
        q = request.rel_url.query
        offset, err = self._page_param(q, "offset", 0, 2 ** 63 - 1)
        if err is None:
            limit, err = self._page_param(q, "limit", 100, 1000)
        if err is not None:
            return err

        async def build():
            blocks = await self.state.get_blocks(offset, limit,
                                                 tx_details=True,
                                                 size_capped=True)
            return {"ok": True, "result": blocks}

        return await self._cached(request, "blocks",
                                  ("details", offset, limit), build)

    async def h_dobby_info(self, request: web.Request) -> web.Response:
        inodes = await self.manager.get_active_inodes_cached()
        data = [
            {**item, "emission": f"{item['emission']:.2f}%"
             if isinstance(item["emission"], Decimal)
             else str(item["emission"]) + "%"}
            for item in inodes
        ]
        return web.json_response({"ok": True, "result": data},
                                 dumps=_json_dumps)

    async def h_get_supply_info(self, request: web.Request) -> web.Response:
        async def build():
            last_block = await self.state.get_last_block()
            last_id = last_block["id"] if last_block else 0
            return {"ok": True, "result": {
                "max_supply": float(MAX_SUPPLY),
                "circulating_supply": float(get_circulating_supply(last_id)),
                "last_block": _json_block(last_block),
            }}

        return await self._cached(request, "supply", (), build)

    async def h_send_to_address(self, request: web.Request) -> web.Response:
        """Localhost-only custodial send (main.py:481-518): looks up the
        wallet keystore by the Authorization pubkey, builds + pushes."""
        params = await self._params(request)
        to_address = params.get("to_address")
        amount = params.get("amount")
        if not to_address or not amount:
            return web.json_response(
                {"ok": False, "error": "Missing required params."}, status=422)
        auth = request.headers.get("Authorization")
        from ..wallet.keystore import KeyStore

        store = KeyStore()
        private_key = store.private_key_for_public(auth)
        if private_key is None:
            return web.json_response({"ok": False, "error": "Unauthorized"})
        from ..wallet.builders import WalletBuilder

        builder = WalletBuilder(self.state)
        try:
            tx = await builder.create_transaction(
                private_key, to_address, Decimal(str(amount)))
        except Exception as e:
            log.debug("send_to_address: tx build failed: %s", e)
            return web.json_response({"ok": False, "error": str(e)})
        result = await self._submit_tx(
            tx, request.headers.get("Sender-Node"))
        return web.json_response(result)

    # ------------------------------------------------------------ sync ----
    @staticmethod
    def _sync_result(outcome, peer: Optional[str]) -> dict:
        """Normalize _sync_blockchain's True|str|Exception outcome into
        the structured {ok, error, peer} shape — callers (and the HTTP
        handler) never see a raw exception object."""
        if outcome is True:
            return {"ok": True, "error": None, "peer": peer}
        if isinstance(outcome, BaseException):
            error = f"{type(outcome).__name__}: {outcome}"
        else:
            error = str(outcome)
        return {"ok": False, "error": error, "peer": peer}

    async def sync_blockchain(self, node_url: Optional[str] = None) -> dict:
        """Guarded wrapper (main.py:230-243) returning a structured
        ``{ok, error, peer}`` dict.  When no peer is named, up to 3
        distinct peers are tried before giving up — the reference picks
        ONE random peer per call (main.py:158-166), so a single dead
        seed (or its own unreachable CORE_URL default) makes that sync
        attempt a no-op even with healthy peers in the book.  The
        sampled candidates are then ordered by breaker health so a peer
        that has been failing all day is the LAST one tried, not an
        equal-odds first pick."""
        if self.is_syncing:
            return self._sync_result("Node is already syncing", None)
        self.is_syncing = True
        self.manager.is_syncing = True
        try:
            if node_url:
                return self._sync_result(
                    await self._sync_blockchain(node_url), node_url)
            nodes = self.peers.recent_nodes()
            if not nodes:
                return self._sync_result("No nodes found.", None)
            result = self._sync_result("no peers tried", None)
            candidates = random.sample(nodes, min(3, len(nodes)))
            for url in self.peers.ranked(candidates):
                try:
                    outcome = await self._sync_blockchain(url)
                except Exception as e:
                    # a dead peer raises from the fork-detection fetches
                    # before the paged loop's own error handling — it
                    # must advance the retry, not abort it
                    outcome = e
                result = self._sync_result(outcome, url)
                if result["ok"]:
                    return result
                log.warning("sync from %s did not complete (%s); trying "
                            "another peer", url, result["error"])
            return result
        except Exception as e:
            log.warning("sync_blockchain error: %s: %s",
                        type(e).__name__, e)
            return self._sync_result(e, node_url)
        finally:
            self.is_syncing = False
            self.manager.is_syncing = False

    async def _sync_blockchain(self, node_url: str):
        """Fork detection + paged download (main.py:153-227), against one
        named peer."""
        cfg = self.config.node
        iface = self.iface_factory(node_url, cfg, session=self._session(),
                                   resilience=self.resilience)
        prefetch: Optional[asyncio.Task] = None
        prefetch_from = None
        try:
            _, last_block = await self.manager.calculate_difficulty()
            starting_from = i = await self.state.get_next_block_id()
            # advisory probe (docs/SNAPSHOT.md): when the peer's tip is
            # further ahead than the reorg window can ever bridge
            # block-by-block cheaply, surface a structured hint that
            # snapshot onboarding would be the better path.  Best
            # effort — a probe failure must not abort the sync.
            try:
                info = (await iface.get("get_mining_info")).get(
                    "result") or {}
                remote_height = int(
                    (info.get("last_block") or {}).get("id") or 0)
            except Exception as e:
                log.debug("tip probe of %s failed: %s", node_url, e)
                remote_height = 0
            if remote_height - (i - 1) > cfg.sync_reorg_window:
                trace.inc("snapshot_recommended")
                telemetry.event(
                    "snapshot_recommended", peer=node_url,
                    local_height=i - 1, remote_height=remote_height,
                    lag=remote_height - (i - 1))
            local_cache = None
            last_common_block = 0
            if last_block and last_block.get("id", 0) > cfg.sync_reorg_window:
                remote_last = (await iface.get_block(i - 1))["block"]
                if remote_last["hash"] != last_block["hash"]:
                    offset = i - cfg.sync_reorg_window
                    remote_blocks = await iface.get_blocks(
                        offset, cfg.sync_reorg_window)
                    local_blocks = await self.state.get_blocks(
                        offset, cfg.sync_reorg_window)
                    # pair by block id, not list index: the peer's page
                    # may be size-truncated (reference-compatible cap),
                    # so index alignment is not guaranteed
                    remote_by_id = {rb["block"]["id"]: rb
                                    for rb in remote_blocks}
                    local_blocks.reverse()
                    for n, local in enumerate(local_blocks):
                        remote = remote_by_id.get(local["block"]["id"])
                        if remote is not None and \
                                local["block"]["hash"] == remote["block"]["hash"]:
                            last_common_block = local["block"]["id"]
                            local_cache = local_blocks[:n]
                            local_cache.reverse()
                            await self.state.remove_blocks(last_common_block + 1)
                            break
            errors: list = []
            # pipelined download: while page k is verified/accepted, page
            # k+1 is already in flight (accept work and peer I/O overlap;
            # the reference fetches and accepts strictly serially,
            # main.py:188-192).  The prefetch targets the EXPECTED next
            # offset; if accept rejects part of a page the speculative
            # fetch is discarded.
            last_fetch = [0.0]

            async def fetch_page(offset):
                # pace below the peer's server-side 40/min get_blocks
                # limit (ratelimit.py:26) — pipelining would otherwise
                # raise the request rate to one per max(fetch, accept)
                wait = cfg.sync_fetch_interval - (
                    time.monotonic() - last_fetch[0])
                if wait > 0:
                    await asyncio.sleep(wait)
                last_fetch[0] = time.monotonic()
                return await iface.get_blocks(offset, cfg.sync_page)

            while True:
                i = await self.state.get_next_block_id()
                try:
                    if prefetch is not None and prefetch_from == i:
                        try:
                            blocks = await prefetch
                        except Exception as e:
                            # a transient blip on the SPECULATIVE fetch
                            # must not abort a multi-thousand-block sync;
                            # one direct retry at consumption time
                            log.info("prefetch of page %s failed (%s); "
                                     "retrying directly", i, e)
                            blocks = await fetch_page(i)
                    else:
                        if prefetch is not None:
                            # retrieve the discarded fetch's outcome via a
                            # callback, not an await: awaiting a task we
                            # just cancelled is indistinguishable from our
                            # OWN cancellation arriving at that suspension
                            # point, and swallowing that would let sync
                            # outlive close()
                            prefetch.cancel()
                            prefetch.add_done_callback(
                                lambda t: t.cancelled() or t.exception())
                        blocks = await fetch_page(i)
                    prefetch = None
                    if len(blocks) == cfg.sync_page:
                        prefetch_from = i + cfg.sync_page
                        prefetch = asyncio.ensure_future(
                            fetch_page(prefetch_from))
                except Exception as e:
                    # a failed page (peer down, response cap, or the
                    # peer's 40/minute get_blocks rate limit on a long
                    # catch-up) must NOT fall through to the success
                    # return below — report it so callers retry
                    log.error("sync fetch failed: %s", e)
                    return f"sync fetch failed: {e}"
                try:
                    _, last_block = await self.manager.calculate_difficulty()
                    if not blocks:
                        log.info("syncing complete")
                        if last_block and last_block.get("id", 0) > starting_from:
                            self.peers.update_last_message(node_url)
                            tip = await self.state.get_last_block()
                            if tip and timestamp() - tip["timestamp"] < 86400:
                                hashes = await self.state.get_block_transaction_hashes(
                                    tip["hash"])
                                await self.propagate("push_block", {
                                    "block_content": tip["content"],
                                    "txs": hashes,
                                    "block_no": tip["id"],
                                }, ignore_url=node_url)
                        return True
                    assert await self.create_blocks(blocks, errors)
                except Exception as e:
                    log.error("sync failed: %s", errors[0] if errors else e)
                    if local_cache is not None:
                        log.info("reverting to previous chain")
                        await self.state.remove_blocks(last_common_block + 1)
                        await self.create_blocks(local_cache, [])
                    return errors[0] if errors else e
            # unreachable: the loop exits only via the returns above
        finally:
            if prefetch is not None:
                # same callback pattern as the mid-loop discard: never
                # await a task we cancelled from inside a finally that
                # may itself be unwinding a cancellation
                prefetch.cancel()
                prefetch.add_done_callback(
                    lambda t: t.cancelled() or t.exception())
            await iface.close()

    async def create_blocks(self, blocks: list,
                            errors: Optional[list] = None,
                            _allow_device_txids: bool = True) -> bool:
        """Batch ingest for sync (main.py:97-150): recompute the merkle,
        rebuild content when absent, accept via the sync path that trusts
        the embedded coinbase.

        TPU-first divergence from the reference: all signature checks of
        the PAGE are collected up front (intra-page input references
        resolve against the parsed page txs themselves) and verified in
        ONE batched dispatch; the per-block accept then reads those
        verdicts instead of paying a device round trip per block."""
        errors = errors if errors is not None else []
        _, last_block = await self.manager.calculate_difficulty()
        last_id = last_block["id"] if last_block else 0
        last_hash = last_block["hash"] if last_block else GENESIS_PREV_HASH
        i = last_id + 1
        # batched txids for the whole page (SURVEY §2.2): one device (or
        # hashlib) batch seeds every tx's hash memo instead of a
        # per-instance sha256 on first .hash() — guarded below by a
        # round-trip identity check (payload == what hash() would
        # digest), by the per-batch roaming integrity sample inside
        # txid_batch, and deterministically by check_block's merkle
        # comparison, whose leaves ARE the seeded memos (core/merkle.py)
        txid_prefill: dict = {}
        dev_cfg = self.config.device
        if dev_cfg.txid_backend != "host" and _allow_device_txids:
            try:
                all_hex = [t for b in blocks
                           for t in b.get("transactions", ())]
                if len(all_hex) >= dev_cfg.txid_min_batch:
                    import functools

                    from ..crypto.sha256 import txid_batch

                    # executor: the first auto-measurement may block for
                    # minutes against a hung device; the per-block parse
                    # loop below must stay the error boundary, so any
                    # failure here (bad hex from the peer included) just
                    # skips the prefill
                    digests = await asyncio.get_event_loop() \
                        .run_in_executor(None, functools.partial(
                            txid_batch,
                            [bytes.fromhex(h) for h in all_hex],
                            backend=dev_cfg.txid_backend,
                            min_batch=dev_cfg.txid_min_batch))
                    txid_prefill = dict(zip(all_hex, digests))
            except Exception as e:
                log.info("txid prefill skipped: %s", e)
        parsed, overlay = [], {}
        parse_error = None
        for block_info in blocks:
            try:
                block = dict(block_info["block"])
                txs = []
                for t in block_info["transactions"]:
                    tx = await self._parse_tx(t, overlay=overlay)
                    seed = txid_prefill.get(t)
                    # seed only when re-serialization is byte-identical
                    # to the wire form (txid = sha256 of the
                    # re-serialized hex — consensus; hex() is memoized
                    # and needed later by storage, so this costs nothing)
                    if seed is not None and getattr(tx, "_hash", "x") is None \
                            and tx.hex() == t:
                        tx._hash = seed
                    txs.append(tx)
            except Exception as e:
                # keep the valid prefix: the accept loop below still
                # commits every block parsed so far (the interleaved
                # reference loop made the same forward progress)
                log.debug("sync: stopping page at unparseable block: %s", e)
                parse_error = f"block parse failed: {e}"
                break
            coinbase = None
            for tx in txs:
                if isinstance(tx, CoinbaseTx):
                    txs.remove(tx)
                    coinbase = tx
                    break
            for tx in txs:
                overlay[tx.hash()] = tx
            if coinbase is not None:
                overlay[coinbase.hash()] = coinbase
            parsed.append((block, txs, coinbase))

        self.manager.page_sig_verdicts = await self._page_sig_prefill(
            parsed, overlay)
        try:
            for block, txs, coinbase in parsed:
                block["merkle_tree"] = merkle_root(txs)
                content = block.get("content")
                if not content:
                    # the rebuilt header must NOT embed the memo-derived
                    # root: check_block compares the header root against
                    # merkle_root's memo leaves, so embedding the memo
                    # root would compare a corrupt device seed with
                    # itself — hash the raw hexes (host) for the header
                    # and the backstop stays deterministic
                    block["merkle_tree"] = merkle_root(
                        [tx.hex() for tx in txs])
                    content = block_to_bytes(last_hash, block).hex()
                if int(block["id"]) != i:
                    errors.append(f"unexpected block id {block['id']} != {i}")
                    return False
                if coinbase is None:
                    errors.append(f"block {i} has no coinbase")
                    return False
                if not await self.manager.create_block_syncing(
                        content, txs, coinbase, errors=errors):
                    if (txid_prefill and
                            any("merkle" in e for e in errors[-2:])):
                        # a wrong device-seeded txid surfaces here as a
                        # merkle mismatch; the integrity sample can miss
                        # a faulty lane, and retrying the page through
                        # the same device would wedge catch-up for as
                        # long as the fault lasts — redo the remaining
                        # blocks with host hashing (fresh parse, no
                        # seeds) before giving up
                        log.warning(
                            "sync accept hit a merkle mismatch with "
                            "device-seeded txids at block %d; retrying "
                            "the page with host hashing", i)
                        self.manager.page_sig_verdicts = None
                        remaining = [
                            b for b in blocks
                            if int(b["block"]["id"]) >= i]
                        errors.append(
                            f"retrying {len(remaining)} blocks with "
                            "host txids after device-seeded merkle "
                            "mismatch")
                        return await self.create_blocks(
                            remaining, errors,
                            _allow_device_txids=False)
                    return False
                last_hash = block["hash"]
                i += 1
        finally:
            self.manager.page_sig_verdicts = None
        if parse_error:
            errors.append(parse_error)
            return False
        return True

    def _prefill_worthwhile(self, n_inputs: int) -> bool:
        """Page-level batching only pays when the checks would go to a
        device (collapsing per-block round trips into one dispatch); on
        the host path it would just double address-resolution reads."""
        from ..verify.txverify import _resolve_backend

        return _resolve_backend(
            self.config.device.sig_backend, n_inputs) != "host"

    async def _page_sig_prefill(self, parsed, overlay) -> Optional[dict]:
        """One batched signature dispatch for a whole sync page.  Checks
        that fail to collect here (unresolvable inputs, malformed txs)
        are simply left out — the per-block accept recomputes anything
        missing and reports the real error.  Skipped entirely when the
        backend resolves to the host path: there the per-block batch is
        already cheap and the prefill would only double the per-input
        address-resolution reads."""
        n_inputs = sum(len(tx.inputs)
                       for _b, txs, _cb in parsed for tx in txs)
        if n_inputs == 0 or not self._prefill_worthwhile(n_inputs):
            return None
        verifier = TxVerifier(
            self.manager.state, is_syncing=True,
            verify_pad_block=self.config.device.verify_pad_block,
            verify_device_timeout=self.config.device.verify_device_timeout,
            tx_overlay=overlay,
            verify_mesh_devices=self.config.device.mesh_devices)
        checks = []
        for _block, txs, _cb in parsed:
            for tx in txs:
                try:
                    c = await verifier.collect_sig_checks(tx)
                except Exception as e:
                    # prefill is best-effort; the accept loop re-verifies
                    log.debug("sig-check prefill skipped a tx: %s", e)
                    c = None
                if c:
                    checks.extend(c)
        if not checks:
            return None
        checks = list(dict.fromkeys(checks))  # dedup, keep order
        verdicts = await run_sig_checks_async(
            checks, backend=self.config.device.sig_backend,
            pad_block=self.config.device.verify_pad_block,
            device_timeout=self.config.device.verify_device_timeout,
            mesh_devices=self.config.device.mesh_devices)
        return dict(zip(checks, verdicts))

    # --------------------------------------------------------- app build --
    def _build_app(self) -> web.Application:
        app = web.Application(middlewares=[self._middleware],
                              client_max_size=self.config.node.response_cap)
        r = app.router
        r.add_get("/", self.h_root)
        for path, handler in [
            ("/push_tx", self.h_push_tx),
            ("/push_block", self.h_push_block),
            ("/send_to_address", self.h_send_to_address),
        ]:
            r.add_get(path, handler)
            r.add_post(path, handler)
        for path, handler in [
            ("/sync_blockchain", self.h_sync_blockchain),
            ("/get_mining_info", self.h_get_mining_info),
            ("/get_validators_info", self.h_get_validators_info),
            ("/get_delegates_info", self.h_get_delegates_info),
            ("/get_address_info", self.h_get_address_info),
            ("/get_address_transactions", self.h_get_address_transactions),
            ("/add_node", self.h_add_node),
            ("/get_nodes", self.h_get_nodes),
            ("/get_pending_transactions", self.h_get_pending_transactions),
            ("/get_transaction", self.h_get_transaction),
            ("/get_block", self.h_get_block),
            ("/get_block_details", self.h_get_block_details),
            ("/get_blocks", self.h_get_blocks),
            ("/get_blocks_details", self.h_get_blocks_details),
            ("/dobby_info", self.h_dobby_info),
            ("/get_supply_info", self.h_get_supply_info),
            ("/snapshot/manifest", self.h_snapshot_manifest),
            ("/archive/manifest", self.h_archive_manifest),
            ("/metrics", self.h_metrics),
        ]:
            r.add_get(path, handler)
        r.add_get("/snapshot/chunk/{i}", self.h_snapshot_chunk)
        r.add_get("/archive/segment/{i}", self.h_archive_segment)
        if self.config.telemetry.debug_endpoints:
            r.add_get("/debug/traces", self.h_debug_traces)
            r.add_get("/debug/events", self.h_debug_events)
            r.add_get("/debug/alerts", self.h_debug_alerts)
            r.add_post("/debug/alerts", self.h_debug_alerts_post)
            r.add_get("/debug/breakers", self.h_debug_breakers)
            r.add_get("/debug/cache", self.h_debug_cache)
            r.add_get("/debug/archive", self.h_debug_archive)
            if self.config.profile.enabled:
                r.add_get("/debug/profile", self.h_debug_profile)
        if self.config.ws.enabled:
            from ..ws.hub import WsHub

            self.ws_hub = WsHub(self.config.ws)
            r.add_get("/ws", self.ws_hub.handle)
        # SLO latency series for the fixed route set (not /ws — a
        # socket's "latency" is its lifetime — and not /debug/*, which
        # would meter the metering).  Preregistered so every endpoint
        # exports an all-zero family from scrape #1.
        self._slo_paths = {
            res.canonical for res in r.resources()
            if res.canonical.startswith("/")
            and not res.canonical.startswith(("/ws", "/debug"))}
        if self.telemetry_scope is not None:
            with self.telemetry_scope.activate():
                telemetry.slo.preregister(self._slo_paths)
        else:
            telemetry.slo.preregister(self._slo_paths)
        if self.watchtower is not None:
            # the cadence task starts with the app (TestServer/AppRunner
            # both run on_startup) and dies with the service set in
            # close(); scenarios that pump evaluate_once() manually set
            # a huge interval so this loop never races them
            async def _start_watchtower(_app) -> None:
                self._spawn_service(self.watchtower.run())

            app.on_startup.append(_start_watchtower)
        return app


def _json_block(block: Optional[dict]) -> dict:
    """Blocks carry Decimal difficulty/reward; make them JSON-clean the way
    the reference's FastAPI encoder does (floats/strings)."""
    if not block:
        return {}
    out = dict(block)
    if "difficulty" in out:
        out["difficulty"] = float(out["difficulty"])
    if "reward" in out:
        out["reward"] = str(out["reward"])
    return out


def _json_dumps(obj) -> str:
    def default(o):
        if isinstance(o, Decimal):
            return str(o)
        raise TypeError(type(o))
    return json.dumps(obj, default=default)


def run(config: Optional[Config] = None) -> None:
    """Launcher (reference run_node.py): serve the node app."""
    node = Node(config)
    web.run_app(node.app, host=node.config.node.host,
                port=node.config.node.port)
