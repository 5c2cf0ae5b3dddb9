"""In-process node target for the load generator.

Boots a real :class:`~upow_tpu.node.app.Node` over an in-memory chain
pre-funded through :func:`~upow_tpu.loadgen.fixtures.chain_with_utxo_fanout`
(so push_tx bursts carry *valid, accepted* spends through the
coalescing intake, not just parse errors) and serves it via aiohttp's
TestServer — the same harness idiom as the telemetry selfcheck.

The executor translates abstract schedule events into wire requests:

* ``balance`` / ``utxo`` / ``history`` — address reads for the wallet
  universe (rank 0 = the funded hot account, the rest fresh keypairs).
* ``mining_info`` — template polling (generation-keyed cache path).
* ``push_tx`` — POST through the mempool intake; payloads are
  pre-signed 1-in-1-out leaf spends, reused modulo the pool when a
  schedule asks for more than the fixture funded (duplicates exercise
  the dedup/conflict path, still a served request).
* ``ws_connect`` / ``ws_ping`` / ``ws_close`` — subscriber churn
  against the hub, latency = time to the acknowledging frame.
"""

from __future__ import annotations

import asyncio
import tempfile
import time
from typing import Dict, List

from ..logger import get_logger
from .population import LoadEvent, PopulationSpec, build_schedule
from .runner import ExecResult, run_schedule, summarize

log = get_logger("loadgen")

_WS_ACK_TIMEOUT = 5.0


class HttpExecutor:
    """async callable(LoadEvent) -> ExecResult against a TestClient."""

    def __init__(self, client, addresses: List[str],
                 payloads: List[str]):
        self.client = client
        self.addresses = addresses
        self.payloads = payloads
        self._ws: Dict[str, object] = {}

    async def _http(self, ev: LoadEvent) -> ExecResult:
        t0 = time.perf_counter()
        if ev.kind == "push_tx":
            payload = self.payloads[ev.param("payload", 0)
                                    % len(self.payloads)]
            resp = await self.client.post("/push_tx",
                                          json={"tx_hex": payload})
        elif ev.kind == "mining_info":
            resp = await self.client.get("/get_mining_info")
        else:
            addr = self.addresses[ev.param("wallet", 0)
                                  % len(self.addresses)]
            if ev.kind == "history":
                resp = await self.client.get(
                    "/get_address_transactions",
                    params={"address": addr, "limit": "5"})
            else:
                params = {"address": addr}
                if ev.kind == "utxo":
                    params["show_pending"] = "true"
                resp = await self.client.get("/get_address_info",
                                             params=params)
        body = await resp.json()
        latency = time.perf_counter() - t0
        # push_tx duplicates/conflicts answer ok=False on a 200 — a
        # served request, not an executor error
        ok = resp.status < 500 and (ev.kind == "push_tx"
                                    or bool(body.get("ok", True)))
        return ExecResult(endpoint=ev.endpoint, status=resp.status,
                          ok=ok, latency=latency)

    async def _ws_event(self, ev: LoadEvent) -> ExecResult:
        conn_id = ev.param("conn")
        t0 = time.perf_counter()
        ok = True
        if ev.kind == "ws_connect":
            ws = await self.client.ws_connect("/ws")
            self._ws[conn_id] = ws
            # connection_established frame, then the subscribe ack
            await asyncio.wait_for(ws.receive_json(),
                                   timeout=_WS_ACK_TIMEOUT)
            await ws.send_json({"type": "subscribe_block"})
            ack = await asyncio.wait_for(ws.receive_json(),
                                         timeout=_WS_ACK_TIMEOUT)
            ok = ack.get("type") != "error"
        elif ev.kind == "ws_ping":
            ws = self._ws.get(conn_id)
            if ws is None or ws.closed:
                ok = False
            else:
                await ws.send_json({"type": "ping"})
                pong = await asyncio.wait_for(ws.receive_json(),
                                              timeout=_WS_ACK_TIMEOUT)
                ok = pong.get("type") == "pong"
        else:  # ws_close
            ws = self._ws.pop(conn_id, None)
            if ws is not None and not ws.closed:
                await ws.close()
        return ExecResult(endpoint="ws", status=200 if ok else 599,
                          ok=ok, latency=time.perf_counter() - t0)

    async def __call__(self, ev: LoadEvent) -> ExecResult:
        if ev.kind.startswith("ws_"):
            return await self._ws_event(ev)
        return await self._http(ev)

    async def close(self) -> None:
        for ws in list(self._ws.values()):
            try:
                if not ws.closed:
                    await ws.close()
            except Exception as e:
                log.debug("ws cleanup close failed: %s", e)
        self._ws.clear()


def _wallet_addresses(spec: PopulationSpec, funded_addr: str) -> List[str]:
    """Rank-indexed address universe: the funded account is the Zipf
    hot spot; the rest are fresh (empty) keypairs — real addresses, so
    reads exercise the same state queries either way."""
    from ..core import curve, point_to_string

    n_keys = min(spec.n_wallets, 48)
    addresses = [funded_addr]
    for i in range(1, n_keys):
        _, pub = curve.keygen(rng=(spec.seed << 8) ^ (0xA0D0 + i))
        addresses.append(point_to_string(pub))
    return addresses


async def run_against_node(spec: PopulationSpec) -> dict:
    """Build the funded fixture, boot the node in-process, drive the
    schedule, return the merged summary (client-side quantiles + the
    node's own slo/ws/mempool counters)."""
    from aiohttp.test_utils import TestClient, TestServer

    from .fixtures import chain_with_utxo_fanout, leaf_spends
    from ..config import Config
    from ..core import clock
    from ..node.app import Node

    events = build_schedule(spec)
    needed = spec.push_bursts * spec.burst_size
    n_per = 24
    n_fan = max(2, -(-needed // n_per))  # ceil division

    state, _manager, d, pub, addr, mids, _mine = \
        await chain_with_utxo_fanout(n_fan, n_per, spec.seed & 0xFFFF)
    payloads = [t.hex() for t in leaf_spends(mids, addr, d, pub)]
    addresses = _wallet_addresses(spec, addr)

    cfg = Config()
    with tempfile.TemporaryDirectory() as tmp:
        cfg.node.db_path = ""
        cfg.node.seed_url = ""
        cfg.node.peers_file = f"{tmp}/nodes.json"
        cfg.node.ip_config_file = ""
        cfg.log.path = ""
        cfg.log.console = False
        node = Node(cfg, state=state)
        server = TestServer(node.app)
        await server.start_server()
        client = TestClient(server)
        node.started = True
        node.rate_limiter.enabled = False  # measuring us, not limits
        executor = HttpExecutor(client, addresses, payloads)
        try:
            t0 = time.perf_counter()
            results = await run_schedule(events, executor)
            elapsed = time.perf_counter() - t0
        finally:
            await executor.close()
            await client.close()
            await server.close()
            await node.close()
            clock.reset()

    summary = summarize(events, results, elapsed)
    summary["backend"] = "node-inprocess"
    summary["population"] = spec.to_dict()
    if node.ws_hub is not None:
        summary["ws_hub"] = node.ws_hub.get_stats()
    from ..telemetry import slo

    summary["server_slo"] = slo.summary()
    return summary
