"""Deterministic wallet-population load generator (ROADMAP item 4).

Drives an in-process node with a seeded, realistic request mix —
Zipfian hot-account balance/UTXO reads, miner ``get_mining_info``
polling, push_tx bursts through the coalescing intake, and WebSocket
subscriber churn — and records per-endpoint req/s plus p50/p95/p99
latency, both client-side (exact quantiles in the run summary) and
server-side (``slo.http.*`` histograms on ``/metrics``).

Layout (this package import is light: only :mod:`.population`):

* :mod:`.population` — seeded schedule builder (stdlib only).
* :mod:`.runner`     — schedule execution + summary (stdlib + asyncio);
  includes the deterministic mock backend the tests pin.
* :mod:`.harness`    — the real in-process node target (aiohttp).
* :mod:`.readpath`, :mod:`.coresidency` — the hot-state cache and the
  shared device-runtime scenarios, each with its byte differential.
* :mod:`.fixtures`   — seeded sig-check tuples and the funded chain.

Rates on the chip are the benchmark's (``benchmarks/run.py``,
``BENCHMARK.json``); nothing here gates on a CPU rate.
"""

from .population import LoadEvent, PopulationSpec, build_schedule  # noqa: F401

__all__ = ["LoadEvent", "PopulationSpec", "build_schedule"]
