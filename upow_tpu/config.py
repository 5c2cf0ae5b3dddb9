"""Typed configuration for every process in the framework.

The reference scatters its knobs across ``config.py:1`` (the CORE_URL
seed), env vars (``upow/node/main.py:249-254``), ``ip_config.json``
(hot-reloaded, ``ip_manager.py:19-40``), WebSocket constants
(``websocket/socket_config.py:6-43``) and hardcoded consensus constants.
Here one dataclass tree feeds the node, miner, wallet and bench; every
field can come from (in order of precedence) explicit kwargs, a JSON
config file, or ``UPOW_``-prefixed environment variables.

Device selection is ``device.device`` (``auto|tpu|cpu``, the switch from
BASELINE.json), read once where the node and the miner start
(device/runtime.py ``start``); mesh shape covers multi-chip.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Optional

DEFAULT_SEED_URL = "https://api.upow.ai/"


@dataclass
class DeviceConfig:
    """Compute-backend selection (BASELINE.json `device` flag)."""

    device: str = "auto"            # auto | tpu | cpu — tpu: arm at
                                    # start-up, exit non-zero without
                                    # the chip, never fall back to the
                                    # host or the jnp program; cpu: no
                                    # TPU backend is ever initialised;
                                    # auto: probe lazily and degrade
    search_backend: str = "auto"    # auto | pallas | jnp | native | python
    sig_backend: str = "auto"       # auto | tpu | host
    search_batch: int = 1 << 24     # nonces per device dispatch
    verify_pad_block: int = 128     # lane padding for the P-256 kernel
    verify_device_timeout: float = 240.0  # seconds before a hung device
                                    # dispatch falls back to the host path
                                    # (the first dispatch of a shape, which
                                    # compiles, gets COMPILE_ALLOWANCE x
                                    # this: verify/txverify.py)
    mesh_devices: int = 0           # 0 = all visible devices
    utxo_index: bool = False        # device-resident UTXO membership
                                    # prefilter on block accept (worth it
                                    # with a real accelerator; on a CPU
                                    # node sqlite is already fast)
    txid_backend: str = "auto"      # auto | device | host — batch txid
                                    # hashing for sync pages / block
                                    # accept (crypto/sha256.txid_batch);
                                    # auto resolves by measuring both
                                    # once per process
    txid_min_batch: int = 256       # below this, always hashlib
    verify_microbatch: int = 1024   # txs per check_block micro-batch:
                                    # digest prep of batch N overlaps the
                                    # in-flight sig verify of batch N-1
                                    # (verify/block.py); 0 = whole block


@dataclass
class DeviceRuntimeConfig:
    """Per-process device-runtime service (upow_tpu/device/runtime.py,
    docs/DEVICE_RUNTIME.md).  Operational only — the runtime changes who
    shares a dispatch, never what is computed, so nodes with different
    runtime settings stay bit-identical on chain state.  All fields
    overridable as ``UPOW_DEVICE_RUNTIME_<FIELD>``."""

    arm_timeout: float = 90.0       # backend probe/arm deadline; a hung
                                    # backend init costs the process ONE
                                    # such timeout, then (device=auto)
                                    # every source runs on the host
                                    # paths
    weights: str = ("block=4,index=3,mempool=2,verify=2,"
                    "mine=1,bench=1,other=1")
                                    # fair-share weights per source; a
                                    # served item charges cost/weight to
                                    # its source's virtual pass, so
                                    # block verify outruns a saturating
                                    # miner stream 4:1
    queue_max: int = 8192           # per-source pending-item cap;
                                    # overflow raises (backpressure)
    max_coalesce: int = 64          # sig submissions merged into one
                                    # shared dispatch

    def parsed_weights(self) -> dict:
        weights = {}
        for part in self.weights.split(","):
            name, _, raw = part.strip().partition("=")
            name, raw = name.strip(), raw.strip()
            if name and raw:
                try:
                    weights[name] = max(1, int(raw))
                except ValueError:
                    raise ValueError(
                        f"device_runtime.weights entry {part!r}: weight "
                        f"must be an integer") from None
        return weights

    @classmethod
    def from_env(cls) -> "DeviceRuntimeConfig":
        """Defaults + ``UPOW_DEVICE_RUNTIME_*`` env overrides — the
        runtime singleton arms before any Config object exists, so it
        reads the same env surface directly."""
        cfg = cls()
        _apply_env_fields(cfg, "device_runtime")
        return cfg


@dataclass
class ResilienceConfig:
    """Retry / circuit-breaker / degradation / fault-injection knobs.

    Everything here is operational policy, not consensus: two nodes with
    different resilience settings stay bit-identical on chain state.
    Fault injection is OFF unless ``faults`` is non-empty, so production
    code paths run unmodified by default.
    """

    # retry with jittered exponential backoff for outbound RPC
    rpc_attempts: int = 3           # total tries per logical call
    rpc_backoff_base: float = 0.25  # first retry delay (seconds)
    rpc_backoff_max: float = 2.0    # per-retry delay ceiling
    rpc_backoff_multiplier: float = 2.0
    rpc_jitter: float = 0.5         # +/- fraction of each delay
    rpc_deadline: float = 45.0      # total budget per logical call
                                    # (attempts + backoffs); 0 = none
    propagate_deadline: float = 10.0  # per-peer bound on gossip sends
    # per-peer circuit breakers (PeerBook health scores)
    breaker_failure_threshold: int = 5   # consecutive failures -> open
    breaker_open_secs: float = 30.0      # open -> half-open probe delay
    breaker_half_open_max: int = 1       # trial calls while half-open
    # TPU -> CPU graceful degradation for the verify hot path
    device_failure_limit: int = 3   # consecutive errors -> degraded
    device_cooldown: float = 60.0   # degraded -> re-probe interval
    # deterministic fault injection (resilience/faultinject.py); empty
    # spec = disabled, hooks are inert.  Example:
    #   "rpc:error:p=0.5;device.verify:error:times=3"
    faults: str = ""
    faults_seed: int = 0


@dataclass
class MempoolConfig:
    """Micro-batched mempool subsystem (upow_tpu/mempool/).

    All operational policy: nodes with different mempool settings stay
    bit-identical on chain state, and push_tx keeps the reference wire
    shape (error strings / status codes) regardless of these knobs.
    """

    enabled: bool = True            # False = per-request serial intake
                                    # (the reference-shaped path, kept
                                    # as the differential baseline)
    coalesce_window_ms: float = 2.0  # admission-queue drain window: how
                                    # long the first waiter of a batch
                                    # holds the door for stragglers
    max_intake_batch: int = 128     # txs per micro-batch (one P-256
                                    # device dispatch per batch)
    max_pool_bytes_hex: int = 64 * 1024 * 1024  # pool byte cap (hex
                                    # chars, 16 reference blocks);
                                    # overflow evicts lowest fee-rate
    tx_ttl: float = 7200.0          # seconds before an un-mined pooled
                                    # tx expires (0 = never)
    tx_cache_size: int = 1 << 16    # push_tx dedup set capacity
                                    # (replaces the 100-entry deque)
    tx_cache_ttl: float = 600.0     # seconds a dedup entry stays live
    allow_rbf: bool = False         # replace-by-fee on outpoint
                                    # conflict (pool API only; intake
                                    # keeps the reference reject)
    reinject_on_reorg: bool = True  # re-queue txs from rolled-back
                                    # blocks into the journal/pool


@dataclass
class CacheConfig:
    """Generation-anchored hot-state read cache (state/hotcache.py,
    docs/CACHING.md).  Operational only: the cache serves byte-identical
    responses, so nodes with different cache settings stay bit-identical
    on the wire.  All overridable as ``UPOW_CACHE_<FIELD>``."""

    enabled: bool = True
    class_cap_bytes: int = 8 * 1024 * 1024  # default LRU byte cap per
                                    # entry class (address/blocks/tx/...)
    class_caps: str = ""            # per-class overrides, e.g.
                                    # "address=16777216,blocks=4194304"
    max_entry_bytes: int = 1 * 1024 * 1024  # bodies above this are
                                    # served but never stored (one giant
                                    # page must not flush a whole class)
    revalidate_interval: float = 0.25  # seconds between re-anchoring the
                                    # generation against the shared DB
                                    # (tip hash + journal stamp) to catch
                                    # OTHER workers' writes; 0 = every
                                    # read, negative = never (sole-writer
                                    # process)

    def parsed_class_caps(self) -> dict:
        caps = {}
        for part in self.class_caps.split(","):
            name, _, raw = part.strip().partition("=")
            if name and raw:
                try:
                    caps[name] = int(raw)
                except ValueError:
                    raise ValueError(
                        f"cache.class_caps entry {part!r}: cap must be an"
                        f" integer byte count") from None
        return caps


@dataclass
class SnapshotConfig:
    """Block-hash-anchored UTXO snapshot subsystem (upow_tpu/snapshot/,
    docs/SNAPSHOT.md).  Operational only: a snapshot-restored node and a
    full-replay node end on byte-identical UTXO fingerprints, so none of
    these knobs touch consensus.  All overridable as
    ``UPOW_SNAPSHOT_<FIELD>``."""

    dir: str = ""                   # snapshot root directory; '' disables
                                    # both building and serving
    chunk_bytes: int = 1 << 20      # fixed chunk size the payload is
                                    # split into (each chunk sha256'd
                                    # into the manifest)
    blocks_tail: int = 64           # recent block rows carried in the
                                    # payload so a restored node has a
                                    # tip + fork-detection history
                                    # (should be >= sync_reorg_window in
                                    # production; swarm uses a tiny
                                    # window so the default covers it)
    keep: int = 2                   # on-disk generations retained; older
                                    # ones and stale staging dirs are
                                    # pruned (never raising)
    chunk_retries: int = 2          # per-chunk integrity retries against
                                    # ONE source before failing over
    max_chunks: int = 1 << 14       # restore-side ceilings on what a
    max_chunk_bytes: int = 16 << 20  # peer manifest may declare; an
    max_payload_bytes: int = 1 << 30  # oversize manifest is rejected
                                    # before any chunk is fetched
                                    # (anti-DoS on the bootstrap path)
    rebuild_interval_blocks: int = 0  # rebuild the snapshot generation
                                    # every N accepted blocks (0 =
                                    # operator-driven only); arms the
                                    # archive compactor without an
                                    # operator
    rebuild_jitter_blocks: int = 0  # per-node deterministic offset
                                    # (seeded from the node identity,
                                    # 0..jitter) added to the cadence so
                                    # a fleet doesn't rebuild in
                                    # lockstep


@dataclass
class ArchiveConfig:
    """Cold-block archival tier (upow_tpu/archive/, docs/ARCHIVE.md).
    Operational only: pruned and unpruned nodes answer every read
    byte-identically, so none of these knobs touch consensus.  All
    overridable as ``UPOW_ARCHIVE_<FIELD>``."""

    dir: str = ""                   # archive root directory; '' disables
                                    # the whole tier (no reader attach,
                                    # no compactor, /archive/* serve 404)
    segment_blocks: int = 256       # fixed height range per segment;
                                    # a pure function of chain content,
                                    # so every node on the same chain
                                    # with the same setting produces
                                    # byte-identical segments
    safety_window: int = 64         # blocks below the snapshot anchor
                                    # kept hot regardless (must exceed
                                    # any plausible reorg depth; pair
                                    # with node.sync_reorg_window)
    reader_cache_segments: int = 4  # parsed segments kept in memory for
                                    # fallthrough reads (LRU)
    max_segment_bytes: int = 256 << 20  # fetch-side ceiling on what a
    max_segments: int = 1 << 12         # peer manifest may declare
                                        # (anti-DoS, mirrors snapshot
                                        # restore caps)


@dataclass
class NodeConfig:
    host: str = "0.0.0.0"
    port: int = 3006                # reference run_node.py port
    db_backend: str = "sqlite"      # sqlite | postgres
    db_path: str = "upow_tpu.db"    # sqlite file ('' -> in-memory)
    pg_dsn: str = ""                # postgres DSN (db_backend=postgres);
                                    # reference ecosystem interop — point
                                    # at an existing uPow database
                                    # (db_setup.sh / schema.sql)
    seed_url: str = DEFAULT_SEED_URL
    peers_file: str = "nodes.json"
    ip_config_file: str = "ip_config.json"
    self_url: str = ""              # discovered from first request if empty
    trust_proxy_headers: bool = False  # honour X-Forwarded-For/X-Real-IP
    max_peers: int = 100            # nodes_manager.py:26
    active_within: int = 7 * 86400  # peer considered active (nodes_manager.py:24)
    prune_after: int = 90 * 86400   # forget peers silent this long (:25)
    propagate_sample: int = 10      # sample size per class (:144-149)
    response_cap: int = 20 * 1024 * 1024  # streaming response cap (:79-86)
    http_timeout: float = 30.0      # outbound RPC session total timeout
                                    # (both session-creation sites: the
                                    # node's shared pool and the lazy
                                    # NodeInterface fallback)
    sync_reorg_window: int = 500    # main.py:167-185
    sync_page: int = 1000           # block download page (main.py:188-192)
    sync_fetch_interval: float = 1.7  # min seconds between get_blocks
                                    # fetches — the peer's limit is
                                    # 40/min (one per 1.5 s); 1.7 s keeps
                                    # headroom for clock jitter and the
                                    # limiter's window alignment even
                                    # with the pipelined next-page
                                    # prefetch
    mempool_clean_interval: int = 600  # main.py:678-683
    rate_limits_enabled: bool = True   # slowapi parity (main.py:55)


@dataclass
class WsConfig:
    """WebSocket push sidecar limits (websocket/socket_config.py:6-43)."""

    enabled: bool = True
    max_connections: int = 1000
    max_per_user: int = 5
    max_message_bytes: int = 64 * 1024
    rate_limit_per_minute: int = 60
    heartbeat_interval: float = 30.0
    connection_expiry: float = 300.0
    cleanup_interval: float = 60.0  # idle-expiry sweep period
    send_queue_max: int = 256       # bounded per-subscriber send queue;
                                    # overflow sheds that subscriber's
                                    # oldest pending message
                                    # (drop-slowest) and counts it as
                                    # upow_ws_dropped_messages; 0 =
                                    # unbounded (never shed)
    channels: tuple = ("block", "transaction")


@dataclass
class MinerConfig:
    address: str = ""
    node_url: str = DEFAULT_SEED_URL
    workers: int = 1                # device shards, not processes
    ttl: float = 90.0               # per-template budget (miner.py:96-98)
    refresh: float = 100.0          # outer watchdog (miner.py:149-156)


@dataclass
class LogConfig:
    path: str = "logs/app.log"
    level: str = "INFO"
    max_bytes: int = 5 * 1024 * 1024   # my_logger.py rotation size
    backups: int = 100
    console: bool = True
    json_format: bool = False       # JSONL records carrying trace_id


@dataclass
class TelemetryConfig:
    """Observability knobs (upow_tpu/telemetry/) — operational only,
    never consensus.  All overridable as ``UPOW_TELEMETRY_<FIELD>``."""

    trace_requests: bool = True     # root span per inbound HTTP request
    trace_recent: int = 32          # completed traces kept, recency ring
    trace_slowest: int = 16         # completed traces kept, slowest top-N
    max_trace_spans: int = 512      # span budget per trace tree
    events_buffer: int = 256        # /debug/events ring size
    max_metric_names: int = 1024    # cardinality cap per registry kind
    debug_endpoints: bool = True    # serve /debug/traces, /debug/events
    instance_scope: bool = False    # per-node registries (swarm fleets);
                                    # default keeps the process globals


@dataclass
class WatchtowerConfig:
    """Streaming alerting engine (upow_tpu/watchtower/) — operational
    only, never consensus.  Overridable as ``UPOW_WATCHTOWER_<FIELD>``.

    Defaults describe the standing rule pack (docs/ALERTING.md):
    verify-throughput collapse, mempool depth spike, sync lag, breaker
    flip storm, ws drop rate, device arm flaps, stuck block height,
    and per-route SLO burn rates.  Thresholds are deliberately
    conservative — the clean seeded geo-soak must fire zero alerts."""

    enabled: bool = False           # run the evaluation task on this node
    interval: float = 5.0           # evaluation cadence, seconds
    # SLO burn-rate (burnrate.py): canonical 5m/1h + 30m/6h pairs,
    # compressible for scenarios via window_scale.
    slo_target: float = 0.999
    fast_burn: float = 14.4
    slow_burn: float = 6.0
    window_scale: float = 1.0
    # for-durations: fast rules page quickly, slow rules must sustain.
    for_fast: float = 15.0
    for_slow: float = 60.0
    # rule thresholds
    verify_min_rate: float = 1.0    # submissions/s EWMA floor before the
                                    # collapse rule may judge a drop
    verify_z: float = 6.0           # z-score magnitude for rate anomalies
    mempool_spike_ratio: float = 8.0
    mempool_spike_floor: float = 1000.0
    sync_lag_limit: float = 600.0   # seconds behind tip timestamp
    breaker_storm_window: float = 60.0
    breaker_storm_opens: int = 6    # breaker open transitions in window
    ws_drop_limit: float = 50.0     # dropped ws messages per second
    arm_flap_window: float = 600.0
    arm_flaps: int = 3              # degrade/arm-failure events in window
    stuck_height_deadline: float = 300.0
    history: int = 64               # firing/resolved transition ring
    bench_events: str = ""          # append alert_fired JSONL records to
                                    # this path (bench harnesses point it
                                    # at .bench_events.jsonl)


@dataclass
class ProfilingConfig:
    """Opt-in performance capture (upow_tpu/profiling/) — all off by
    default; overridable as ``UPOW_PROFILE_<FIELD>``."""

    enabled: bool = False           # serve /debug/profile (also requires
                                    # telemetry.debug_endpoints)
    trace_dir: str = "logs/jax_traces"  # xprof capture output directory
    max_capture_seconds: float = 120.0  # auto-stop: a capture left
                                    # running past this is closed on the
                                    # next /debug/profile touch


@dataclass
class Config:
    device: DeviceConfig = field(default_factory=DeviceConfig)
    device_runtime: DeviceRuntimeConfig = field(
        default_factory=DeviceRuntimeConfig)
    node: NodeConfig = field(default_factory=NodeConfig)
    ws: WsConfig = field(default_factory=WsConfig)
    miner: MinerConfig = field(default_factory=MinerConfig)
    log: LogConfig = field(default_factory=LogConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    mempool: MempoolConfig = field(default_factory=MempoolConfig)
    cache: CacheConfig = field(default_factory=CacheConfig)
    snapshot: SnapshotConfig = field(default_factory=SnapshotConfig)
    archive: ArchiveConfig = field(default_factory=ArchiveConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    watchtower: WatchtowerConfig = field(default_factory=WatchtowerConfig)
    profile: ProfilingConfig = field(default_factory=ProfilingConfig)

    @classmethod
    def load(cls, path: Optional[str] = None, **overrides) -> "Config":
        """File -> env -> kwargs, later wins.

        Env vars: ``UPOW_<SECTION>_<FIELD>`` (e.g. ``UPOW_NODE_PORT=3007``,
        ``UPOW_DEVICE_DEVICE=tpu``).  ``overrides`` are dotted
        (``node__port=3007``).
        """
        cfg = cls()
        if path and os.path.exists(path):
            # RC001: config is a one-time startup read, before the
            # event loop serves any traffic
            with open(path) as f:  # upowlint: disable=RC001
                cfg = _merge_dict(cfg, json.load(f))
        cfg = _merge_env(cfg)
        for key, value in overrides.items():
            section, _, fname = key.partition("__")
            sub = getattr(cfg, section)
            if not hasattr(sub, fname):
                raise KeyError(f"unknown config field {key}")
            setattr(sub, fname, value)
        return cfg

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _merge_dict(cfg: Config, data: dict) -> Config:
    for section, values in data.items():
        if not hasattr(cfg, section):
            raise KeyError(f"unknown config section {section}")
        sub = getattr(cfg, section)
        for fname, value in values.items():
            if not hasattr(sub, fname):
                raise KeyError(f"unknown config field {section}.{fname}")
            setattr(sub, fname, value)
    return cfg


def _merge_env(cfg: Config) -> Config:
    for section in ("device", "device_runtime", "node", "ws", "miner",
                    "log", "resilience", "mempool", "cache", "snapshot",
                    "archive", "telemetry", "watchtower", "profile"):
        _apply_env_fields(getattr(cfg, section), section)
    return cfg


def _apply_env_fields(sub, section: str) -> None:
    """Apply ``UPOW_<SECTION>_<FIELD>`` env overrides onto one config
    dataclass instance (shared by _merge_env and the sections that must
    self-load before a Config exists, e.g. DeviceRuntimeConfig)."""
    for f in dataclasses.fields(sub):
        env = f"UPOW_{section.upper()}_{f.name.upper()}"
        if env in os.environ:
            raw = os.environ[env]
            if f.type in ("int", int):
                value = int(raw)
            elif f.type in ("float", float):
                value = float(raw)
            elif f.type in ("bool", bool):
                value = raw.lower() in ("1", "true", "yes")
            else:
                value = raw
            setattr(sub, f.name, value)
