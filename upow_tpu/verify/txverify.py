"""Transaction verification: DPoS rules on host, signatures batched on TPU.

The reference validates each transaction serially — ~10 rule checks with
live DB reads, then one fastecdsa verify per input (transaction.py:185-238,
transaction_input.py:100-109).  Here the rule checks stay host-side (they
are state lookups, not compute) but signature verification is *collected*
per transaction or per block and dispatched to the batched P-256 kernel in
one device call (crypto/p256.py) — the design SURVEY.md §2.3 calls for.

Signature semantics replicated exactly, including the reference's quirk of
accepting a signature over EITHER the raw signing bytes OR their ASCII-hex
string (transaction_input.py:100-109 tries both), and the per-tx
(pubkey, signature) dedup (transaction.py:148-163).
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

from ..core.codecs import OutputType, TransactionType, string_to_point
from ..core.constants import MAX_INODES, SMALLEST
from ..core.tx import Tx
from ..state.storage import ChainState, _INPUT_TABLE

# The one grandfathered unstake tx exempt from the release-votes rule
# (reference transaction.py:471-472).
_UNSTAKE_EXCEPTION_HASHES = {
    "8befeb253bc6eddd8501f5b27a02b195f5c06a51ccf788213cbedafe7cc49c53",
}


class SigCheck(Tuple):
    """(digest_bytes, digest_hexform, (r, s), pubkey_point) — one deferred
    signature check."""


def _dedup_sig_checks(tx: Tx, voter: bool, address_of,
                      digests: Optional[tuple] = None) -> Optional[List[tuple]]:
    """Collect per-input signature checks with the reference's dedup.

    Returns None if any input is unsigned or its key can't resolve.
    ``address_of(tx_input)`` -> spending (or voter) address string.
    ``digests`` optionally carries the (digest, digest_hexform) pair a
    fused batch prep (verify/block.py:_fused_digest_prep) already
    computed, skipping the two per-tx hashlib passes here.
    """
    if digests is not None:
        digest, digest_hexform = digests
    else:
        signing_bytes = bytes.fromhex(tx.hex(False))
        digest = hashlib.sha256(signing_bytes).digest()
        digest_hexform = hashlib.sha256(tx.hex(False).encode()).digest()
    checks, seen = [], set()
    for tx_input in tx.inputs:
        if tx_input.signature is None:
            return None
        address = address_of(tx_input)
        if address is None:
            return None
        try:
            pub = string_to_point(address)
        except (ValueError, NotImplementedError):
            return None
        # Consensus-exact dedup: the reference keys on
        # (tx_input.public_key, signature) but from_hex never sets
        # public_key (transaction.py:148-163, 520-592), so its runtime key
        # degenerates to the signature value ALONE — a later input reusing
        # an earlier input's (r, s) is skipped even under a different
        # address.  Replicate that exactly; hardening here would fork.
        key = tx_input.signature
        if key in seen:
            continue
        seen.add(key)
        checks.append((digest, digest_hexform, tx_input.signature, pub))
    return checks


# Device-path health for the verify hot path: one process-wide state
# machine (ok / degraded-with-cooldown / poisoned) replacing the old
# one-way _DEVICE_POISONED flag.  Errors now degrade with periodic
# re-probes; hangs still poison permanently (the stuck daemon thread
# cannot be reclaimed).  The node pushes its configured failure_limit /
# cooldown in via DEGRADE.configure() at startup.
from ..resilience.degrade import DegradeManager, POISONED as _POISONED

DEGRADE = DegradeManager()


#: the FIRST dispatch of a padded verify shape in this process is boxed
#: by this many ``device_timeout``s (20 min at the default 240 s): that
#: dispatch compiles — minutes per shape on a cold persistent cache,
#: PERF.md — and a compile in flight is not a hang.  Warm dispatches
#: keep the plain box.
COMPILE_ALLOWANCE = 5
_WARM_SHAPES: set = set()


def _device_usable() -> bool:
    """True iff a device backend initialized within the probe budget.

    Backend init can hang rather than raise when the accelerator is
    unreachable.  A validating node must never wedge block accept on
    that, so backend detection asks the device runtime, whose arm ran
    the process's one thread-boxed probe, and a hang poisons the device
    path for the life of the process (the stuck thread cannot be
    recovered)."""
    if DEGRADE.state == _POISONED:
        return False
    from ..device.runtime import get_runtime

    platform = get_runtime().platform()
    if platform is None:
        DEGRADE.poison("jax backend init hung/failed")
        import logging

        logging.getLogger("upow_tpu.verify").warning(
            "jax backend init hung/failed; signature verification "
            "pinned to the host path for this process")
    return platform not in (None, "cpu")


def device_verify_allowed() -> bool:
    """Public gate for other verify-path device dispatches (fused accept
    path, txid batching): a device backend is up AND the degrade state
    machine currently allows dispatching to it.  Mirrors exactly the
    check ``_resolve_backend`` applies before routing signature batches
    to the device."""
    return _device_usable() and DEGRADE.allow()


async def run_sig_checks_async(checks: Sequence[tuple],
                               backend: str = "auto",
                               pad_block: int = 128,
                               device_timeout: float = 240.0,  # operational timeout  # upowlint: disable=CP001
                               precomputed=None,
                               mesh_devices: int = 1) -> List[bool]:
    """Executor-wrapped :func:`run_sig_checks`: the device dispatch (and
    its hang time-box) must not block the node's event loop — the C++
    host batch and ctypes both release the GIL, so this also overlaps
    verification with peer I/O."""
    import asyncio
    import functools

    return await asyncio.get_event_loop().run_in_executor(
        None, functools.partial(run_sig_checks, checks, backend=backend,
                                pad_block=pad_block,
                                device_timeout=device_timeout,
                                precomputed=precomputed,
                                mesh_devices=mesh_devices))


_VERIFY_MESH = {}  # mesh_devices -> Mesh | None, built once per process
_VERIFY_MESH_LOCK = threading.Lock()  # intake + block verify race this
# cache from different executor threads


def _verify_mesh(mesh_devices: int):
    """DP mesh for the device verify dispatch (SURVEY §2.3): 0 = all
    visible devices, 1 = single device (no mesh), N = first N.  On a
    one-chip host this is always None — the batch stays resident on the
    single device with no partitioning overhead."""
    if mesh_devices == 1:
        return None
    with _VERIFY_MESH_LOCK:
        if mesh_devices not in _VERIFY_MESH:
            from ..device.runtime import get_runtime

            devices = get_runtime().devices()
            n = len(devices) if mesh_devices == 0 else min(
                mesh_devices, len(devices))
            if n <= 1:
                _VERIFY_MESH[mesh_devices] = None
            else:
                from ..parallel.mesh import make_mesh

                _VERIFY_MESH[mesh_devices] = make_mesh(devices[:n])
        return _VERIFY_MESH[mesh_devices]


_SIG_VERDICTS: "OrderedDict[tuple, bool]" = OrderedDict()
_SIG_VERDICTS_MAX = 1 << 16
_SIG_VERDICTS_LOCK = threading.Lock()  # intake + block verify run on
# different executor threads; OrderedDict mutation is not atomic
_SIG_VERDICT_STATS = {"hits": 0, "misses": 0}


def sig_verdict_stats() -> dict:
    """Cache size + hit/miss counters (observability: node /metrics)."""
    with _SIG_VERDICTS_LOCK:
        return {"size": len(_SIG_VERDICTS), **_SIG_VERDICT_STATS}


def clear_sig_verdicts() -> None:
    """Drop the process-level signature-verdict cache (tests)."""
    with _SIG_VERDICTS_LOCK:
        _SIG_VERDICTS.clear()
        _SIG_VERDICT_STATS["hits"] = _SIG_VERDICT_STATS["misses"] = 0


_CANARY_LOCK = threading.Lock()
_CANARY: Optional[Tuple[tuple, tuple]] = None
_CANARY_EXPECTED = (True, False)


def _canary_checks() -> Tuple[tuple, tuple]:
    """Deterministic (known-good, known-bad) signature checks.

    Appended to every device-path cache-miss dispatch; the device's
    verdicts are admitted into the process-wide cache only when the
    canaries come back exactly ``(True, False)``.  A device batch that
    silently miscomputes (stale AOT cache entry, sick device) then
    taints at most the one dispatch it belongs to instead of being
    replayed from the cache on every re-accept forever.  The key pair
    is fixed and public BY DESIGN — it signs nothing but this
    self-check message and guards no value.
    """
    global _CANARY
    with _CANARY_LOCK:
        if _CANARY is None:
            from ..core import curve
            from ..core.constants import CURVE_N

            priv = 0x7E57AB1E_0000C0DE_7E57AB1E_0000C0DE % CURVE_N
            k = 0x9E3779B97F4A7C15_F39CC060_5CEDC834 % CURVE_N
            pub = curve.point_mul(priv, curve.G)
            digest = hashlib.sha256(b"upow-tpu verify canary").digest()
            hexform = hashlib.sha256(b"upow-tpu verify canary hex").digest()
            z = int.from_bytes(digest, "big")  # upowlint: disable=CE001
            r = curve.point_mul(k, curve.G)[0] % CURVE_N
            s = (pow(k, -1, CURVE_N) * (z + r * priv)) % CURVE_N
            good = (digest, hexform, (r, s), pub)
            bad = (digest, hexform, (r, s - 1 if s > 1 else s + 1), pub)
            _CANARY = (good, bad)
        return _CANARY


def _resolve_backend(backend: str, n_checks: int) -> str:
    """Apply the ``auto`` policy and the device-health override (single
    source for the cached and uncached layers)."""
    if backend == "auto":
        if n_checks < 8:
            return "host"
        return "device" if (_device_usable() and DEGRADE.allow()) \
            else "host"
    if backend != "host" and not DEGRADE.allow():
        # an explicitly configured device backend must also honor the
        # health state: re-paying device_timeout (and leaking another
        # stuck daemon thread) on every block would stall the node 4 min
        # per block after one hang; a degraded device is only retried
        # after its cooldown
        return "host"
    return backend


def run_sig_checks(checks: Sequence[tuple], backend: str = "auto",
                   pad_block: int = 128,
                   device_timeout: float = 240.0,  # operational timeout  # upowlint: disable=CP001
                   use_cache: bool = True,
                   precomputed=None,
                   mesh_devices: int = 1) -> List[bool]:
    """Verify deferred checks in one (or two) batched device calls.

    Pass 1 verifies against the raw-bytes digest; only failures re-try the
    hex-string digest (the reference's or-fallback).  ``backend='host'``
    uses the C++/pure-Python path.

    ``auto`` policy: the device batch only pays off on a real
    accelerator — on a CPU-only host the XLA ladder costs minutes of
    compile for throughput the OpenMP C++ batch beats anyway, so auto
    means device iff a device backend probes healthy (see
    :func:`_device_usable` — the probe survives a backend init that hangs), and
    the host batch otherwise (small batches always stay host-side:
    dispatch overhead dominates under ~8 signatures).

    Verdicts are memoized process-wide (bounded LRU) keyed on the full
    (digest, hexdigest, signature, pubkey) tuple: ECDSA verification is
    pure, so a tx verified at mempool intake is NOT re-verified when its
    block is accepted — the reference pays that double verification
    (push_tx intake then check_block, transaction.py:185-238) on every
    gossiped tx.  Reorgs and sync re-accepts hit the same cache.

    Host-path verdicts are always cached.  Device-path verdicts are
    cached only when the batch's canary pair (:func:`_canary_checks`,
    one known-good and one known-bad signature riding in the same
    dispatch) comes back exactly (True, False): a device batch that
    silently miscomputes (stale AOT cache entry, sick device) would
    otherwise turn one wrong verdict into a permanent one — replayed on
    every re-accept even after the device path is poisoned off.  With
    the canary gate, a sick batch taints at most itself.
    """
    if not checks:
        return []
    if precomputed:
        # page-level batch verdicts (chain-sync prefill): one device
        # dispatch per sync page instead of one per block.  Transient —
        # lives only for that page's accept loop, so it carries exactly
        # the per-batch device trust the per-block dispatch would.
        out_pre: List[Optional[bool]] = [precomputed.get(c) for c in checks]
        rest_idx = [i for i, v in enumerate(out_pre) if v is None]
        if rest_idx:
            rest = run_sig_checks(
                [checks[i] for i in rest_idx], backend=backend,
                pad_block=pad_block, device_timeout=device_timeout,
                use_cache=use_cache, mesh_devices=mesh_devices)
            for i, v in zip(rest_idx, rest):
                out_pre[i] = v
        return out_pre  # type: ignore[return-value]
    if use_cache:
        out: List[Optional[bool]] = [None] * len(checks)
        misses = []
        with _SIG_VERDICTS_LOCK:
            for i, c in enumerate(checks):
                v = _SIG_VERDICTS.get(c)
                if v is None:
                    misses.append(i)
                else:
                    _SIG_VERDICTS.move_to_end(c)
                    out[i] = v
            _SIG_VERDICT_STATS["hits"] += len(checks) - len(misses)
            _SIG_VERDICT_STATS["misses"] += len(misses)
        if misses:
            miss_checks = [checks[i] for i in misses]
            resolved = _resolve_backend(backend, len(miss_checks))
            dispatch_checks = miss_checks
            canaries = 0
            if resolved != "host":
                # ride the canary pair along in the same device batch;
                # their verdicts gate whether this batch may be cached
                canary = _canary_checks()
                dispatch_checks = miss_checks + list(canary)
                canaries = len(canary)
            fresh = run_sig_checks(
                dispatch_checks, backend=resolved,
                pad_block=pad_block, device_timeout=device_timeout,
                use_cache=False, mesh_devices=mesh_devices)
            cacheable = resolved == "host"
            if canaries:
                canary_ok = tuple(fresh[-canaries:]) == _CANARY_EXPECTED
                fresh = fresh[: len(miss_checks)]
                from .. import trace

                trace.inc("verify.canary_%s"
                          % ("pass" if canary_ok else "fail"))
                if canary_ok:
                    cacheable = True
                else:
                    import logging

                    logging.getLogger("upow_tpu.verify").warning(
                        "device verify canary failed; %d verdicts NOT "
                        "cached", len(miss_checks))
            for i, v in zip(misses, fresh):
                out[i] = v
            if cacheable:
                with _SIG_VERDICTS_LOCK:
                    for i, v in zip(misses, fresh):
                        _SIG_VERDICTS[checks[i]] = v
                    while len(_SIG_VERDICTS) > _SIG_VERDICTS_MAX:
                        _SIG_VERDICTS.popitem(last=False)
        return out  # type: ignore[return-value]
    backend = _resolve_backend(backend, len(checks))
    if backend == "host":
        from .. import native

        batch = native.p256_verify_batch(
            [c[0] for c in checks], [c[2] for c in checks],
            [c[3] for c in checks])
        if batch is None:
            batch = [_host_verify_digest(c[0], c[2], c[3]) for c in checks]
        out = list(map(bool, batch))
        retry = [i for i, ok in enumerate(out) if not ok]
        if retry:
            second = native.p256_verify_batch(
                [checks[i][1] for i in retry],
                [checks[i][2] for i in retry],
                [checks[i][3] for i in retry])
            if second is None:
                second = [_host_verify_digest(checks[i][1], checks[i][2],
                                              checks[i][3]) for i in retry]
            for i, ok in zip(retry, second):
                out[i] = bool(ok)
        return out

    from ..crypto import p256

    def device_batch(digests, sigs, pubs):
        """Time-boxed device dispatch: a device that dies AFTER the
        startup probe makes the call hang, not raise.  The first
        dispatch of a padded shape gets ``COMPILE_ALLOWANCE`` boxes and
        reports its seconds (event ``verify_first_dispatch``).
        In ``device=auto`` a hang poisons the device path and raised
        exceptions degrade it (CPU fallback + cooldown re-probe) — the
        caller re-runs on the host and the node survives.  In a
        ``device=tpu`` process the failure is the caller's."""
        import logging

        from ..device.runtime import get_runtime
        from ..resilience.faultinject import get_injector

        def dispatch():
            # chaos hook: an injected hang lands INSIDE the boxed
            # worker thread, exercising the same time-box a real stuck
            # PJRT call would
            injector = get_injector()
            if injector is not None:
                injector.fire_sync("device.verify")
            return p256.verify_batch_prehashed(
                digests, sigs, pubs, pad_block=pad_block,
                mesh=_verify_mesh(mesh_devices))

        import time as _time

        from .. import trace as _trace

        shape = (p256._pad_to_block(len(digests), pad_block), mesh_devices)
        cold = shape not in _WARM_SHAPES
        t0 = _time.perf_counter()
        # through the device-runtime queue (executes inline when this
        # already runs on the drainer thread — a coalesced front group)
        status, value = get_runtime().run_boxed(
            dispatch, device_timeout * (COMPILE_ALLOWANCE if cold else 1),
            kernel="p256_verify", source="verify")
        seconds = _time.perf_counter() - t0
        from ..telemetry.device import DISPATCH_BUCKETS as _DISPATCH_BUCKETS

        _trace.observe("kernel.p256_verify.dispatch_seconds", seconds,
                       buckets=_DISPATCH_BUCKETS)
        log = logging.getLogger("upow_tpu.verify")
        if cold:
            from ..telemetry import events

            events.emit("verify_first_dispatch", padded=shape[0],
                        real=len(digests), status=status,
                        seconds=round(seconds, 3))
            log.info("first p256 verify dispatch at %d lanes (%d real): "
                     "%s in %.1fs", shape[0], len(digests), status, seconds)
        if status == "ok":
            _WARM_SHAPES.add(shape)
            DEGRADE.record_success()
            return value
        if strict:
            if status == "err":
                raise value
            raise TimeoutError(
                "device verify of %d lanes still running after %.0fs"
                % (shape[0], seconds))
        if status == "err":
            DEGRADE.record_failure(value)
            log.warning(
                "device verify dispatch failed (state=%s): %s",
                DEGRADE.state, value, exc_info=value)
            raise value
        DEGRADE.poison("device verify hung")
        log.warning(
            "device verify dispatch hung; falling back to host path "
            "(device poisoned for this process)")
        raise TimeoutError("device verify hung")

    import logging

    from ..device.runtime import tpu_required

    # device=tpu: a failed device verify is an error, never a host run
    strict = tpu_required()
    log = logging.getLogger("upow_tpu.verify")
    try:
        first = device_batch(
            [c[0] for c in checks], [c[2] for c in checks],
            [c[3] for c in checks])
    except Exception as e:
        if strict:
            raise
        from .. import trace

        trace.inc("resilience.device_fallback")
        log.warning("device verify pass-1 unusable (%s); host fallback for "
                    "%d checks", e, len(checks))
        return run_sig_checks(checks, backend="host", pad_block=pad_block,
                              device_timeout=device_timeout, use_cache=False)
    out = list(map(bool, first))
    retry = [i for i, ok in enumerate(out) if not ok]
    if retry:
        try:
            second = device_batch(
                [checks[i][1] for i in retry],
                [checks[i][2] for i in retry],
                [checks[i][3] for i in retry])
        except Exception as e:
            if strict:
                raise
            # pass-1 verdicts are already in hand (same math on device);
            # only the hex-digest retries need the host
            log.debug("device verify pass-2 unusable (%s); host retry for "
                      "%d checks", e, len(retry))
            second = [_host_verify_digest(checks[i][1], checks[i][2],
                                          checks[i][3]) for i in retry]
        for i, ok in zip(retry, second):
            out[i] = bool(ok)
    return out


def _host_verify_digest(digest: bytes, sig, pub) -> bool:
    from ..core import curve
    from ..core.constants import CURVE_N

    r, s = sig
    if not (0 < r < CURVE_N and 0 < s < CURVE_N):
        return False
    # ECDSA bits2int (SEC 1 / RFC 6979): the digest is a big-endian
    # integer by the signature algorithm's definition, not wire format.
    z = int.from_bytes(digest, "big")  # upowlint: disable=CE001
    w = pow(s, -1, CURVE_N)
    p1 = curve.point_mul(z * w % CURVE_N, curve.G)
    p2 = curve.point_mul(r * w % CURVE_N, pub)
    p = curve.point_add(p1, p2)
    return p is not None and p[0] % CURVE_N == r % CURVE_N


class TxVerifier:
    """All rule checks for one transaction against a ChainState.

    Mirrors Transaction.verify's chain (transaction.py:185-238); each rule
    method cites its reference lines.
    """

    def __init__(self, state: ChainState, is_syncing: bool = False,
                 verify_pad_block: int = 128,
                 verify_device_timeout: float = 240.0,  # operational timeout  # upowlint: disable=CP001
                 tx_overlay: Optional[Dict[str, Tx]] = None,
                 verify_mesh_devices: int = 1):
        self.state = state
        self.is_syncing = is_syncing
        self.verify_pad_block = verify_pad_block
        self.verify_device_timeout = verify_device_timeout
        self.verify_mesh_devices = verify_mesh_devices
        # not-yet-accepted source txs (chain-sync page prefill): input
        # resolution consults these before the chain state, so signature
        # checks for a whole sync page can be collected up front even
        # when a tx spends an output created earlier in the same page
        self.tx_overlay = tx_overlay or {}

    # -- address resolution ------------------------------------------------

    async def input_address(self, tx_input) -> Optional[str]:
        src = self.tx_overlay.get(tx_input.tx_hash)
        if src is not None:
            # coinbase sources included: spending a same-page miner
            # reward is the common case two blocks into a sync page
            if 0 <= tx_input.index < len(src.outputs):
                return src.outputs[tx_input.index].address
            return None
        return await self.state.resolve_output_address(tx_input.tx_hash, tx_input.index)

    async def voter_address(self, tx_input) -> Optional[str]:
        """For revoke inputs: the vote tx's FIRST input address
        (transaction_input.py:56-58, 79-82)."""
        src = self.tx_overlay.get(tx_input.tx_hash)
        if src is not None:
            if src.is_coinbase or not src.inputs:
                return None
            return await self.input_address(src.inputs[0])
        info = await self.state.get_transaction_info(tx_input.tx_hash)
        if info is None or not info["inputs_addresses"]:
            tx = await self.state.get_transaction(tx_input.tx_hash, include_pending=True)
            if tx is None or tx.is_coinbase or not tx.inputs:
                return None
            return await self.input_address(tx.inputs[0])
        return info["inputs_addresses"][0]

    # -- double spends -----------------------------------------------------

    @staticmethod
    def no_internal_double_spend(tx: Tx) -> bool:
        """No outpoint used twice within the tx (transaction.py:90-97)."""
        outpoints = [i.outpoint for i in tx.inputs]
        return len(set(outpoints)) == len(outpoints)

    async def inputs_unspent(self, tx: Tx) -> bool:
        """Every input exists in the UTXO-class table its tx type spends
        (transaction.py:99-124)."""
        table = _INPUT_TABLE.get(tx.transaction_type, "unspent_outputs")
        present = await self.state.outpoints_exist(
            [i.outpoint for i in tx.inputs], table)
        return all(present)

    async def no_pending_double_spend(self, tx: Tx) -> bool:
        """Inputs absent from the pending-spent overlay
        (transaction.py:126-133; like the reference, only this tx's
        outpoints are fetched — not the whole overlay)."""
        pending = await self.state.get_pending_spent_outpoints(
            [i.outpoint for i in tx.inputs])
        return all(i.outpoint not in pending for i in tx.inputs)

    # -- DPoS rules (each returns True when the rule does not apply) -------

    async def check_stake(self, tx: Tx) -> bool:
        """transaction.py:434-465."""
        if not any(o.output_type == OutputType.STAKE for o in tx.outputs):
            return True
        address = await self.input_address(tx.inputs[0])
        stakes = await self.state.get_stake_outputs(address)
        if stakes and not self.is_syncing:
            return False
        pending = [
            t for t in await self.state.get_pending_stake_transactions(address)
            if t.hash() != tx.hash()
        ]
        if pending:
            return False
        delegate_power = sum(
            o.amount for o in tx.outputs
            if o.output_type == OutputType.DELEGATE_VOTING_POWER)
        if delegate_power > 0:
            if delegate_power != 10 * SMALLEST:  # 10 "coins" of voting power
                return False
            if await self.state.get_delegates_all_power(address):
                return False
        else:
            if not await self.state.get_delegates_all_power(address):
                return False
        return True

    async def check_unstake(self, tx: Tx) -> bool:
        """transaction.py:467-479."""
        if not any(o.output_type == OutputType.UN_STAKE for o in tx.outputs):
            return True
        address = await self.input_address(tx.inputs[0])
        if await self.state.get_delegates_spent_votes(address) \
                and tx.hash() not in _UNSTAKE_EXCEPTION_HASHES:
            return False
        if await self.state.get_pending_vote_as_delegate_transactions(address):
            return False
        return True

    async def check_inode_register(self, tx: Tx) -> bool:
        """transaction.py:325-362."""
        if not any(o.output_type == OutputType.INODE_REGISTRATION for o in tx.outputs):
            return True
        address = await self.input_address(tx.inputs[0])
        amount = sum(o.amount for o in tx.outputs
                     if o.output_type == OutputType.INODE_REGISTRATION)
        if amount != 1000 * SMALLEST:
            return False
        if not await self.state.get_stake_outputs(address):
            return False
        if await self.state.is_inode_registered(address, check_pending_txs=True):
            return False
        if await self.state.is_validator_registered(address, check_pending_txs=True):
            return False
        if len(await self.state.get_active_inodes(check_pending_txs=True)) >= MAX_INODES:
            return False
        active = await self.state.get_active_inodes()
        if any(e["wallet"] == address for e in active):
            return False
        return True

    async def check_inode_deregister(self, tx: Tx) -> bool:
        """transaction.py:240-254."""
        if tx.transaction_type != TransactionType.INODE_DE_REGISTRATION:
            return True
        address = await self.input_address(tx.inputs[0])
        if not await self.state.get_inode_registration_outputs(address):
            return False
        active = await self.state.get_active_inodes()
        if any(e["wallet"] == address for e in active):
            return False
        return True

    async def check_validator_register(self, tx: Tx) -> bool:
        """transaction.py:364-396."""
        if tx.transaction_type != TransactionType.VALIDATOR_REGISTRATION:
            return True
        address = await self.input_address(tx.inputs[0])
        if not await self.state.get_stake_outputs(address):
            return False
        if await self.state.is_validator_registered(address, check_pending_txs=True):
            return False
        if await self.state.is_inode_registered(address, check_pending_txs=True):
            return False
        reg_amount = sum(o.amount for o in tx.outputs
                         if o.output_type == OutputType.VALIDATOR_REGISTRATION)
        if reg_amount != 100 * SMALLEST:
            return False
        power = [o for o in tx.outputs
                 if o.output_type == OutputType.VALIDATOR_VOTING_POWER]
        if len(power) != 1 or power[0].amount != 10 * SMALLEST:
            return False
        return True

    async def check_vote_as_validator(self, tx: Tx) -> bool:
        """transaction.py:256-288."""
        if tx.transaction_type != TransactionType.VOTE_AS_VALIDATOR:
            return True
        vote_range = sum(o.amount for o in tx.outputs
                         if o.output_type == OutputType.VOTE_AS_VALIDATOR)
        if vote_range > 10 * SMALLEST or vote_range <= 0:
            return False
        address = await self.input_address(tx.inputs[0])
        if await self.state.is_inode_registered(address, check_pending_txs=True):
            return False
        if not await self.state.is_validator_registered(address, check_pending_txs=True):
            return False
        recipient = ""
        for o in tx.outputs:
            if o.output_type == OutputType.VOTE_AS_VALIDATOR:
                recipient = o.address
        if not await self.state.is_inode_registered(recipient, check_pending_txs=True):
            return False
        return True

    async def check_vote_as_delegate(self, tx: Tx,
                                     verifying_add_pending: bool = False) -> bool:
        """transaction.py:290-323."""
        if tx.transaction_type != TransactionType.VOTE_AS_DELEGATE:
            return True
        vote_range = sum(o.amount for o in tx.outputs
                         if o.output_type == OutputType.VOTE_AS_DELEGATE)
        if vote_range > 10 * SMALLEST or vote_range <= 0:
            return False
        address = await self.input_address(tx.inputs[0])
        if await self.state.is_inode_registered(address, check_pending_txs=True):
            return False
        if not await self.state.get_stake_outputs(
                address, check_pending_txs=verifying_add_pending):
            return False
        recipient = ""
        for o in tx.outputs:
            if o.output_type == OutputType.VOTE_AS_DELEGATE:
                recipient = o.address
        if not await self.state.is_validator_registered(recipient, check_pending_txs=True):
            return False
        return True

    async def check_revoke_as_validator(self, tx: Tx) -> bool:
        """transaction.py:399-417."""
        if tx.transaction_type != TransactionType.REVOKE_AS_VALIDATOR:
            return True
        address = await self.voter_address(tx.inputs[0])
        if not await self.state.is_validator_registered(address, check_pending_txs=True):
            return False
        if not await self.state.get_stake_outputs(address):
            return False
        valid = [await self.state.is_revoke_valid(i.tx_hash) for i in tx.inputs]
        return any(valid)

    async def check_revoke_as_delegate(self, tx: Tx) -> bool:
        """transaction.py:419-432."""
        if tx.transaction_type != TransactionType.REVOKE_AS_DELEGATE:
            return True
        address = await self.voter_address(tx.inputs[0])
        if not await self.state.get_stake_outputs(address):
            return False
        valid = [await self.state.is_revoke_valid(i.tx_hash) for i in tx.inputs]
        return any(valid)

    # -- outputs & fees ----------------------------------------------------

    @staticmethod
    def check_outputs(tx: Tx) -> bool:
        """Non-empty, every output verifies (transaction.py:181-183)."""
        return bool(tx.outputs) and all(o.verify() for o in tx.outputs)

    async def check_fees(self, tx: Tx) -> bool:
        """fee >= 0 (transaction.py:234-236, 499-518)."""
        return await self.state.tx_fees(tx) >= 0

    # -- the full chain ----------------------------------------------------

    async def rules_ok(self, tx: Tx, check_double_spend: bool = True,
                       verifying_add_pending: bool = False) -> bool:
        """Everything except signatures, in reference order."""
        if check_double_spend and not self.no_internal_double_spend(tx):
            return False
        if check_double_spend and not await self.inputs_unspent(tx):
            return False
        for rule in (
            self.check_stake,
            self.check_unstake,
            self.check_validator_register,
            self.check_revoke_as_validator,
            self.check_revoke_as_delegate,
            self.check_inode_deregister,
            self.check_inode_register,
            self.check_vote_as_validator,
        ):
            if not await rule(tx):
                return False
        if not await self.check_vote_as_delegate(
                tx, verifying_add_pending=verifying_add_pending):
            return False
        if not self.check_outputs(tx):
            return False
        if not await self.check_fees(tx):
            return False
        return True

    async def collect_sig_checks(self, tx: Tx,
                                 digests: Optional[tuple] = None
                                 ) -> Optional[List[tuple]]:
        """Deferred signature tuples for this tx (None -> invalid).
        ``digests`` forwards a fused-prep (digest, digest_hexform) pair
        so the per-tx sha256 passes are skipped (verify/block.py)."""
        is_revoke = tx.transaction_type in (
            TransactionType.REVOKE_AS_VALIDATOR, TransactionType.REVOKE_AS_DELEGATE)
        addresses = {}
        for tx_input in tx.inputs:
            addr = (await self.voter_address(tx_input) if is_revoke
                    else await self.input_address(tx_input))
            addresses[tx_input.outpoint] = addr
        return _dedup_sig_checks(
            tx, is_revoke, lambda i: addresses.get(i.outpoint),
            digests=digests)

    async def verify(self, tx: Tx, check_double_spend: bool = True,
                     verifying_add_pending: bool = False,
                     sig_backend: str = "auto") -> bool:
        """Full single-tx verification (rules + signatures)."""
        if not await self.rules_ok(tx, check_double_spend, verifying_add_pending):
            return False
        checks = await self.collect_sig_checks(tx)
        if checks is None:
            return False
        return all(await run_sig_checks_async(
            checks, backend=sig_backend, pad_block=self.verify_pad_block,
            device_timeout=self.verify_device_timeout,
            mesh_devices=self.verify_mesh_devices))

    async def prepare_pending(self, tx: Tx) -> Optional[List[tuple]]:
        """Host-side half of add-pending verification: every rule check
        plus the pending-double-spend overlay, with the signature work
        COLLECTED but not dispatched.  The mempool intake flattens the
        returned check tuples across a whole micro-batch into one
        ``run_sig_checks_async`` call; ``None`` means the tx failed a
        host-side rule and never reaches the device."""
        if not await self.rules_ok(tx, verifying_add_pending=True):
            return None
        if not await self.no_pending_double_spend(tx):
            return None
        return await self.collect_sig_checks(tx)

    async def verify_pending(self, tx: Tx, sig_backend: str = "auto") -> bool:
        """add-pending intake check (transaction.py:481-482)."""
        checks = await self.prepare_pending(tx)
        if checks is None:
            return False
        return all(await run_sig_checks_async(
            checks, backend=sig_backend, pad_block=self.verify_pad_block,
            device_timeout=self.verify_device_timeout,
            mesh_devices=self.verify_mesh_devices))
