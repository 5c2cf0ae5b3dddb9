"""HBM-resident UTXO index: device membership + value store (ISSUE 11).

Earlier rounds kept 64-bit XOR-fold fingerprints on device as a
*prefilter* and resolved every hit through a host-side exact map — one
Python dict walk per probed outpoint, which is exactly the per-tx host
round-trip the accept path must shed to reach the PAPER.md target.
This round promotes the structure to a true resident index:

* **128-bit effective fingerprints.**  The sorted key is the historical
  64-bit XOR-fold (``fingerprint_batch`` — bit-identical to previous
  rounds); each entry additionally carries an independent 64-bit
  *check* fingerprint (``check_batch``, distinct odd multipliers per
  txid lane).  A probe matches only when both agree, so a false
  "present" needs a 128-bit collision (~2^64 birthday work even for an
  adversary minting both outputs) — the device verdict is trusted
  without consulting the host map.
* **Packed value store.**  Aligned with the keys: amount (two int32
  lanes), a 32-bit script hash (crc32 of the owning address), and the
  creation height.  Probes gather the amount lanes in the same
  dispatch, so the differential can cross-check resident amounts
  against SQL without extra traffic.
* **Windowed sorted probe.**  One ``searchsorted`` on the
  order-preserving high key lane, then an 8-slot window scan over the
  equal-run (key + check lanes compared elementwise).  int32 lanes
  throughout: without jax_enable_x64 JAX silently downcasts 64-bit
  arrays, which would truncate AFTER the host sort and hand
  searchsorted an unsorted array.  Sign-flip (``x ^ 0x8000_0000``)
  keeps uint32 order under int32 compare.
* **Shadow map, demoted.**  The exact multiset map ``fp64 ->
  [outpoints]`` is still maintained (it is the rollback/differential
  oracle and the twin resolver) but it is consulted ONLY when the
  device declares ambiguity: an equal-key run longer than the probe
  window, or a hit on a fingerprint that has ever had 64-bit twins.
  ``index.shadow_consults`` counts every consult; a collision-free
  block keeps it at zero (acceptance criterion).
* **O(delta) reorg.**  ``apply_block`` appends an undo record
  (created, spent, spent values) to a bounded log; ``rollback_block``
  replays the inverse as two sorted-slab splices — no full rebuild.
  Storage backends mirror this with per-outpoint delta add/remove in
  ``remove_blocks``.

All device work — probes, batched apply, the fused accept-path
dispatch (:func:`fused_probe`) — is issued through
``device/runtime.py``'s ``submit_call`` so the weighted fair scheduler
and degrade choke point govern it like every other kernel.
"""

from __future__ import annotations

import functools
import time
import zlib
from collections import deque
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Outpoint = Tuple[str, int]

# Odd 64-bit mixing constant (2^64 / golden ratio).  The txid prefix is
# already uniform (it IS sha256 output); the multiply spreads the output
# index so (h, 0) and (h, 1) land far apart.
_MIX = 0x9E3779B97F4A7C15
_U64 = 0xFFFFFFFFFFFFFFFF

# Independent lane multipliers for the check fingerprint (xxhash64 /
# splitmix64 odd constants).  Any fixed distinct-odd-multiplier combine
# of sha256-uniform lanes is independent enough of the XOR fold that a
# simultaneous collision in both needs genuine 128-bit birthday work.
_CHECK_MULTS = (0x9E3779B185EBCA87, 0xC2B2AE3D27D4EB4F,
                0x165667B19E3779F9, 0x27D4EB2F165667C5)
_MIX2 = 0xFF51AFD7ED558CCD

#: slots scanned past the searchsorted position; an equal-key run that
#: extends past the window flags the probe ambiguous (shadow consult)
PROBE_WINDOW = 8

_I32_MIN = np.int32(np.iinfo(np.int32).min)
_I32_MAX = np.int32(np.iinfo(np.int32).max)


def fingerprint(outpoint: Outpoint) -> int:
    """64-bit unsigned fingerprint of one outpoint: XOR-fold of the four
    u64 lanes of the (already sha256-uniform) txid, mixed with the
    output index.  Folding the WHOLE hash — not a prefix — keeps the
    fingerprint discriminating even for structured/test txids.

    Must stay bit-identical to ``fingerprint_batch`` — the class mixes
    both paths freely.
    """
    tx_hash, index = outpoint
    raw = bytes.fromhex(tx_hash)
    base = 0
    for off in range(0, 32, 8):
        base ^= int.from_bytes(raw[off:off + 8], "little")
    return (base ^ ((index + 1) * _MIX)) & _U64


def fingerprint_batch(outpoints: Sequence[Outpoint]) -> np.ndarray:
    """(N,) uint64 fingerprints in one ``np.frombuffer`` pass.

    One joined-hex decode + one frombuffer + vectorized fold/mix — no
    per-outpoint hashlib/int.from_bytes loop.
    """
    n = len(outpoints)
    if not n:
        return np.zeros(0, dtype=np.uint64)
    blob = bytes.fromhex("".join(o[0] for o in outpoints))
    lanes = np.frombuffer(blob, dtype="<u8").reshape(n, 4)
    base = np.bitwise_xor.reduce(lanes, axis=1)
    idx = np.fromiter((o[1] for o in outpoints), dtype=np.uint64, count=n)
    with np.errstate(over="ignore"):
        return base ^ ((idx + np.uint64(1)) * np.uint64(_MIX))


def check_fp(outpoint: Outpoint) -> int:
    """Scalar twin of :func:`check_batch` (tests / spot checks)."""
    tx_hash, index = outpoint
    raw = bytes.fromhex(tx_hash)
    acc = 0
    for k, off in enumerate(range(0, 32, 8)):
        lane = int.from_bytes(raw[off:off + 8], "little")
        acc ^= (lane * _CHECK_MULTS[k]) & _U64
    return (acc ^ (((index + 1) * _MIX2) & _U64)) & _U64


def check_batch(outpoints: Sequence[Outpoint]) -> np.ndarray:
    """(N,) uint64 *check* fingerprints — independent of
    :func:`fingerprint_batch`; together they form the 128-bit effective
    identity a resident probe trusts without host confirmation."""
    n = len(outpoints)
    if not n:
        return np.zeros(0, dtype=np.uint64)
    blob = bytes.fromhex("".join(o[0] for o in outpoints))
    lanes = np.frombuffer(blob, dtype="<u8").reshape(n, 4)
    idx = np.fromiter((o[1] for o in outpoints), dtype=np.uint64, count=n)
    with np.errstate(over="ignore"):
        acc = lanes[:, 0] * np.uint64(_CHECK_MULTS[0])
        for k in range(1, 4):
            acc = acc ^ (lanes[:, k] * np.uint64(_CHECK_MULTS[k]))
        return acc ^ ((idx + np.uint64(1)) * np.uint64(_MIX2))


def _lane_hi(fps: np.ndarray) -> np.ndarray:
    """High 32 bits as order-preserving int32 (sign-bit flip maps uint32
    order onto int32 order)."""
    hi = (fps >> np.uint64(32)).astype(np.uint32)
    return (hi ^ np.uint32(0x80000000)).view(np.int32)


def _lane_lo(fps: np.ndarray) -> np.ndarray:
    lo = (fps & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return (lo ^ np.uint32(0x80000000)).view(np.int32)


def _eq_lanes(fps: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Equality-only int32 lane pair of a uint64 array (no order flip —
    the check lanes are compared, never sorted)."""
    u32 = fps.view(np.uint32).reshape(-1, 2)
    return (u32[:, 0].view(np.int32).copy(),
            u32[:, 1].view(np.int32).copy())


def _pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length()) if n else 1


@functools.partial(jax.jit, static_argnames=("window",))
def _probe_kernel(keys_hi, keys_lo, chk_a, chk_b, amt_lo, amt_hi,
                  n_live, q_hi, q_lo, q_ca, q_cb, window):
    """Windowed sorted probe: searchsorted on the high key lane, then a
    ``window``-slot scan of the equal run comparing all four identity
    lanes.  Returns per query: full 128-bit hit, 64-bit key hit (the
    prefilter contract), run overflow (ambiguity), and the amount lanes
    gathered at the matched row."""
    with jax.named_scope("upow.utxo_probe"):
        cap = keys_hi.shape[0]
        pos = jnp.searchsorted(keys_hi, q_hi, side="left")
        idx = pos[:, None] + jnp.arange(window)[None, :]
        valid = idx < n_live
        idx_c = jnp.clip(idx, 0, cap - 1)
        hi_eq = (keys_hi[idx_c] == q_hi[:, None]) & valid
        key_eq = hi_eq & (keys_lo[idx_c] == q_lo[:, None])
        full_eq = key_eq & (chk_a[idx_c] == q_ca[:, None]) \
            & (chk_b[idx_c] == q_cb[:, None])
        hit = full_eq.any(axis=1)
        key_hit = key_eq.any(axis=1)
        overflow = hi_eq[:, window - 1]
        row = jnp.clip(pos + jnp.argmax(full_eq, axis=1), 0, cap - 1)
        return hit, key_hit, overflow, amt_lo[row], amt_hi[row]


class DeviceUtxoIndex:
    """HBM-resident sorted-fingerprint UTXO index, one per UTXO-class
    table: 128-bit effective identity, packed value store, bounded undo
    log, shadow map consulted only on declared ambiguity."""

    #: undo records retained for O(delta) reorg rollback; a reorg deeper
    #: than this falls back to the storage layer's rebuild
    UNDO_DEPTH = 64

    def __init__(self, outpoints: Iterable[Outpoint] = (),
                 values: Optional[Sequence[tuple]] = None):
        # shadow map: fp64 -> live outpoints with that fingerprint.  A
        # list, not a set: duplicates mirror the old multiset semantics
        # (add twice -> remove twice), and twins (distinct outpoints,
        # one fp64) stay individually tracked so spending one never
        # makes the survivor report absent — the one error class the
        # index must never produce.
        self._shadow: Dict[int, List[Outpoint]] = {}
        # fingerprints that EVER held >=2 live outpoints: any hit on one
        # routes to the shadow map (sticky — a surviving twin's row may
        # carry its spent sibling's check lanes after a k-th-duplicate
        # removal, so the ambiguity outlives the second entry)
        self._twin_fps: set = set()
        self._twins_arr: Optional[np.ndarray] = None
        self._host_keys = np.zeros(0, dtype=np.uint64)   # sorted fp64
        self._host_chk = np.zeros(0, dtype=np.uint64)    # aligned check
        self._host_amount = np.zeros(0, dtype=np.int64)  # aligned values
        self._host_script = np.zeros(0, dtype=np.uint32)
        self._host_height = np.zeros(0, dtype=np.uint32)
        self._dirty = True
        self._dev: Optional[tuple] = None                # device arrays
        self._undo: deque = deque(maxlen=self.UNDO_DEPTH)
        self._probes = 0
        self._shadow_consults = 0
        ops = [tuple(o) for o in outpoints]
        if ops:
            self.add(ops, values)

    def __len__(self):
        return int(self._host_keys.shape[0])

    # ------------------------------------------------------------ values --

    @staticmethod
    def _norm_values(n: int, values: Optional[Sequence[tuple]]):
        """(amount int64, script uint32, height uint32) arrays from the
        optional per-outpoint (amount, address|script_hash, height)
        tuples; zeros where unknown (membership never depends on them)."""
        amt = np.zeros(n, dtype=np.int64)
        script = np.zeros(n, dtype=np.uint32)
        height = np.zeros(n, dtype=np.uint32)
        if values is not None:
            for i, v in enumerate(values):
                if v is None:
                    continue
                a, s, h = (tuple(v) + (0, 0, 0))[:3]
                amt[i] = int(a or 0)
                if isinstance(s, str):
                    script[i] = zlib.crc32(s.encode())
                elif s:
                    script[i] = int(s) & 0xFFFFFFFF
                height[i] = int(h or 0) & 0xFFFFFFFF
        return amt, script, height

    def _capture_values(self, outpoints: Sequence[Outpoint]) -> List[tuple]:
        """Value rows for live outpoints (zeros when absent) — snapshot
        taken before a spend so the undo log can restore them."""
        out: List[tuple] = []
        if not outpoints:
            return out
        fps = fingerprint_batch(outpoints)
        chks = check_batch(outpoints)
        lo = np.searchsorted(self._host_keys, fps, side="left")
        hi = np.searchsorted(self._host_keys, fps, side="right")
        for i in range(len(outpoints)):
            row = None
            for j in range(int(lo[i]), int(hi[i])):
                if self._host_chk[j] == chks[i]:
                    row = j
                    break
            if row is None:
                out.append((0, 0, 0))
            else:
                out.append((int(self._host_amount[row]),
                            int(self._host_script[row]),
                            int(self._host_height[row])))
        return out

    # ------------------------------------------------------------ updates --

    def add(self, outpoints: Iterable[Outpoint],
            values: Optional[Sequence[tuple]] = None) -> None:
        ops = [tuple(o) for o in outpoints]
        if not ops:
            return
        fps = fingerprint_batch(ops)
        chks = check_batch(ops)
        for o, fp in zip(ops, fps.tolist()):
            bucket = self._shadow.setdefault(fp, [])
            bucket.append(o)
            if len(bucket) >= 2 and fp not in self._twin_fps:
                self._twin_fps.add(fp)
                self._twins_arr = None
        amt, script, height = self._norm_values(len(ops), values)
        # incremental sorted insert: sort only the (small) slab, then
        # splice it into place — no full re-sort of the whole key set
        order = np.argsort(fps, kind="stable")
        slab = fps[order]
        pos = np.searchsorted(self._host_keys, slab)
        self._host_keys = np.insert(self._host_keys, pos, slab)
        self._host_chk = np.insert(self._host_chk, pos, chks[order])
        self._host_amount = np.insert(self._host_amount, pos, amt[order])
        self._host_script = np.insert(self._host_script, pos, script[order])
        self._host_height = np.insert(self._host_height, pos, height[order])
        self._dirty = True

    def remove(self, outpoints: Iterable[Outpoint]) -> None:
        ops = [tuple(o) for o in outpoints]
        if not ops:
            return
        doomed: List[Tuple[int, int]] = []  # (fp, chk) of live removals
        fps = fingerprint_batch(ops)
        chks = check_batch(ops)
        for o, fp, chk in zip(ops, fps.tolist(), chks.tolist()):
            bucket = self._shadow.get(fp)
            if bucket is None or o not in bucket:
                # absent entries are a no-op, matching the SQL DELETE
                # (e.g. replaying a log whose spend references a
                # never-created output must report a MISMATCH, not crash)
                continue
            bucket.remove(o)
            if not bucket:
                del self._shadow[fp]
            doomed.append((fp, chk))
        if not doomed:
            return
        rem_fps = np.array([d[0] for d in doomed], dtype=np.uint64)
        lo = np.searchsorted(self._host_keys, rem_fps, side="left")
        hi = np.searchsorted(self._host_keys, rem_fps, side="right")
        marked: set = set()
        for (fp, chk), l, h in zip(doomed, lo.tolist(), hi.tolist()):
            # within the equal-fp run, delete the row whose check lanes
            # match (keeps twins' value rows individually correct); the
            # k-th-duplicate fallback covers true 128-bit twins, whose
            # rows are indistinguishable anyway
            pick = None
            for j in range(l, h):
                if j not in marked and self._host_chk[j] == chk:
                    pick = j
                    break
            if pick is None:
                for j in range(l, h):
                    if j not in marked:
                        pick = j
                        break
            if pick is not None:
                marked.add(pick)
        if not marked:
            return
        gone = np.fromiter(marked, dtype=np.int64, count=len(marked))
        self._host_keys = np.delete(self._host_keys, gone)
        self._host_chk = np.delete(self._host_chk, gone)
        self._host_amount = np.delete(self._host_amount, gone)
        self._host_script = np.delete(self._host_script, gone)
        self._host_height = np.delete(self._host_height, gone)
        self._dirty = True

    def apply_block(self, created: Sequence[Outpoint],
                    spent: Sequence[Outpoint],
                    created_values: Optional[Sequence[tuple]] = None,
                    materialize: bool = False) -> None:
        """Batched spend/create application for one accepted block,
        recorded in the undo log for :meth:`rollback_block`.
        ``materialize=True`` re-uploads the device arrays through the
        runtime now (one ``utxo_apply`` dispatch) instead of lazily on
        the next probe."""
        spent = [tuple(o) for o in spent]
        created = [tuple(o) for o in created]
        spent_values = self._capture_values(spent) if spent else []
        if spent:
            self.remove(spent)
        if created:
            self.add(created, created_values)
        self._undo.append((created, spent, spent_values))
        if materialize and (created or spent):
            self.materialize()

    def rollback_block(self) -> bool:
        """O(delta) inverse of the most recent :meth:`apply_block`:
        two sorted-slab splices, no rebuild.  False when the undo log
        is exhausted (caller falls back to a rebuild)."""
        if not self._undo:
            return False
        created, spent, spent_values = self._undo.pop()
        if created:
            self.remove(created)
        if spent:
            self.add(spent, spent_values)
        return True

    def undo_depth(self) -> int:
        return len(self._undo)

    # ------------------------------------------------------ device state --

    def _device_state(self) -> tuple:
        """(keys_hi, keys_lo, chk_a, chk_b, amt_lo, amt_hi, n_live) jnp
        arrays at power-of-two capacity.  Must only run on the runtime's
        drainer thread (inside a submitted call)."""
        if self._dirty or self._dev is None:
            n = len(self._host_keys)
            cap = _pow2(n)
            pad = cap - n

            def _padded(lane: np.ndarray, fill) -> np.ndarray:
                return np.concatenate(
                    [lane, np.full(pad, fill, dtype=np.int32)])

            chk_a, chk_b = _eq_lanes(self._host_chk)
            amt_u = self._host_amount.view(np.uint64)
            amt_lo = (amt_u & np.uint64(0xFFFFFFFF)).astype(
                np.uint32).view(np.int32)
            amt_hi = (amt_u >> np.uint64(32)).astype(
                np.uint32).view(np.int32)
            self._dev = tuple(jnp.asarray(_padded(lane, fill)) for lane, fill in (
                (_lane_hi(self._host_keys), _I32_MAX),
                (_lane_lo(self._host_keys), _I32_MAX),
                (chk_a, 0), (chk_b, 0),
                (amt_lo, 0), (amt_hi, 0),
            )) + (np.int32(n),)
            self._dirty = False
        return self._dev

    def materialize(self) -> None:
        """Upload the current host state to the device through the
        runtime (kernel ``utxo_apply``) — the batched spend/create
        transfer the accept path schedules after each block."""
        from ..device.runtime import get_runtime
        from ..telemetry import device as ktel

        n = len(self._host_keys)

        def _upload():
            t0 = time.perf_counter()
            dev = self._device_state()
            jax.block_until_ready(dev[0])
            ktel.record_batch("utxo_apply", real=n,
                              padded=int(dev[0].shape[0]),
                              seconds=time.perf_counter() - t0,
                              compile_key=int(dev[0].shape[0]))
            return True

        get_runtime().submit_call(_upload, kernel="utxo_apply",
                                  source="index").result()

    def resident_bytes(self) -> int:
        """Device residency: six int32 lanes at padded capacity."""
        return 6 * 4 * _pow2(len(self._host_keys))

    def stats(self) -> dict:
        return {
            "entries": len(self),
            "capacity": _pow2(len(self._host_keys)),
            "resident_bytes": self.resident_bytes(),
            "probes": self._probes,
            "shadow_consults": self._shadow_consults,
            "twin_fingerprints": len(self._twin_fps),
            "undo_depth": len(self._undo),
        }

    # ------------------------------------------------------------ queries --

    def _twins_sorted(self) -> np.ndarray:
        if self._twins_arr is None:
            self._twins_arr = np.array(
                sorted(self._twin_fps), dtype=np.uint64)
        return self._twins_arr

    def _probe_eval(self, ops: Sequence[Outpoint], fps: np.ndarray,
                    chks: np.ndarray) -> tuple:
        """Run one probe kernel + host postprocess.  Must run on the
        runtime drainer thread (inside a submitted call).  Returns
        (present bool[N], maybe bool[N], amounts int64[N],
        shadow_consults)."""
        from ..telemetry import device as ktel

        n = len(ops)
        qn = _pow2(n)
        t0 = time.perf_counter()
        dev = self._device_state()

        def _padq(lane: np.ndarray, fill) -> np.ndarray:
            return np.concatenate(
                [lane, np.full(qn - n, fill, dtype=np.int32)])

        q_ca, q_cb = _eq_lanes(chks)
        hit, key_hit, overflow, amt_lo, amt_hi = _probe_kernel(
            *dev[:6], dev[6],
            jnp.asarray(_padq(_lane_hi(fps), _I32_MIN)),
            jnp.asarray(_padq(_lane_lo(fps), _I32_MIN)),
            jnp.asarray(_padq(q_ca, 0)), jnp.asarray(_padq(q_cb, 0)),
            window=PROBE_WINDOW)
        hit = np.asarray(hit)[:n]
        key_hit = np.asarray(key_hit)[:n]
        overflow = np.asarray(overflow)[:n]
        amt_lo = np.asarray(amt_lo)[:n]
        amt_hi = np.asarray(amt_hi)[:n]
        dt = time.perf_counter() - t0

        ambiguous = overflow.copy()
        twins = self._twins_sorted()
        if twins.size:
            ambiguous |= (key_hit & np.isin(fps, twins))
        present = hit & ~ambiguous
        consults = 0
        for i in np.nonzero(ambiguous)[0]:
            bucket = self._shadow.get(int(fps[i]))
            present[i] = bucket is not None and tuple(ops[i]) in bucket
            consults += 1
        amounts = ((amt_hi.view(np.uint32).astype(np.uint64)
                    << np.uint64(32))
                   | amt_lo.view(np.uint32).astype(np.uint64)
                   ).view(np.int64)
        amounts = np.where(present & ~ambiguous, amounts, 0)
        maybe = key_hit | overflow
        self._probes += 1
        self._shadow_consults += consults
        ktel.record_batch("utxo_probe", real=n, padded=qn, seconds=dt,
                          compile_key=(int(dev[0].shape[0]), qn))
        ktel.record_index_probe(n, consults, int(ambiguous.sum()))
        return present, maybe, amounts, consults

    def _probe(self, outpoints: Sequence[Outpoint]) -> tuple:
        """One standalone probe dispatch through the runtime."""
        ops = [tuple(o) for o in outpoints]
        fps = fingerprint_batch(ops)
        chks = check_batch(ops)
        from ..device.runtime import get_runtime

        return get_runtime().submit_call(
            lambda: self._probe_eval(ops, fps, chks),
            kernel="utxo_probe", source="index").result()

    def maybe_contains_batch(self, outpoints: Sequence[Outpoint]) -> np.ndarray:
        """(N,) bool prefilter contract: False is definitive absence;
        True means a fingerprint hit (use ``contains_batch`` for the
        exact answer)."""
        if not outpoints:
            return np.zeros(0, dtype=bool)
        return self._probe(outpoints)[1]

    def contains_batch(self, outpoints: Sequence[Outpoint]) -> np.ndarray:
        """(N,) bool EXACT membership in one device dispatch.

        The 128-bit lane compare answers directly; the shadow map is
        consulted only for probes the kernel itself declares ambiguous
        (run overflow or a known-twin fingerprint)."""
        if not outpoints:
            return np.zeros(0, dtype=bool)
        return self._probe(outpoints)[0]

    def lookup_batch(self, outpoints: Sequence[Outpoint]) -> tuple:
        """(present bool[N], amounts int64[N]) — membership plus the
        resident value store's amount column, one dispatch."""
        if not outpoints:
            return np.zeros(0, dtype=bool), np.zeros(0, dtype=np.int64)
        present, _maybe, amounts, _c = self._probe(outpoints)
        return present, amounts

    def shadow_contains_batch(self, outpoints: Sequence[Outpoint]) -> np.ndarray:
        """(N,) bool membership answered PURELY by the host shadow map —
        the byte-identity differential's oracle; never dispatches."""
        out = np.zeros(len(outpoints), dtype=bool)
        if not len(outpoints):
            return out
        ops = [tuple(o) for o in outpoints]
        for i, (o, fp) in enumerate(
                zip(ops, fingerprint_batch(ops).tolist())):
            bucket = self._shadow.get(fp)
            out[i] = bucket is not None and o in bucket
        return out

    def missing(self, outpoints: Sequence[Outpoint]) -> List[Outpoint]:
        """Outpoints that are definitely absent (exact)."""
        present = self.contains_batch(outpoints)
        return [o for o, m in zip(outpoints, present) if not m]


def fused_probe(parts: Sequence[Tuple[DeviceUtxoIndex, Sequence[Outpoint]]],
                extra_fn: Optional[Callable] = None,
                source: str = "block") -> tuple:
    """ONE runtime dispatch covering every (index, outpoints) part —
    the accept path's fused membership probe.  ``extra_fn`` (e.g. the
    device txid batch for the same micro-batch) runs inside the same
    submitted call, so digest prep and outpoint probing share a single
    scheduler slot instead of racing each other through the queue.

    Returns ``([(present, amounts, shadow_consults), ...], extra)``
    with parts in input order.
    """
    staged = []
    for index, outpoints in parts:
        ops = [tuple(o) for o in outpoints]
        staged.append((index, ops, fingerprint_batch(ops), check_batch(ops)))

    def _run():
        results = []
        for index, ops, fps, chks in staged:
            if not ops:
                results.append((np.zeros(0, dtype=bool),
                                np.zeros(0, dtype=np.int64), 0))
                continue
            present, _maybe, amounts, consults = index._probe_eval(
                ops, fps, chks)
            results.append((present, amounts, consults))
        extra = extra_fn() if extra_fn is not None else None
        return results, extra

    from ..device.runtime import get_runtime

    return get_runtime().submit_call(
        _run, kernel="accept_fused", source=source).result()
