"""Batched 256-bit prime-field arithmetic for TPU: 21×13-bit limbs, lazy.

Design (SURVEY.md §2.2 — the role fastecdsa's C/GMP extension plays in the
reference, transaction_input.py:100-109):

* **13-bit limbs in int32 lanes** — a limb product is < 2²⁶ and a 21-term
  accumulation stays < 2³¹, so schoolbook multiply + Montgomery reduction
  run in plain int32 VPU ops with no u64 widening.
* **Non-negative lazy representation with static bounds** — an element is
  a (21, N) int32 array with limbs in [0, 2¹³] plus a *Python-side* upper
  bound on the represented value, tracked exactly while tracing (the
  fiat-crypto discipline).  Values stay congruent mod p but unreduced;
  adds are one vector add + one carry sweep; subtraction is
  ``a + (K·p − b)`` with the multiple K chosen statically from b's bound,
  so limbs never go negative and carry sweeps can never lose a top carry
  (every bound is asserted ≪ 2²⁷³ at trace time).
* **One guard limb** (21 limbs = 273 bits for a 256-bit field) — gives
  Montgomery products the slack that makes the lazy bounds self-stable:
  with R = 2²⁷³, inputs bounded by ~2²⁶⁴ still return below 2p + ε.
* **Array layout (L, N)** — limb index on the sublane axis, batch on the
  lane axis; every op is a handful of large fused VPU instructions, which
  keeps both the XLA graph small (fast compiles) and the TPU busy.  The
  only sequential pieces are the per-site borrow chain inside ``sub`` and
  the one exact reduction in :func:`canon` at the end of a verification.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

LIMB_BITS = 13
NUM_LIMBS = 21
LIMB_MASK = (1 << LIMB_BITS) - 1
R_BITS = LIMB_BITS * NUM_LIMBS  # Montgomery R = 2^273

# Hard cap on any element's value bound: far enough below 2^273 that a
# carry sweep's top limb is always < 2^13 (no dropped carries), with room
# for the K·p subtraction offsets.
_BOUND_CAP = 1 << 270

# a product is one jaxpr for each operand shape and field (a FieldSpec is
# a tuple of integers): traced once, bound wherever a program multiplies
_jit_by_field = functools.partial(jax.jit, static_argnames=("fs",))


class FieldSpec(NamedTuple):
    """Host-side constants for one prime field."""

    p: int
    p_limbs: tuple             # 21 Python-int limbs (scalar constants only:
                               # non-scalar closures are illegal in Pallas)
    pinv: int                  # -p^-1 mod 2^13
    r_mod_p: int               # R mod p  (Montgomery form of 1)
    r2_mod_p: int              # R^2 mod p


def make_field(p: int) -> FieldSpec:
    return FieldSpec(
        p=p,
        p_limbs=tuple(int(x) for x in int_to_limbs(p)),
        pinv=(-pow(p, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS),
        r_mod_p=(1 << R_BITS) % p,
        r2_mod_p=pow(1 << R_BITS, 2, p),
    )


# --- host conversions -----------------------------------------------------

def int_to_limbs(x: int) -> np.ndarray:
    out = np.zeros(NUM_LIMBS, dtype=np.int32)
    for i in range(NUM_LIMBS):
        out[i] = x & LIMB_MASK
        x >>= LIMB_BITS
    assert x == 0, "value exceeds 273 bits"
    return out


def ints_to_limbs(xs) -> np.ndarray:
    """list of ints -> (21, N) int32 batch.

    Vectorized: per-int ``to_bytes`` (C speed) then one numpy unpack —
    the per-limb Python loop was the host-side bottleneck of an 8k-sig
    batch verify (~0.7 s/call before, ~10 ms now)."""
    n = len(xs)
    if n == 0:
        return np.zeros((NUM_LIMBS, 0), dtype=np.int32)
    raw = b"".join(x.to_bytes(35, "little") for x in xs)  # 273 bits < 280
    assert max(xs) < (1 << R_BITS), "value exceeds 273 bits"
    bits = np.unpackbits(
        np.frombuffer(raw, dtype=np.uint8).reshape(n, 35),
        axis=1, bitorder="little")[:, :NUM_LIMBS * LIMB_BITS]
    weights = (1 << np.arange(LIMB_BITS, dtype=np.int32))
    out = bits.reshape(n, NUM_LIMBS, LIMB_BITS).astype(np.int32) @ weights
    return np.ascontiguousarray(out.T)


def limbs_to_int(limbs) -> int:
    # Host-side exact reassembly of a 256-bit value from limbs; int64
    # never reaches a traced computation.
    limbs = np.asarray(limbs, dtype=np.int64)  # upowlint: disable=DT001
    return sum(int(limbs[i]) << (LIMB_BITS * i) for i in range(limbs.shape[0]))


def limbs_to_ints(limbs) -> list:
    limbs = np.asarray(limbs)
    return [limbs_to_int(limbs[:, j]) for j in range(limbs.shape[1])]


def to_mont(x: int, fs: FieldSpec) -> int:
    return x * (1 << R_BITS) % fs.p


# --- the element type -----------------------------------------------------

@dataclass(frozen=True)
class FE:
    """Field-element batch: (21, N) int32 limbs + static value bound.

    ``bound`` is exclusive, tracked in Python while tracing — it never
    touches the device.  Stacked-layout limbs are in [0, 2^13 + 22]
    (the residue after mont_mul's two one-hop sweeps: 8191 + a round-2
    carry of at most 22); limb-list (FL) limbs are in [0, 2^13 − 1]
    (:func:`_l_sweep` is a full ripple).  Values are >= 0 and < bound.
    21-term product accumulations stay < 2^31 at either cap
    (21 · 8213² ≈ 1.42e9).
    """

    arr: jnp.ndarray
    bound: int

    def __post_init__(self):
        assert self.bound <= _BOUND_CAP, (
            f"fp bound overflow: {self.bound.bit_length()} bits — "
            "missing a mont_mul in the chain?")


def wrap(arr, bound: int) -> FE:
    return FE(arr, bound)


def from_ints(xs, fs: FieldSpec) -> FE:
    """Host canonical ints (< p) -> device FE."""
    assert all(0 <= x < fs.p for x in xs)
    return FE(jnp.asarray(ints_to_limbs(xs)), fs.p)


def const(x: int, n: int, bound: int) -> FE:
    """Broadcast one host int (< bound) to a (21, N) batch.

    Built from scalar fills (not a closed-over (21, 1) array) so the same
    code is legal inside a Pallas kernel."""
    limbs = int_to_limbs(x)
    return FE(
        jnp.stack([jnp.full((n,), int(l), dtype=jnp.int32) for l in limbs]),
        bound,
    )


# --- device ops -----------------------------------------------------------

def _sweep(t, rounds: int):
    """Carry sweep: re-digitize non-negative limbs toward [0, 2^13].

    Each round keeps the low 13 bits and moves the carry one limb up.
    Safe to drop the top-limb carry: all values are non-negative and
    bounded < 2^270 ≪ 2^273, so that carry is provably zero.
    """
    for _ in range(rounds):
        c = t >> LIMB_BITS
        t = (t & LIMB_MASK) + jnp.concatenate(
            [jnp.zeros_like(c[:1]), c[:-1]], axis=0
        )
    return t


def add(a: FE, b: FE) -> FE:
    return FE(_sweep(a.arr + b.arr, 1), a.bound + b.bound)


def _pow2_p_multiple(bound: int, p: int) -> int:
    """Smallest K = 2^k · p with K >= bound (so K − b is non-negative)."""
    k = 1
    while k * p < bound:
        k <<= 1
    return k * p


def sub(a: FE, b: FE, fs: FieldSpec) -> FE:
    """a − b computed as a + (K·p − b), K statically chosen from b.bound."""
    K = _pow2_p_multiple(b.bound, fs.p)
    k_limbs = int_to_limbs(K)
    # exact borrow chain for K − b (non-negative by construction of K)
    limbs = []
    c = jnp.zeros_like(b.arr[0])
    for i in range(NUM_LIMBS):
        v = int(k_limbs[i]) - b.arr[i] + c
        limbs.append(v & LIMB_MASK)
        c = v >> LIMB_BITS
    neg_b = jnp.stack(limbs, axis=0)
    return FE(_sweep(a.arr + neg_b, 1), a.bound + K)


def _shift_add(t, x, off: int):
    """t (2L, N) + x ((rows), N) placed at static row offset ``off``.

    Built from a concatenate of zero pads instead of ``t.at[...].add`` —
    indexed-add lowers to scatter-add, which has no Pallas TPU lowering;
    a static-offset concatenate lowers on both XLA and Pallas TPU.
    """
    rows = x.shape[0]
    n = t.shape[1]
    parts = []
    if off:
        parts.append(jnp.zeros((off, n), dtype=jnp.int32))
    parts.append(x)
    top = t.shape[0] - off - rows
    if top:
        parts.append(jnp.zeros((top, n), dtype=jnp.int32))
    return t + jnp.concatenate(parts, axis=0)


@_jit_by_field
def _mont_mul_arr(a, b, *, fs: FieldSpec):
    """The stacked product on bare (21, N) arrays: one jaxpr for each
    batch shape and field, bound by every product of the programs that
    call it (the device scalar prep makes ~45 of them in two fields)."""
    L = NUM_LIMBS
    n = a.shape[1]
    t = jnp.zeros((2 * L, n), dtype=jnp.int32)
    for i in range(L):
        t = _shift_add(t, a[i] * b, i)
    # sweep counts: pre 1 one-hop round (rows ≤ 2^13 + 2^17.4; the
    # reduction-round budget in _mont_reduce_rows' proof absorbs it);
    # post 2 one-hop rounds (limbs ≤ 2^13 + 22 — see the FE docstring)
    t = _sweep(t, 1)
    # Montgomery rounds: zero the bottom L limbs; the single-limb carry per
    # round keeps m exact (t[i] ≡ value/b^i mod b at round i).  p's limbs
    # enter as scalar constants (Pallas-legal; see FieldSpec.p_limbs).
    for i in range(L):
        m = (t[i] * fs.pinv) & LIMB_MASK
        mp = jnp.stack([m * pl for pl in fs.p_limbs])
        t = _shift_add(t, mp, i)
        t = _shift_add(t, (t[i] >> LIMB_BITS)[None], i + 1)
    return _sweep(t[L:], 2)


def mont_mul(a: FE, b: FE, fs: FieldSpec) -> FE:
    """Montgomery product a·b·R⁻¹ mod p; bound resets to ~2p for sane inputs."""
    return FE(_mont_mul_arr(a.arr, b.arr, fs=fs),
              a.bound * b.bound // (1 << R_BITS) + 2 * fs.p)


# --- limb-list variant (Pallas kernel layout) ------------------------------
# Same arithmetic, but an element is a Python TUPLE of 21 per-limb arrays
# (each typically an (8, 128) int32 tile = 1024 batch lanes).  Limb shifts
# become Python indexing — zero data movement — where the stacked (L, N)
# layout pays a concatenate per shifted add.  This is the layout the
# VMEM-resident ladder kernel runs in; bounds are tracked identically.


@dataclass(frozen=True)
class FL:
    """Field-element batch as a limb tuple + static value bound."""

    limbs: tuple  # length NUM_LIMBS, arrays of identical shape
    bound: int

    def __post_init__(self):
        assert self.bound <= _BOUND_CAP, (
            f"fp bound overflow: {self.bound.bit_length()} bits")


def _xp(*arrs):
    """numpy when every input is a host numpy array (eager differential
    tests run the limb-list programs at C speed), jax otherwise (tracers,
    device arrays, Pallas ref reads).  Most limb ops are dunder-dispatched
    and need no shim — this covers the explicit ``where``/``zeros`` calls."""
    return np if all(isinstance(a, np.ndarray) for a in arrs) else jnp


def l_full(x: int, like, bound: int) -> FL:
    """Broadcast a host int against a sample limb array, matching its
    array namespace (see :func:`_xp`)."""
    xp = _xp(like)
    limbs = int_to_limbs(x)
    return FL(tuple(xp.full(like.shape, int(l), dtype=xp.int32)
                    for l in limbs), bound)


def l_wrap(limbs, bound: int) -> FL:
    return FL(tuple(limbs), bound)


def _l_sweep(t: list, rounds: int) -> list:
    """In-place-style carry sweep over a limb list (top carry provably 0)."""
    t = list(t)
    for _ in range(rounds):
        carry = None
        for i in range(len(t)):
            v = t[i] if carry is None else t[i] + carry
            carry = v >> LIMB_BITS
            t[i] = v & LIMB_MASK
    return t


def l_add(a: FL, b: FL) -> FL:
    t = [x + y for x, y in zip(a.limbs, b.limbs)]
    return FL(tuple(_l_sweep(t, 1)), a.bound + b.bound)


def l_sub(a: FL, b: FL, fs: FieldSpec) -> FL:
    K = _pow2_p_multiple(b.bound, fs.p)
    k_limbs = int_to_limbs(K)
    limbs = []
    c = None
    for i in range(NUM_LIMBS):
        v = int(k_limbs[i]) - b.limbs[i] + (0 if c is None else c)
        limbs.append(v & LIMB_MASK)
        c = v >> LIMB_BITS
    t = [x + y for x, y in zip(a.limbs, limbs)]
    return FL(tuple(_l_sweep(t, 1)), a.bound + K)


def _traced_once(jitted):
    """Over a jitted ``fn(*limb tuples, fs=...)``, as the ladder calls
    it: numpy limbs run ``fn`` itself (the eager reference side of the
    differential tests), traced limbs bind its jaxpr, which jit traces
    once for each limb shape and field and every later call binds again.
    A Pallas kernel body takes the bound jaxpr as it takes any other
    (Mosaic's lowering inlines ``pjit``), so the arithmetic is the same
    operations in the same order either way; what changes is that Python
    walks the 21 × 21 loops once a program and not once a product
    (PERF.md section 6, PR 46)."""
    fn = jitted.__wrapped__

    @functools.wraps(jitted)
    def call(*limbs, fs):
        flat = [x for t in limbs for x in t]
        return (fn if _xp(*flat) is np else jitted)(*limbs, fs=fs)

    return call


@_traced_once
@_jit_by_field
def _mont_reduce_rows(rows, *, fs: FieldSpec) -> tuple:
    """Shared tail of the limb-list Montgomery products: sweep the
    double-width accumulator (``rows``: the 2L − 1 product rows), run the
    21 reduction rounds, sweep the top half.

    Sweep-count proof (int32 overflow is the only constraint — m's
    exactness needs just "every contribution into row i lands before
    round i", which product accumulation + the single round-carry chain
    guarantee at any sweep count).  Unlike the stacked :func:`_sweep`
    (one carry hop per round), :func:`_l_sweep` is a full sequential
    ripple — ONE round leaves every limb ≤ 2¹³ − 1:

    * pre-sweep 1: raw rows ≤ 21·2²⁶ ≈ 2³⁰·⁴ — one ripple normalizes.
      Each reduction round then adds ≤ 21 m·p products (< 2²⁶ each)
      plus one carry (< 2¹⁸) to a row — worst row value
      2¹³ + 21·2²⁶ + 2¹⁸ < 2³⁰·⁵ < 2³¹.  (A formula accumulating more
      than NUM_LIMBS products per row would break this — re-derive
      before changing the multiply structure.)
    * post-sweep 1: the output rows (≤ 2³⁰·⁵) ripple back to ≤ 2¹³ − 1
      in one round, restoring the canonical limb range.
    """
    L = NUM_LIMBS
    t = list(rows) + [_xp(rows[0]).zeros_like(rows[0])]
    t = _l_sweep(t, 1)
    for i in range(L):
        m = (t[i] * fs.pinv) & LIMB_MASK
        for j in range(L):
            if fs.p_limbs[j]:  # nine of P-256's 21 limbs are zero
                t[i + j] = t[i + j] + m * fs.p_limbs[j]
        t[i + 1] = t[i + 1] + (t[i] >> LIMB_BITS)
    return tuple(_l_sweep(t[L:], 1))


@_traced_once
@_jit_by_field
def _mont_mul_limbs(a, b, *, fs: FieldSpec) -> tuple:
    """The anti-diagonal accumulation is Python indexing
    (t[i+j] += a_i·b_j) — no concatenates, every MAC one full-tile VPU
    op."""
    L = NUM_LIMBS
    t = [None] * (2 * L - 1)
    for i in range(L):
        for j in range(L):
            p_ij = a[i] * b[j]
            k = i + j
            t[k] = p_ij if t[k] is None else t[k] + p_ij
    return _mont_reduce_rows(tuple(t), fs=fs)


@_traced_once
@_jit_by_field
def _mont_sqr_limbs(a, *, fs: FieldSpec) -> tuple:
    """The schoolbook product's symmetry halves the cross-term MACs
    (t[i+j] gets 2·aᵢaⱼ once instead of aᵢaⱼ twice; the factor 2 is
    applied once per row after accumulation).

    Bound safety: a row collects ≤10 doubled cross products (< 2²⁷ each)
    plus one square (< 2²⁶) — under 2³¹ in int32, same margin as
    :func:`_mont_mul_limbs`'s 21-term accumulation."""
    L = NUM_LIMBS
    cross = [None] * (2 * L - 1)  # Σ_{i<j} a_i·a_j per row (to be doubled)
    for i in range(L):
        for j in range(i + 1, L):
            k = i + j
            p_ij = a[i] * a[j]
            cross[k] = p_ij if cross[k] is None else cross[k] + p_ij
    t = [None if c is None else c + c for c in cross]
    for i in range(L):  # diagonal squares
        k = 2 * i
        sq = a[i] * a[i]
        t[k] = sq if t[k] is None else t[k] + sq
    return _mont_reduce_rows(tuple(t), fs=fs)


def l_mont_mul(a: FL, b: FL, fs: FieldSpec) -> FL:
    """Montgomery product a·b·R⁻¹ mod p in limb-list form."""
    return FL(_mont_mul_limbs(a.limbs, b.limbs, fs=fs),
              a.bound * b.bound // (1 << R_BITS) + 2 * fs.p)


def l_mont_sqr(a: FL, fs: FieldSpec) -> FL:
    """Montgomery square: :func:`l_mont_mul` of a with itself, cheaper."""
    return FL(_mont_sqr_limbs(a.limbs, fs=fs),
              a.bound * a.bound // (1 << R_BITS) + 2 * fs.p)


def l_canon(a: FL, fs: FieldSpec) -> list:
    limbs = []
    c = None
    for i in range(NUM_LIMBS):
        v = a.limbs[i] if c is None else a.limbs[i] + c
        limbs.append(v & LIMB_MASK)
        c = v >> LIMB_BITS
    k = 1
    while k * fs.p < a.bound:
        k <<= 1
    while k >= 1:
        limbs = _l_cond_sub(limbs, k * fs.p)
        k //= 2
    return limbs


def _l_cond_sub(t: list, m: int) -> list:
    mc = int_to_limbs(m)
    limbs = []
    c = None
    for i in range(NUM_LIMBS):
        v = t[i] - int(mc[i]) + (0 if c is None else c)
        limbs.append(v & LIMB_MASK)
        c = v >> LIMB_BITS
    ge = c == 0
    xp = _xp(*t)
    return [xp.where(ge, d, orig) for d, orig in zip(limbs, t)]


def l_select(cond, a: FL, b: FL) -> FL:
    """cond ? a : b per lane; ``cond`` is a bool array of the limb shape."""
    xp = _xp(*a.limbs, *b.limbs)
    return FL(tuple(xp.where(cond, x, y) for x, y in zip(a.limbs, b.limbs)),
              max(a.bound, b.bound))


def l_is_zero_mod_p(a: FL, fs: FieldSpec):
    limbs = l_canon(a, fs)
    z = limbs[0] == 0
    for i in range(1, NUM_LIMBS):
        z = z & (limbs[i] == 0)
    return z


def canon(a: FE, fs: FieldSpec):
    """Exact canonical reduction to [0, p) with canonical limbs.

    One sequential carry chain + log2(bound/p) conditional subtractions.
    Used once per verification (final equality), not in the hot path.
    """
    limbs = []
    c = jnp.zeros_like(a.arr[0])
    for i in range(NUM_LIMBS):
        v = a.arr[i] + c
        limbs.append(v & LIMB_MASK)
        c = v >> LIMB_BITS
    t = jnp.stack(limbs, axis=0)
    k = 1
    while k * fs.p < a.bound:
        k <<= 1
    while k >= 1:
        t = _cond_sub(t, k * fs.p)
        k //= 2
    return t


def _cond_sub(t, m: int):
    """t (canonical limbs) -> t − m if t >= m else t (exact borrow chain)."""
    mc = int_to_limbs(m)
    limbs = []
    c = jnp.zeros_like(t[0])
    for i in range(NUM_LIMBS):
        v = t[i] - int(mc[i]) + c
        limbs.append(v & LIMB_MASK)
        c = v >> LIMB_BITS
    ge = c == 0  # no net borrow -> t >= m
    d = jnp.stack(limbs, axis=0)
    return jnp.where(ge, d, t)


def eq_zero_canon(a):
    """all-limbs-zero test for an already-canonical array."""
    return jnp.all(a == 0, axis=0)


def is_zero_mod_p(a: FE, fs: FieldSpec):
    return eq_zero_canon(canon(a, fs))
