"""Batched NIST P-256 ECDSA verification on TPU.

The reference verifies every transaction input serially through fastecdsa's
C extension (transaction_input.py:100-109, called per input inside the block
accept hot loop manager.py:628-632).  Here the whole block's signatures are
verified in ONE jitted program: a fixed-window (w = 4) Strauss double-scalar
ladder u₁·G + u₂·Q, batched across the lane axis in 13-bit-limb lazy
Montgomery arithmetic (:mod:`.fp`): 64 rounds of 4 doublings, one add from
a 16-entry G table (constants) and one from a 16-entry Q table built on the
device.

What the file holds, and who runs each:

* ``_verify_device`` — the ladder over *complete* projective addition
  (Renes–Costello–Batina 2016, Algorithm 4, a = −3) as a plain jnp program:
  correct for EVERY input pair (identity, doubling, inverses), so no
  signature can steer it into an exceptional case.  A CPU node runs it, and
  ``device=auto`` falls back to it.
* ``_ladder_kernel_jac`` — the one Pallas kernel: the same ladder in
  Jacobian coordinates (fewer products; exceptional lanes flagged, see the
  section comment), with ``_jac_verify_eager``, its numpy twin, as the
  reference the tests compare it with.  A TPU node runs it, fused behind
  the device scalar prep (``_prep_and_verify_pallas_jac``).
* ``_host_verify_prehashed`` — the Python-integer oracle that judges the
  lanes the Jacobian kernel flags.

The final check avoids field inversion: with R = (X : Y : Z) homogeneous,
accept ⇔ x mod n == r ⇔ X ≡ r·Z or X ≡ (r+n)·Z (mod p) (valid because
p < 2n on P-256); Jacobian, the same against Z².

Scalar prep (s⁻¹ mod n, u₁, u₂, range checks, on-curve checks) runs on the
device in front of the Pallas kernel (``_scalar_prep``) and on the host in
front of the jnp program.
"""

from __future__ import annotations

import functools
import hashlib
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.constants import CURVE_B, CURVE_GX, CURVE_GY, CURVE_N, CURVE_P
from ..core.codecs import is_on_curve
from . import fp
from .fp import FE

_FS = fp.make_field(CURVE_P)
_B_M = fp.to_mont(CURVE_B, _FS)
_GX_M = fp.to_mont(CURVE_GX, _FS)
_GY_M = fp.to_mont(CURVE_GY, _FS)
_ONE_M = _FS.r_mod_p

# Loop-invariant value bound for ladder point coordinates: the complete-add
# output coords are (sub of two ≤3p products) / (add of two) — ≤ 7p; the
# static bound tracking in fp asserts this at trace time.
_COORD_BOUND = 8 * CURVE_P

Proj = Tuple[FE, FE, FE]  # (X, Y, Z), Montgomery domain


def _point_add_complete(P1: Proj, P2: Proj, b_m: FE) -> Proj:
    """RCB16 Algorithm 4: complete addition for a=-3, homogeneous projective.

    12 generic muls + 2 muls by curve-b; handles P1==P2, inverses and the
    identity (0:1:0) with no branches — a fixed straight-line program, which
    is exactly what XLA wants.
    """
    fs = _FS
    mul = lambda x, y: fp.mont_mul(x, y, fs)
    add_ = fp.add
    sub_ = lambda x, y: fp.sub(x, y, fs)
    X1, Y1, Z1 = P1
    X2, Y2, Z2 = P2

    t0 = mul(X1, X2)
    t1 = mul(Y1, Y2)
    t2 = mul(Z1, Z2)
    t3 = add_(X1, Y1)
    t4 = add_(X2, Y2)
    t3 = mul(t3, t4)
    t4 = add_(t0, t1)
    t3 = sub_(t3, t4)
    t4 = add_(Y1, Z1)
    X3 = add_(Y2, Z2)
    t4 = mul(t4, X3)
    X3 = add_(t1, t2)
    t4 = sub_(t4, X3)
    X3 = add_(X1, Z1)
    Y3 = add_(X2, Z2)
    X3 = mul(X3, Y3)
    Y3 = add_(t0, t2)
    Y3 = sub_(X3, Y3)
    Z3 = mul(b_m, t2)
    X3 = sub_(Y3, Z3)
    Z3 = add_(X3, X3)
    X3 = add_(X3, Z3)
    Z3 = sub_(t1, X3)
    X3 = add_(t1, X3)
    Y3 = mul(b_m, Y3)
    t1 = add_(t2, t2)
    t2 = add_(t1, t2)
    Y3 = sub_(Y3, t2)
    Y3 = sub_(Y3, t0)
    t1 = add_(Y3, Y3)
    Y3 = add_(t1, Y3)
    t1 = add_(t0, t0)
    t0 = add_(t1, t0)
    t0 = sub_(t0, t2)
    t1 = mul(t4, Y3)
    t2 = mul(t0, Y3)
    Y3 = mul(X3, Z3)
    Y3 = add_(Y3, t2)
    t2 = mul(t3, X3)
    X3 = sub_(t2, t1)
    t2 = mul(t4, Z3)
    t1 = mul(t3, t0)
    Z3 = add_(t2, t1)
    return (X3, Y3, Z3)


def _clamp_point(P: Proj) -> Proj:
    """Re-declare coords at the loop-invariant bound (trace-time assert)."""
    for c in P:
        assert c.bound <= _COORD_BOUND, c.bound
    return tuple(fp.wrap(c.arr, _COORD_BOUND) for c in P)  # type: ignore


_WINDOW = 4
_DIGITS = 256 // _WINDOW  # 64 ladder iterations


def _scalar_digits(xs: Sequence[int]) -> np.ndarray:
    """Host bigints -> (64, N) int32 w=4 window digits, MSB digit first.

    Vectorized via per-int ``to_bytes`` + one numpy nibble split (the
    per-digit Python loop was ~0.3 s per 8k batch)."""
    n = len(xs)
    if n == 0:
        return np.zeros((_DIGITS, 0), dtype=np.int32)
    raw = b"".join(x.to_bytes(32, "little") for x in xs)
    by = np.frombuffer(raw, dtype=np.uint8).reshape(n, 32).astype(np.int32)
    nibbles = np.empty((n, 64), dtype=np.int32)  # nibble k = (x >> 4k) & 0xF
    nibbles[:, 0::2] = by & 0xF
    nibbles[:, 1::2] = by >> 4
    return np.ascontiguousarray(nibbles[:, ::-1].T)  # MSB digit first


def _g_window_table() -> np.ndarray:
    """(3, 16, 21) int32 — Montgomery projective [k]G for k in 0..15.

    Entry 0 is the identity (0 : 1 : 0); complete addition makes adding it
    a no-op, so zero digits need no branch."""
    from ..core import curve as host_curve

    rows = np.zeros((3, 16, fp.NUM_LIMBS), dtype=np.int32)
    rows[1, 0] = fp.int_to_limbs(_ONE_M)  # identity: (0, R mod p, 0)
    for k in range(1, 16):
        x, y = host_curve.point_mul(k, (CURVE_GX, CURVE_GY))
        rows[0, k] = fp.int_to_limbs(fp.to_mont(x, _FS))
        rows[1, k] = fp.int_to_limbs(fp.to_mont(y, _FS))
        rows[2, k] = fp.int_to_limbs(_ONE_M)
    return rows


_G_TABLE = _g_window_table()

# --- device-side scalar prep ----------------------------------------------
# The per-signature host work (s⁻¹ mod n via pow, u₁/u₂, Montgomery
# conversions, on-curve check, window-digit extraction) costs ~1 s of
# Python bigint time per 8k batch — 5x the ladder kernel itself.  This
# program does all of it on-device from raw little-endian limbs; the host
# only unpacks bytes (numpy) and checks scalar ranges.

_NS = fp.make_field(CURVE_N)
_SCALAR_BOUND = 4 * CURVE_N  # stable lazy bound for the mod-n mul chain
_INV_DIGITS = np.array(  # w=4 digits of n-2, MSB first (fixed exponent)
    [((CURVE_N - 2) >> (_WINDOW * (_DIGITS - 1 - k))) & 0xF
     for k in range(_DIGITS)], dtype=np.int32)


def _mod_n_inv_mont(s_m: FE) -> FE:
    """s_m (Montgomery domain mod n) -> s⁻¹ in Montgomery domain, via
    Fermat x^(n-2) with a 4-bit fixed window: 15-entry table (14 muls)
    then 64 scanned steps of 4 squarings + one table mul (~334 muls —
    ~6% of the ladder's budget).  The table's products are one scan
    body: XLA:TPU takes most of a second to compile each product it is
    handed inline (PERF.md section 6, PR 46).  The step's four squarings
    stay unrolled: as a loop of their own they cost the device 15% more
    time (call 2, PR 46)."""
    ns = _NS
    n_lanes = s_m.arr.shape[1]
    one_m = fp.const(ns.r_mod_p, n_lanes, _SCALAR_BOUND)

    def mul(x, y):
        return fp.mont_mul(fp.wrap(x, _SCALAR_BOUND),
                           fp.wrap(y, _SCALAR_BOUND), ns).arr

    _, powers = jax.lax.scan(
        lambda prev, _: (mul(prev, s_m.arr),) * 2, s_m.arr, None, length=14)
    table = jnp.concatenate([one_m.arr[None], s_m.arr[None], powers])

    def step(acc, digit):
        for _ in range(_WINDOW):
            acc = mul(acc, acc)
        oh = jax.nn.one_hot(digit, 16, dtype=jnp.int32)  # (16,)
        return mul(acc, (oh[:, None, None] * table).sum(axis=0)), None

    out, _ = jax.lax.scan(step, one_m.arr, jnp.asarray(_INV_DIGITS))
    return fp.wrap(out, _SCALAR_BOUND)


def _words_to_limbs(w) -> jnp.ndarray:
    """(8, N) uint32 little-endian words -> (21, N) int32 13-bit limbs.

    The host ships 256-bit scalars as 32 raw bytes instead of 84 bytes
    of pre-split limbs (2.6x less host->device transfer); the split is ~4 static shift/mask ops per limb here."""
    lb = fp.LIMB_BITS
    rows = []
    for j in range(fp.NUM_LIMBS):
        lo_bit = lb * j
        a, r = divmod(lo_bit, 32)
        if a >= 8:
            rows.append(jnp.zeros_like(w[0], dtype=jnp.int32))
            continue
        v = w[a] >> jnp.uint32(r)
        if r + lb > 32 and a + 1 < 8:
            v = v | (w[a + 1] << jnp.uint32(32 - r))
        rows.append((v & jnp.uint32(fp.LIMB_MASK)).astype(jnp.int32))
    return jnp.stack(rows, axis=0)


def _pack_words(xs, pad: int) -> np.ndarray:
    """Host ints (< 2^256) -> (8, N+pad) uint32 little-endian words."""
    n = len(xs)
    raw = b"".join(x.to_bytes(32, "little") for x in xs)
    w = np.frombuffer(raw, dtype="<u4").reshape(n, 8).T
    return np.pad(w, ((0, 0), (0, pad)), constant_values=0)


def _digits_from_limbs(limbs) -> jnp.ndarray:
    """(21, N) canonical 13-bit limbs -> (64, N) window digits, MSB
    first.  Static bit surgery: a digit spans at most two limbs."""
    lb = fp.LIMB_BITS
    mask = (1 << _WINDOW) - 1
    rows = []
    for k in range(_DIGITS):
        j, off = divmod(_WINDOW * k, lb)
        v = limbs[j] >> off
        if off + _WINDOW > lb:
            v = v | (limbs[j + 1] << (lb - off))
        rows.append(v & mask)
    return jnp.stack(rows[::-1], axis=0)


@jax.jit
def _scalar_prep(z, r, s, qx, qy, range_ok, rn_ok):
    """Packed 256-bit scalars -> ladder inputs, all on device.

    z/r/s/qx/qy: (8, N) uint32 little-endian words of the digest int,
    signature pair and affine pubkey (values < 2^256, unreduced; see
    :func:`_pack_words`).  range_ok: host-checked 0 < r,s < n and
    (qx,qy) != (0,0).  rn_ok: r + n < p.

    Returns (d1, d2, qx_m, qy_m, r_mp, rn_mp, flags) matching the ladder
    kernel's operands: canonical Montgomery limbs + (2, N) int32 flags.
    """
    fs, ns = _FS, _NS
    z, r, s, qx, qy = (_words_to_limbs(x) for x in (z, r, s, qx, qy))
    n_lanes = z.shape[1]
    raw = 1 << 256  # bound of any 256-bit input

    # Independent products of one field ride one fp.mont_mul, side by
    # side on the lane axis: the same integers a lane, and a program of
    # twelve products where it held forty-five (see _mod_n_inv_mont)
    def side_by_side(*arrs):
        return jnp.concatenate(arrs, axis=1)

    def apart(arr, k):
        return jnp.split(arr, k, axis=1)

    # mod-n: w = s^-1, u1 = z·w, u2 = r·w  (Montgomery domain throughout)
    s_m, z_m, r_mn = apart(fp.mont_mul(
        fp.wrap(side_by_side(s, z, r), raw),
        fp.const(ns.r2_mod_p, 3 * n_lanes, ns.p), ns).arr, 3)
    w_m = _mod_n_inv_mont(fp.wrap(s_m, _SCALAR_BOUND)).arr
    u_m = fp.mont_mul(fp.wrap(side_by_side(z_m, r_mn), _SCALAR_BOUND),
                      fp.wrap(side_by_side(w_m, w_m), _SCALAR_BOUND), ns)
    u = fp.canon(fp.mont_mul(u_m, fp.const(1, 2 * n_lanes, 2), ns), ns)
    d1, d2 = apart(_digits_from_limbs(u), 2)

    # mod-p: Montgomery forms of qx, qy, r, (r+n) mod p + on-curve check
    rn = fp.add(fp.wrap(r, raw), fp.const(CURVE_N, n_lanes, CURVE_N + 1))
    to_mont = fp.mont_mul(
        fp.wrap(side_by_side(qx, qy, r, rn.arr), rn.bound),
        fp.const(fs.r2_mod_p, 4 * n_lanes, fs.p), fs)
    qx_m, qy_m = (fp.wrap(x, to_mont.bound)
                  for x in apart(to_mont.arr, 4)[:2])
    qx_c, qy_c, r_mp, rn_mp = apart(fp.canon(to_mont, fs), 4)

    # y² == x³ - 3x + b  (all Montgomery domain)
    b_m = fp.const(_B_M, n_lanes, fs.p)
    yx = fp.wrap(side_by_side(qy_m.arr, qx_m.arr), to_mont.bound)
    squares = fp.mont_mul(yx, yx, fs)
    y2, x2 = (fp.wrap(x, squares.bound) for x in apart(squares.arr, 2))
    x3 = fp.mont_mul(x2, qx_m, fs)
    three_x = fp.add(fp.add(qx_m, qx_m), qx_m)
    rhs = fp.add(fp.sub(x3, three_x, fs), b_m)
    on_curve = fp.is_zero_mod_p(fp.sub(y2, rhs, fs), fs)

    valid = range_ok & on_curve
    flags = jnp.stack([rn_ok.astype(jnp.int32), valid.astype(jnp.int32)])
    return d1, d2, qx_c, qy_c, r_mp, rn_mp, flags


@jax.jit
def _verify_device(d1, d2, qx, qy, r_m, rn_m, rn_ok, valid):
    """d1/d2: (64, N) int32 window digits (MSB first); qx/qy/r_m/rn_m:
    (21, N) int32 canonical Montgomery limbs; rn_ok/valid: (N,) bool.

    Returns (N,) bool accept verdicts.

    Compile-cost discipline: one traced complete-add costs XLA:CPU ~15 s
    to compile, so the whole program keeps exactly TWO add call-sites —
    one inside the Q-table ``scan`` and one inside the ladder's inner
    6-step ``scan`` (4 doublings + G-add + Q-add are the *same* site with
    the second operand selected by step index).  Cold compile lands in
    well under a minute; the persistent cache makes reruns instant.
    """
    fs = _FS
    n = qx.shape[1]
    p = fs.p
    b_m = fp.const(_B_M, n, p)
    Q: Proj = (fp.wrap(qx, p), fp.wrap(qy, p), fp.const(_ONE_M, n, p))
    identity: Proj = (fp.const(0, n, p), fp.const(_ONE_M, n, p), fp.const(0, n, p))

    def stack_point(P: Proj):
        return jnp.stack([c.arr for c in P], axis=0)  # (3, 21, N)

    def unstack_point(a, bound: int) -> Proj:
        return tuple(fp.wrap(a[i], bound) for i in range(3))  # type: ignore

    # --- Q window table: [k]Q for k=0..15, one scanned add site ----------
    def qstep(carry, _):
        P = unstack_point(carry, _COORD_BOUND)
        nxt = stack_point(_clamp_point(_point_add_complete(P, Q, b_m)))
        return nxt, nxt

    q1 = stack_point(_clamp_point(Q))
    _, q_rest = jax.lax.scan(qstep, q1, None, length=14)  # (14, 3, 21, N)
    q_table = jnp.concatenate(
        [stack_point(_clamp_point(identity))[None], q1[None], q_rest], axis=0
    )  # (16, 3, 21, N)
    g_table = jnp.asarray(_G_TABLE.transpose(1, 0, 2))  # (16, 3, 21)

    # --- ladder: 64 digit rounds × (4 dbl + G-add + Q-add), 1 add site ---
    def round_body(k, carry):
        dg1 = jax.lax.dynamic_index_in_dim(d1, k, axis=0, keepdims=False)
        dg2 = jax.lax.dynamic_index_in_dim(d2, k, axis=0, keepdims=False)
        # table picks as one-hot contractions, not gathers: a (16,N) one-hot
        # against the shared G table is a plain matmul, and the Q pick is a
        # regular masked reduction — both orders of magnitude faster on TPU
        # than per-lane gather + transpose of (N,3,21) blocks
        oh1 = jax.nn.one_hot(dg1, 16, dtype=jnp.int32, axis=0)  # (16, N)
        oh2 = jax.nn.one_hot(dg2, 16, dtype=jnp.int32, axis=0)
        g_pick = jnp.einsum("kcl,kn->cln", g_table, oh1)  # (3, 21, N)
        q_pick = (q_table * oh2[:, None, None, :]).sum(axis=0)  # (3, 21, N)

        def step(r_arrs, j):
            R = unstack_point(r_arrs, _COORD_BOUND)
            operand = jnp.where(j < 4, r_arrs, jnp.where(j == 4, g_pick, q_pick))
            P2 = unstack_point(operand, _COORD_BOUND)
            out = stack_point(_clamp_point(_point_add_complete(R, P2, b_m)))
            return out, None

        out, _ = jax.lax.scan(step, carry, jnp.arange(6))
        return out

    carry0 = stack_point(_clamp_point(identity))
    final = jax.lax.fori_loop(0, _DIGITS, round_body, carry0)
    Xa, Ya, Za = final[0], final[1], final[2]
    X = fp.wrap(Xa, _COORD_BOUND)
    Z = fp.wrap(Za, _COORD_BOUND)

    rz = fp.mont_mul(fp.wrap(r_m, p), Z, fs)
    rnz = fp.mont_mul(fp.wrap(rn_m, p), Z, fs)
    at_infinity = fp.is_zero_mod_p(Z, fs)
    ok = fp.is_zero_mod_p(fp.sub(X, rz, fs), fs) | (
        rn_ok & fp.is_zero_mod_p(fp.sub(X, rnz, fs), fs)
    )
    return ok & (~at_infinity) & valid


# --- Jacobian ladder (the fast production kernel) --------------------------
# The RCB16 complete-addition ladder above is branch-free and safe for any
# input, but pays ~14 Montgomery products per add and 14 per doubling-as-
# addition.  Jacobian coordinates cut the per-round product count ~1.5x:
# doubling is 3M+5S (dbl-2001-b, a = -3), the G-add is a mixed affine add
# (madd-2007-bl, 7M+4S) and the Q-add a general add (add-2007-bl, 11M+5S).
#
# Jacobian formulas are NOT complete — they break when an operand is the
# identity or when P1 = ±P2.  Consensus safety is preserved structurally:
#
# * identity operands never reach the formulas: a zero window digit keeps
#   the accumulator (digit==0 mask select), and an all-zero-so-far scalar
#   prefix ("started" flag) replaces the result with the picked point;
#   the identity encoding (R, R, 0) is an exact fixed point of the
#   doubling program, so untouched lanes stay canonical through the 4
#   doublings per round;
# * the remaining exceptional case — H ≡ 0 with both operands real, i.e.
#   the accumulator colliding with ±(table pick) — sets a per-lane
#   EXCEPTION FLAG, and flagged lanes are re-verified on the host oracle
#   (:func:`_host_verify_prehashed`).  For honest signatures a collision
#   has probability ~2⁻²⁵⁰; a crafted signature can at worst force its
#   own lane onto the host path (one ~ms verify), never flip a verdict.
#
# Both sub-cases of H ≡ 0 are flagged (P1 = P2, which needs a doubling,
# and P1 = −P2, which yields the identity), so the ladder never has to
# distinguish them on device.

_JB = 64 * CURVE_P  # Jacobian ladder loop-invariant coordinate bound


def _jac_clamp(P):
    for c in P:
        assert c.bound <= _JB, c.bound.bit_length()
    return tuple(fp.l_wrap(c.limbs, _JB) for c in P)


def _jac_dbl(P, fs=_FS):
    """dbl-2001-b (a = -3): 3M + 5S.  Identity-safe: (X, Y, 0) maps to
    Z3 = (Y+0)² − Y² − 0 = 0, and the (R, R, 0) encoding is an exact
    fixed point (alpha = 3R, X3 = 9R − 8R = R, Y3 = 3R·3R − 8R = R)."""
    X, Y, Z = P
    delta = fp.l_mont_sqr(Z, fs)
    gamma = fp.l_mont_sqr(Y, fs)
    beta = fp.l_mont_mul(X, gamma, fs)
    alpha = fp.l_mont_mul(fp.l_sub(X, delta, fs), fp.l_add(X, delta), fs)
    alpha = fp.l_add(fp.l_add(alpha, alpha), alpha)
    beta2 = fp.l_add(beta, beta)
    beta4 = fp.l_add(beta2, beta2)
    beta8 = fp.l_add(beta4, beta4)
    X3 = fp.l_sub(fp.l_mont_sqr(alpha, fs), beta8, fs)
    g2 = fp.l_mont_sqr(gamma, fs)
    g4 = fp.l_add(g2, g2)
    g8 = fp.l_add(g4, g4)
    Y3 = fp.l_sub(
        fp.l_mont_mul(alpha, fp.l_sub(beta4, X3, fs), fs),
        fp.l_add(g8, g8), fs)
    Z3 = fp.l_sub(fp.l_sub(fp.l_mont_sqr(fp.l_add(Y, Z), fs), gamma, fs),
                  delta, fs)
    return X3, Y3, Z3


def _jac_madd(P1, x2, y2, fs=_FS):
    """madd-2007-bl (P2 affine, Z2 = 1): 7M + 4S.  Returns (P3, H); the
    caller must select away P1-identity / P2-identity lanes and flag
    H ≡ 0 lanes (P1 = ±P2)."""
    X1, Y1, Z1 = P1
    z1z1 = fp.l_mont_sqr(Z1, fs)
    u2 = fp.l_mont_mul(x2, z1z1, fs)
    s2 = fp.l_mont_mul(y2, fp.l_mont_mul(Z1, z1z1, fs), fs)
    H = fp.l_sub(u2, X1, fs)
    hh = fp.l_mont_sqr(H, fs)
    i2 = fp.l_add(hh, hh)
    i4 = fp.l_add(i2, i2)
    j = fp.l_mont_mul(H, i4, fs)
    rr = fp.l_sub(s2, Y1, fs)
    rr = fp.l_add(rr, rr)
    v = fp.l_mont_mul(X1, i4, fs)
    X3 = fp.l_sub(fp.l_sub(fp.l_mont_sqr(rr, fs), j, fs),
                  fp.l_add(v, v), fs)
    y1j = fp.l_mont_mul(Y1, j, fs)
    Y3 = fp.l_sub(fp.l_mont_mul(rr, fp.l_sub(v, X3, fs), fs),
                  fp.l_add(y1j, y1j), fs)
    Z3 = fp.l_sub(fp.l_sub(fp.l_mont_sqr(fp.l_add(Z1, H), fs), z1z1, fs),
                  hh, fs)
    return (X3, Y3, Z3), H


def _jac_add(P1, P2, fs=_FS):
    """add-2007-bl (both Jacobian): 11M + 5S.  Returns (P3, H); same
    caller obligations as :func:`_jac_madd`."""
    X1, Y1, Z1 = P1
    X2, Y2, Z2 = P2
    z1z1 = fp.l_mont_sqr(Z1, fs)
    z2z2 = fp.l_mont_sqr(Z2, fs)
    u1 = fp.l_mont_mul(X1, z2z2, fs)
    u2 = fp.l_mont_mul(X2, z1z1, fs)
    s1 = fp.l_mont_mul(Y1, fp.l_mont_mul(Z2, z2z2, fs), fs)
    s2 = fp.l_mont_mul(Y2, fp.l_mont_mul(Z1, z1z1, fs), fs)
    H = fp.l_sub(u2, u1, fs)
    h2 = fp.l_add(H, H)
    i = fp.l_mont_sqr(h2, fs)
    j = fp.l_mont_mul(H, i, fs)
    rr = fp.l_sub(s2, s1, fs)
    rr = fp.l_add(rr, rr)
    v = fp.l_mont_mul(u1, i, fs)
    X3 = fp.l_sub(fp.l_sub(fp.l_mont_sqr(rr, fs), j, fs),
                  fp.l_add(v, v), fs)
    s1j = fp.l_mont_mul(s1, j, fs)
    Y3 = fp.l_sub(fp.l_mont_mul(rr, fp.l_sub(v, X3, fs), fs),
                  fp.l_add(s1j, s1j), fs)
    Z3 = fp.l_mont_mul(
        fp.l_sub(fp.l_sub(fp.l_mont_sqr(fp.l_add(Z1, Z2), fs), z1z1, fs),
                 z2z2, fs), H, fs)
    return (X3, Y3, Z3), H


_TABLE = 1 << _WINDOW  # entries of a window table, the identity's included

# (2, 16, 21) int32 — affine Montgomery (x, y) of [k]G, k >= 1: the
# rows of _G_TABLE without their Z.  Entry 0 is a placeholder: zero
# digits select the accumulator before the pick is ever used.
_G_AFFINE = _G_TABLE[:2]


def _jac_identity(like):
    """The (R, R, 0) identity encoding, matching ``like``'s namespace."""
    return (fp.l_full(_ONE_M, like, CURVE_P),
            fp.l_full(_ONE_M, like, CURVE_P),
            fp.l_full(0, like, CURVE_P))


def _jac_lift_affine(x2, y2):
    return (fp.l_wrap(x2.limbs, _JB), fp.l_wrap(y2.limbs, _JB),
            fp.l_full(_ONE_M, x2.limbs[0], _JB))


def _jac_qtable(qx, qy, fs=_FS):
    """Entries [1..15] = [k]Q as Jacobian FL points (bound <= _JB), as a
    list: the eager twin's table.  The kernel builds the same entries
    into VMEM scratch with the same two formulas
    (:func:`_ladder_kernel_jac`).

    Exception-free for on-curve Q: [k]Q = ±Q would need (k∓1)Q = identity
    with k−1 < 16 ≪ n (prime group order).  Off-curve garbage (already
    doomed by the `valid` flag) may produce garbage entries — harmless,
    the verdict is masked and any spurious exception flag just routes the
    lane to the host oracle, which rejects it."""
    e1 = _jac_clamp(_jac_lift_affine(qx, qy))
    entries = [e1, _jac_clamp(_jac_dbl(e1, fs))]
    for _ in range(3, _TABLE):
        nxt, _h = _jac_madd(entries[-1], qx, qy, fs)
        entries.append(_jac_clamp(nxt))
    return entries


def _jac_flatten(P):
    """Point -> nested tuple of limb arrays (a ``fori_loop`` carry)."""
    return tuple(tuple(c.limbs) for c in P)


def _jac_unflatten(t):
    return tuple(fp.l_wrap(limbs, _JB) for limbs in t)


def _jac_dbl_window(acc, fs=_FS):
    """The round's ``_WINDOW`` doublings.  Traced limbs: one loop over
    one :func:`_jac_dbl`, so that the compiler is handed the doubling
    once and not four times; numpy limbs (the eager twin) run the same
    doublings one after another."""
    acc = _jac_clamp(acc)
    if fp._xp(*acc[0].limbs) is np:
        for _ in range(_WINDOW):
            acc = _jac_clamp(_jac_dbl(acc, fs))
        return acc
    return _jac_unflatten(jax.lax.fori_loop(
        0, _WINDOW,
        lambda _, t: _jac_flatten(_jac_clamp(_jac_dbl(_jac_unflatten(t),
                                                      fs))),
        _jac_flatten(acc)))


def _jac_round(acc, started, exc, dg1, dg2, g_pick_fn, q_pick_fn, fs=_FS):
    """One 4-bit digit round: 4 doublings, G mixed add, Q general add —
    with the structural identity selects and exception flagging described
    in the section comment.  ``started``/``exc`` are int32 masks of the
    limb shape; ``g_pick_fn(dg) -> (x2, y2)`` affine FLs, ``q_pick_fn(dg)
    -> Jacobian FL point``.  Returns (acc, started, exc)."""
    acc = _jac_dbl_window(acc, fs)

    gx, gy = g_pick_fn(dg1)
    res, H = _jac_madd(acc, gx, gy, fs)
    acc, started, exc = _jac_apply_add(
        acc, res, H, _jac_lift_affine(gx, gy), dg1, started, exc, fs)

    q_pick = q_pick_fn(dg2)
    res, H = _jac_add(acc, q_pick, fs)
    acc, started, exc = _jac_apply_add(
        acc, res, H, q_pick, dg2, started, exc, fs)
    return acc, started, exc


def _jac_apply_add(acc, res, H, pick_point, dg, started, exc, fs=_FS):
    """The single-sourced post-add masking invariant for both add sites:

    * digit == 0 (identity pick)            -> keep the accumulator;
    * accumulator still identity, real pick -> take the picked point;
    * H ≡ 0 with both operands real         -> flag the lane (P1 = ±P2,
      the formula output is unusable; host oracle decides);
    * otherwise                             -> the formula result.

    ``started`` flips once any nonzero digit lands."""
    pick_id = (dg == 0)
    acc_inf = started == 0
    h0 = fp.l_is_zero_mod_p(H, fs)
    exc = exc | (h0 & ~pick_id & ~acc_inf).astype(np.int32)
    out = []
    for c_res, c_acc, c_pick in zip(res, acc, pick_point):
        c = fp.l_select(pick_id, c_acc, fp.l_wrap(c_res.limbs, _JB))
        c = fp.l_select(acc_inf & ~pick_id, fp.l_wrap(c_pick.limbs, _JB), c)
        out.append(c)
    return (_jac_clamp(tuple(out)), started | (~pick_id).astype(np.int32),
            exc)


def _jac_final(acc, started, r_m, rn_m, rn_ok, valid, fs=_FS):
    """Jacobian accept check: x = X/Z², so accept ⇔ X ≡ r·Z² or
    (r + n < p and X ≡ (r+n)·Z²) (mod p), R not the identity."""
    X, _Y, Z = acc
    z2 = fp.l_mont_sqr(Z, fs)
    rz = fp.l_mont_mul(fp.l_wrap(r_m.limbs, CURVE_P), z2, fs)
    rnz = fp.l_mont_mul(fp.l_wrap(rn_m.limbs, CURVE_P), z2, fs)
    at_inf = fp.l_is_zero_mod_p(Z, fs) | (started == 0)
    ok = fp.l_is_zero_mod_p(fp.l_sub(X, rz, fs), fs) | (
        rn_ok & fp.l_is_zero_mod_p(fp.l_sub(X, rnz, fs), fs))
    return ok & ~at_inf & valid


def _jac_verify_eager(d1, d2, qx, qy, r_m, rn_m, rn_ok, valid,
                      n_rounds: int = _DIGITS):
    """Host twin of the Pallas Jacobian kernel, same round logic via the
    shared helpers — runs on plain numpy (no jit, no device) so tests can
    drive short crafted ladders cheaply.  d1/d2: (n_rounds, N) int32
    digits; qx..rn_m: (21, N) canonical Montgomery limb numpy arrays;
    rn_ok/valid: (N,) bool.  Returns (ok, exc) bool arrays."""
    def to_fl(a, bound):
        return fp.l_wrap([np.asarray(a[i]) for i in range(fp.NUM_LIMBS)],
                         bound)

    qx_f, qy_f = to_fl(qx, CURVE_P), to_fl(qy, CURVE_P)
    n = d1.shape[1]
    qtab = _jac_qtable(qx_f, qy_f)

    def g_pick_fn(dg):
        out = []
        for c in range(2):
            limbs = []
            for l in range(fp.NUM_LIMBS):
                acc = np.zeros((n,), np.int32)
                for k in range(1, _TABLE):
                    g = int(_G_AFFINE[c, k, l])
                    if g:
                        acc = acc + np.where(dg == k, g, 0)
                limbs.append(acc)
            out.append(fp.l_wrap(limbs, CURVE_P))
        return tuple(out)

    def q_pick_fn(dg):
        out = []
        for c in range(3):
            limbs = []
            for l in range(fp.NUM_LIMBS):
                acc = np.zeros((n,), np.int32)
                for k in range(1, _TABLE):
                    acc = acc + np.where(dg == k, qtab[k - 1][c].limbs[l], 0)
                limbs.append(acc)
            out.append(fp.l_wrap(limbs, _JB))
        return tuple(out)

    d1, d2 = np.asarray(d1), np.asarray(d2)
    acc = _jac_identity(np.zeros((n,), np.int32))
    started = np.zeros((n,), np.int32)
    exc = np.zeros((n,), np.int32)
    for k in range(n_rounds):
        acc, started, exc = _jac_round(acc, started, exc, d1[k], d2[k],
                                       g_pick_fn, q_pick_fn)
    ok = _jac_final(acc, started, to_fl(r_m, CURVE_P), to_fl(rn_m, CURVE_P),
                    rn_ok, valid)
    return np.asarray(ok), np.asarray(exc != 0)


def _ladder_kernel_jac(d1_ref, d2_ref, qx_ref, qy_ref, rm_ref, rnm_ref,
                       flags_ref, out_ref, qtab_ref):
    """Pallas limb-list Jacobian ladder: every limb of every element is
    one full (S, 128) VMEM tile, and limb shifts inside the Montgomery
    multiply are Python indexing.  Emits bit0 = verdict, bit1 = exception
    flag per lane.

    What the compiler is handed inline is what it takes its time over
    (PERF.md section 6, PR 46), so each formula stands in the body once
    where a loop can carry it: one mixed add for the Q table's thirteen,
    one doubling for a round's four."""
    fs = _FS
    S = qx_ref.shape[1]
    shape = (S, 128)

    def read_fl(ref, bound):
        return fp.l_wrap([ref[i] for i in range(fp.NUM_LIMBS)], bound)

    def read_entry(k):
        return tuple(
            fp.l_wrap([qtab_ref[k, c, l] for l in range(fp.NUM_LIMBS)], _JB)
            for c in range(3))

    def write_entry(k, e):
        for c in range(3):
            for l in range(fp.NUM_LIMBS):
                qtab_ref[k, c, l] = e[c].limbs[l]

    # --- Q table into VMEM scratch: slot k holds [k + 1]Q ----------------
    # the same entries as _jac_qtable's, each from the one before
    def read_q():
        return read_fl(qx_ref, CURVE_P), read_fl(qy_ref, CURVE_P)

    e1 = _jac_clamp(_jac_lift_affine(*read_q()))
    write_entry(0, e1)
    write_entry(1, _jac_clamp(_jac_dbl(e1, fs)))

    def qstep(k, carry):
        nxt, _h = _jac_madd(read_entry(k - 1), *read_q(), fs)
        write_entry(k, _jac_clamp(nxt))
        return carry

    jax.lax.fori_loop(2, _TABLE - 1, qstep, 0)

    def g_pick_fn(dg):
        masks = [(dg == k).astype(jnp.int32) for k in range(_TABLE)]
        out = []
        for c in range(2):
            limbs = []
            for l in range(fp.NUM_LIMBS):
                acc = None
                for k in range(1, _TABLE):
                    g = int(_G_AFFINE[c, k, l])
                    if g == 0:
                        continue
                    term = masks[k] * g
                    acc = term if acc is None else acc + term
                limbs.append(jnp.zeros(shape, jnp.int32) if acc is None
                             else acc)
            out.append(fp.l_wrap(limbs, CURVE_P))
        return tuple(out)

    def q_pick_fn(dg):
        masks = [(dg == k).astype(jnp.int32) for k in range(_TABLE)]
        out = []
        for c in range(3):
            limbs = []
            for l in range(fp.NUM_LIMBS):
                acc = masks[1] * qtab_ref[0, c, l]
                for k in range(2, _TABLE):
                    acc = acc + masks[k] * qtab_ref[k - 1, c, l]
                limbs.append(acc)
            out.append(fp.l_wrap(limbs, _JB))
        return tuple(out)

    def round_body(k, carry):
        acc, started, exc = _jac_round(
            _jac_unflatten(carry[:3]), carry[3], carry[4],
            d1_ref[k], d2_ref[k], g_pick_fn, q_pick_fn, fs)
        return _jac_flatten(acc) + (started, exc)

    z = jnp.zeros(shape, jnp.int32)
    carry = jax.lax.fori_loop(
        0, _DIGITS, round_body,
        _jac_flatten(_jac_identity(z)) + (z, z))

    rn_ok = flags_ref[0] != 0
    valid = flags_ref[1] != 0
    ok = _jac_final(_jac_unflatten(carry[:3]), carry[3],
                    read_fl(rm_ref, CURVE_P), read_fl(rnm_ref, CURVE_P),
                    rn_ok, valid, fs)
    out_ref[...] = ok.astype(jnp.int32) + 2 * carry[4]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _verify_device_pallas_jac(d1, d2, qx, qy, r_m, rn_m, flags,
                              tile: int = 1024, interpret: bool = False):
    """Run the Jacobian ladder kernel; returns (ok, exc) bool (N,) arrays.

    ``tile`` = lanes per grid step, a multiple of 128 (the batch axis is
    reshaped to (rows, 128) so each limb is a full VPU tile)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = qx.shape[1]
    assert n % 128 == 0 and tile % 128 == 0 and n % tile == 0, (n, tile)
    rows, sub = n // 128, tile // 128
    grid = rows // sub

    def rs(x):
        return x.reshape(x.shape[0], rows, 128)

    spec = lambda r: pl.BlockSpec(
        (r, sub, 128), lambda i: (0, i, 0), memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _ladder_kernel_jac,
        grid=(grid,),
        in_specs=[
            spec(_DIGITS), spec(_DIGITS),
            spec(fp.NUM_LIMBS), spec(fp.NUM_LIMBS),
            spec(fp.NUM_LIMBS), spec(fp.NUM_LIMBS),
            spec(2),
        ],
        out_specs=pl.BlockSpec((sub, 128), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((_TABLE - 1, 3, fp.NUM_LIMBS, sub, 128), jnp.int32)],
        interpret=interpret,
    )(rs(d1), rs(d2), rs(qx), rs(qy), rs(r_m), rs(rn_m), rs(flags))
    out = out.reshape(n)
    return (out & 1) != 0, (out & 2) != 0


def _host_verify_prehashed(z: int, r: int, s: int, qx: int, qy: int) -> bool:
    """Host oracle for exception-flagged lanes — the exact device
    semantics: range checks, coordinate reduction mod p (fastecdsa
    parity), on-curve check, then x(u₁G + u₂Q) ≡ r (mod n)."""
    from ..core import curve as host_curve

    if not (0 < r < CURVE_N and 0 < s < CURVE_N):
        return False
    if qx == 0 and qy == 0:
        return False
    qx, qy = qx % CURVE_P, qy % CURVE_P
    if not is_on_curve((qx, qy)):
        return False
    w = pow(s, -1, CURVE_N)
    u1, u2 = z * w % CURVE_N, r * w % CURVE_N
    pt = host_curve.point_add(host_curve.point_mul(u1, host_curve.G),
                              host_curve.point_mul(u2, (qx, qy)))
    return pt is not None and pt[0] % CURVE_N == r


PALLAS_STRICT = False  # True: never fall back (tests assert kernel health)


def _one_stack_chunk(thunk):
    """Call ``thunk`` with every Python frame it pushes in one chunk."""
    return thunk()


# CPython (3.11 on) keeps a thread's frames in 16 KiB chunks and hands a
# chunk back to the OS the moment its first frame pops, so a call site
# that happens to stand at a chunk's end maps and unmaps memory at every
# call.  Lowering this program is ~10^7 calls at a few dozen depths, and
# where it starts decides how many of them stand there: the same
# lowering took the chip's host 17 s from a bare process's main thread
# and 134 s from a node's worker thread (PERF.md section 6, PR 46).  A
# frame this wide gets a chunk of its own, 2 MiB, whose second half
# holds whatever jax pushes below it.
_one_stack_chunk.__code__ = _one_stack_chunk.__code__.replace(
    co_stacksize=1 << 17)


def _pallas_or_jnp(pallas_thunk, jnp_thunk) -> np.ndarray:
    """Run the Pallas program, materialized; on a failure — lowering or
    async runtime (which only surfaces at materialization) — recompute
    via the jnp program and count it (``kernel.p256_verify.
    pallas_fallbacks`` in /metrics): same math either way, and in
    ``device=auto`` a broken kernel degrades a validating node to the
    slow path rather than taking it down.  In a ``device=tpu`` process
    (and under ``PALLAS_STRICT``) the failure raises instead: the slow
    path would pass for the device."""
    try:
        return np.asarray(_one_stack_chunk(pallas_thunk))
    except Exception:
        from ..device.runtime import tpu_required

        if PALLAS_STRICT or tpu_required():
            raise
        import logging

        from ..telemetry import metrics

        metrics.inc("kernel.p256_verify.pallas_fallbacks")
        logging.getLogger("upow_tpu.crypto").warning(
            "pallas verify kernel failed; falling back to jnp",
            exc_info=True)
        return np.asarray(jnp_thunk())


_TILE_CAP = 1024  # lanes a grid step: one vreg a limb, eight sublane rows


def _pick_tile(padded: int) -> int:
    """Largest 128-multiple divisor of ``padded`` that is <= 1024
    (``padded`` is always a multiple of 128 on the pallas path)."""
    rows = padded // 128
    for k in range(min(_TILE_CAP // 128, rows), 0, -1):
        if rows % k == 0:
            return 128 * k
    return 128


def _pad_to_block(n: int, block: int = 128) -> int:
    """Round up to a power-of-two multiple of ``block`` (>= block).

    ``block`` = 128 fills TPU lanes; small blocks (e.g. 8) keep the CPU
    dryrun/interpret paths cheap."""
    padded = max(block, 1 << (n - 1).bit_length())
    return ((padded + block - 1) // block) * block


def verify_batch(
    messages: Sequence[bytes],
    signatures: Sequence[Tuple[int, int]],
    pubkeys: Sequence[Tuple[int, int]],
    pad_block: int = 128,
) -> np.ndarray:
    """Batch-verify ECDSA signatures over sha256(message).  Returns (N,) bool.

    Semantics match ``fastecdsa.ecdsa.verify`` as used by the reference
    (transaction_input.py:100-109): sha256 digest, bits2int truncation,
    range-checked r/s, and on-curve pubkeys.  Invalid-by-construction
    entries short-circuit to False on the host and never reach the device.
    """
    digests = [hashlib.sha256(m).digest() for m in messages]
    return verify_batch_prehashed(digests, signatures, pubkeys, pad_block)


def _unpack_fused(packed):
    """(42, N) uint32 fused input -> the 7 logical scalar-prep operands.

    Rows 0-39 are five (8, N) little-endian word arrays (z, r, s, qx,
    qy); rows 40/41 are the host-checked range_ok / rn_ok masks.  Fusing
    the operands into one array keeps the host->device path at ONE
    transfer per batch (each separate transfer pays its own host
    round trip)."""
    z, r, s, qx, qy = (packed[8 * i:8 * i + 8] for i in range(5))
    return z, r, s, qx, qy, packed[40] != 0, packed[41] != 0


def _jac_body(packed, tile: int):
    """Shared trace body: fused input -> device scalar prep -> Jacobian
    ladder kernel -> stacked (2, N) bool (row 0 accept verdicts, row 1
    exception flags; those lanes need the host oracle).  One input and
    one output array = one transfer each way."""
    with jax.named_scope("upow.p256_verify"):
        args = _scalar_prep(*_unpack_fused(packed))
        ok, exc = _verify_device_pallas_jac(*args, tile=tile)
        return jnp.stack([ok, exc])


@functools.partial(jax.jit, static_argnames=("tile",))
def _prep_and_verify_pallas_jac(packed, tile: int):
    """One dispatch: device scalar prep -> Jacobian ladder kernel."""
    return _jac_body(packed, tile)


@functools.partial(jax.jit, static_argnames=("tile", "mesh"))
def _prep_and_verify_pallas_jac_sharded(packed, tile: int, mesh):
    """Mesh-DP variant: every device runs scalar prep + the Pallas ladder
    on its own batch shard (the program is elementwise over lanes, so the
    only communication is the output gather).  ``shard_map`` is required
    — pallas_call has no SPMD partitioning rule, so plain jit + sharded
    inputs cannot split it."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import shard_map_compat

    shard_map, check_kw = shard_map_compat()

    def per_device(packed_):
        return _jac_body(packed_, tile)

    lanes = P(None, "dp")
    return shard_map(
        per_device, mesh=mesh,
        in_specs=(lanes,), out_specs=lanes, **check_kw,
    )(packed)


@jax.jit
def _prep_and_verify_jnp(packed):
    with jax.named_scope("upow.p256_verify"):
        d1, d2, qxm, qym, rmp, rnmp, flags = _scalar_prep(
            *_unpack_fused(packed))
        return _verify_device(d1, d2, qxm, qym, rmp, rnmp,
                              flags[0] != 0, flags[1] != 0)


def _pack_device_inputs(digests, signatures, pubkeys, padded: int):
    """Host side of the device-prep path: sanitize scalars and pack them
    into ONE fused (42, padded) uint32 array (see :func:`_unpack_fused`)
    moved to the device in a single transfer.  Returns
    (fused_device_array, zs, rs, ss, qxs, qys) — the python-int lists
    feed the host oracle for exception-flagged lanes.  Split out so the
    bench can pipeline this host stage against in-flight device batches
    (the chain-sync ingest profile)."""
    n = len(digests)

    def sane(x):  # out-of-[0, 2^256) scalars never reach the word packer
        return x if 0 <= x < (1 << 256) else 0

    def coord(x):
        # the reference's fastecdsa computes everything mod p, so a
        # coordinate in [p, 2^256) encodes the reduced point — accept
        # it identically (consensus parity); reduce oversized/negative
        # ints the way Python % does on the host oracle path
        return x if 0 <= x < (1 << 256) else x % CURVE_P

    # u1 depends only on z mod n, so oversized digests (a direct API
    # caller hashing with sha512, say) reduce exactly like the host's
    # z*w % n — never an exception where the host returns a verdict
    zs = [z if z < (1 << 256) else z % CURVE_N
          for z in (int.from_bytes(d, "big") for d in digests)]
    rs = [sig[0] for sig in signatures]
    ss = [sig[1] for sig in signatures]
    qxs = [coord(pk[0]) for pk in pubkeys]
    qys = [coord(pk[1]) for pk in pubkeys]
    range_ok = np.array(
        [0 < r_ < CURVE_N and 0 < s_ < CURVE_N
         and not (qx_ == 0 and qy_ == 0)
         for r_, s_, (qx_, qy_) in zip(rs, ss, pubkeys)], dtype=bool)
    rn_ok = np.array([0 < r_ and r_ + CURVE_N < CURVE_P for r_ in rs],
                     dtype=bool)
    fused = np.zeros((42, padded), dtype=np.uint32)
    for i, xs in enumerate((zs, [sane(r_) for r_ in rs],
                            [sane(s_) for s_ in ss], qxs, qys)):
        fused[8 * i:8 * i + 8, :n] = _pack_words(xs, 0)
    fused[40, :n] = range_ok
    fused[41, :n] = rn_ok
    return jnp.asarray(fused), zs, rs, ss, qxs, qys


def verify_batch_prehashed(
    digests: Sequence[bytes],
    signatures: Sequence[Tuple[int, int]],
    pubkeys: Sequence[Tuple[int, int]],
    pad_block: int = 128,
    backend: Optional[str] = None,
    mesh=None,
    scalar_prep: Optional[str] = None,
) -> np.ndarray:
    """``mesh``: a jax.sharding.Mesh — the padded batch is placed with
    its lane axis sharded over the mesh ("dp"), so the elementwise
    verify program runs SPMD with zero collectives (SURVEY §2.3 DP
    verify).  Without it, inputs live on one device.  The jnp backend
    shards via plain jit; the pallas backend wraps the kernel in
    shard_map — pallas_call has no partitioning rule, so each device
    runs the grid on its own shard.

    ``scalar_prep``: "device" moves s⁻¹ mod n, u₁/u₂, Montgomery
    conversions, the on-curve check and digit extraction into the jitted
    program (default on TPU — the host bigint loop costs 5x the ladder
    kernel); "host" keeps them in Python (default on CPU, where compile
    time matters more than per-batch host microseconds).  The pallas
    backend is one fused dispatch and takes the device prep only."""
    n = len(digests)
    assert len(signatures) == n and len(pubkeys) == n
    if n == 0:
        return np.zeros(0, dtype=bool)
    if backend is None or scalar_prep is None:
        from ..device.runtime import get_runtime

        platform = get_runtime().platform()
        if backend is None:
            backend = "pallas" if platform == "tpu" else "jnp"
        if scalar_prep is None:
            scalar_prep = "device" if platform == "tpu" else "host"
    n_dev = mesh.devices.size if mesh is not None else 1
    # padded length must split evenly across the mesh ...
    unit = n_dev
    if backend == "pallas":
        if scalar_prep != "device":
            raise ValueError(
                "the pallas ladder takes its operands from the device "
                "scalar prep; pass backend='jnp' for host prep")
        # ... and the kernel reshapes the batch axis to (rows, 128), so
        # every device's shard fills whole kernel tiles
        unit = 128 * n_dev
    pad_block = math.lcm(pad_block, unit)
    padded = _pad_to_block(n, pad_block)

    # occupancy + in-process jit hit/miss telemetry: real lanes vs the
    # padded batch actually dispatched; the compile key mirrors what
    # jit retraces on (padded shape + static kernel choices)
    from ..telemetry import device as _ktel

    _ktel.record_batch(
        "p256_verify", real=n, padded=padded,
        compile_key=(backend, scalar_prep, padded,
                     n_dev if mesh is not None else 0))

    def over_mesh(*arrays):
        if mesh is None:
            return arrays
        from ..parallel.mesh import shard_batch_arrays

        return shard_batch_arrays(mesh, *arrays)

    if scalar_prep == "device":
        inputs, zs, rs, ss, qxs, qys = _pack_device_inputs(
            digests, signatures, pubkeys, padded)
        inputs, = over_mesh(inputs)
        if backend != "pallas":
            return np.asarray(_prep_and_verify_jnp(inputs))[:n]

        def pallas_thunk():
            if mesh is not None:
                return _prep_and_verify_pallas_jac_sharded(
                    inputs, tile=_pick_tile(padded // n_dev), mesh=mesh)
            return _prep_and_verify_pallas_jac(inputs,
                                               tile=_pick_tile(padded))

        def jnp_thunk():
            # the jnp fallback's complete formulas have no exceptions
            # (sharded inputs partition the plain-jit program too)
            ok = np.asarray(_prep_and_verify_jnp(inputs))
            return np.stack([ok, np.zeros_like(ok)])

        out, exc = _pallas_or_jnp(pallas_thunk, jnp_thunk)
        if exc[:n].any():
            out = out.copy()
            for i in np.nonzero(exc[:n])[0]:
                out[i] = _host_verify_prehashed(
                    zs[i], rs[i], ss[i], qxs[i], qys[i])
        return out[:n]

    u1s, u2s, qxs, qys, rms, rnms, rnoks, valids = [], [], [], [], [], [], [], []
    for digest, (r, s), (qx, qy) in zip(digests, signatures, pubkeys):
        ok = 0 < r < CURVE_N and 0 < s < CURVE_N and is_on_curve((qx, qy)) \
            and not (qx == 0 and qy == 0)
        if ok:
            z = int.from_bytes(digest, "big")
            w = pow(s, -1, CURVE_N)
            u1, u2 = z * w % CURVE_N, r * w % CURVE_N
        else:
            u1, u2, qx, qy, r = 1, 1, CURVE_GX, CURVE_GY, 1
        rn = r + CURVE_N
        u1s.append(u1)
        u2s.append(u2)
        qxs.append(fp.to_mont(qx, _FS))
        qys.append(fp.to_mont(qy, _FS))
        rms.append(fp.to_mont(r, _FS))
        rnms.append(fp.to_mont(rn % CURVE_P, _FS))
        rnoks.append(rn < CURVE_P)
        valids.append(ok)

    pad = padded - n

    def arr(xs):
        return jnp.asarray(
            np.pad(fp.ints_to_limbs(xs), ((0, 0), (0, pad)), constant_values=0)
        )

    def digits(xs):
        return jnp.asarray(
            np.pad(_scalar_digits(xs), ((0, 0), (0, pad)), constant_values=0)
        )

    inputs = (
        digits(u1s), digits(u2s), arr(qxs), arr(qys), arr(rms), arr(rnms),
        jnp.asarray(np.pad(np.array(rnoks, dtype=bool), (0, pad))),
        jnp.asarray(np.pad(np.array(valids, dtype=bool), (0, pad))),
    )
    return np.asarray(_verify_device(*over_mesh(*inputs)))[:n]
