"""Differential tests for the Jacobian ladder (the fast Pallas path).

The Jacobian formulas are not complete, so beyond the random-point
differentials these tests drive the ladder through every structural edge
it claims to handle (identity accumulator, zero digits, identity picks)
and through the exceptional H ≡ 0 collisions it claims to FLAG — crafted
digit arrays force accumulator/table-pick collisions that are
cryptographically unreachable for honest signatures.

Everything runs the shared round logic eagerly (no jit) with short
ladders, so the suite stays fast; the assembled Pallas kernel is
exercised on real TPU by chip_smoke.py (a ``device=tpu`` node against a
host-verify node on the same block).
"""

import random

import numpy as np
import pytest

from upow_tpu.core import curve
from upow_tpu.core.constants import CURVE_N, CURVE_P
from upow_tpu.crypto import fp
from upow_tpu.crypto import p256

rng = random.Random(421)

_FS = fp.make_field(CURVE_P)
_R_INV = pow(1 << fp.R_BITS, -1, CURVE_P)


def _to_fl(xs, bound=CURVE_P):
    limbs = fp.ints_to_limbs(xs)
    return fp.l_wrap([np.asarray(limbs[i]) for i in range(fp.NUM_LIMBS)],
                     bound)


def _fl_ints(a):
    limbs = np.stack([np.asarray(x) for x in fp.l_canon(a, _FS)])
    return fp.limbs_to_ints(limbs)


def _jac_points(points):
    """affine (x,y) list (None = infinity) -> Jacobian FL point batch."""
    xs = [fp.to_mont(1 if p is None else p[0], _FS) for p in points]
    ys = [fp.to_mont(1 if p is None else p[1], _FS) for p in points]
    zs = [fp.to_mont(0 if p is None else 1, _FS) for p in points]
    return tuple(_to_fl(v) for v in (xs, ys, zs))


def _from_jac(P):
    """Jacobian FL point batch -> affine list via host inversion."""
    X, Y, Z = (_fl_ints(c) for c in P)
    out = []
    for x, y, z in zip(X, Y, Z):
        x, y, z = (v * _R_INV % CURVE_P for v in (x, y, z))
        if z == 0:
            out.append(None)
        else:
            zi = pow(z, -1, CURVE_P)
            out.append((x * zi * zi % CURVE_P,
                        y * zi * zi * zi % CURVE_P))
    return out


def _rand_pt():
    return curve.point_mul(rng.randrange(1, CURVE_N), curve.G)


def test_mont_reduce_sweep_margin_worst_case():
    """The Montgomery tail runs 1 pre-/2 post-sweeps on an int32 overflow
    budget (see fp._mont_reduce_rows' docstring).  Drive it with the worst
    representation the pipeline can produce — every limb at the post-sweep
    cap (2^13 + 2^4) — through mul, sqr and chained add/sub + mul, against
    exact bigints."""
    cap = (1 << fp.LIMB_BITS) + 22  # the stacked layout's true worst limb
    shape = (4,)
    # caps on limbs 0..16, ones above: every accumulation row still sums
    # near-cap products, while the VALUE (~2^260) stays inside the
    # pipeline's envelope (all-limbs-at-cap would encode ~2^273 — beyond
    # any reachable bound, where sweeps may legitimately drop carries)
    limb_vals = [cap] * 17 + [1] * (fp.NUM_LIMBS - 17)
    worst = fp.l_wrap([np.full(shape, v, np.int32) for v in limb_vals],
                      1 << 261)
    worst_val = sum(v << (fp.LIMB_BITS * i) for i, v in enumerate(limb_vals))
    r_inv = pow(1 << fp.R_BITS, -1, CURVE_P)

    got = _fl_ints(fp.l_mont_mul(worst, worst, _FS))
    want = worst_val * worst_val * r_inv % CURVE_P
    assert got == [want] * 4
    assert _fl_ints(fp.l_mont_sqr(worst, _FS)) == [want] * 4

    # stacked layout shares the same sweep budget
    import jax.numpy as jnp

    arr = jnp.asarray(np.stack([np.full(shape, v, np.int32)
                                for v in limb_vals]))
    got_s = fp.limbs_to_ints(np.asarray(
        fp.canon(fp.mont_mul(fp.wrap(arr, 1 << 261),
                             fp.wrap(arr, 1 << 261), _FS), _FS)))
    assert got_s == [want] * 4

    # chained: (worst + worst - small) * worst, exact vs bigint
    small = fp.l_wrap([np.full(shape, 3, np.int32)] +
                      [np.zeros(shape, np.int32)] * (fp.NUM_LIMBS - 1),
                      CURVE_P)
    t = fp.l_sub(fp.l_add(worst, worst), small, _FS)
    got2 = _fl_ints(fp.l_mont_mul(t, worst, _FS))
    want2 = (2 * worst_val - 3) * worst_val * r_inv % CURVE_P
    assert got2 == [want2] * 4


# --- formulas -------------------------------------------------------------

def test_jac_dbl_matches_oracle():
    pts = [_rand_pt() for _ in range(4)] + [curve.G, None]
    P = _jac_points(pts)
    got = _from_jac(p256._jac_clamp(p256._jac_dbl(P)))
    want = [curve.point_add(p, p) if p is not None else None for p in pts]
    assert got == want
    # chained doublings stay within bounds and exact: 4x dbl == [16]P
    cur = P
    for _ in range(4):
        cur = p256._jac_clamp(p256._jac_dbl(cur))
    assert _from_jac(cur) == [
        curve.point_mul(16, p) if p is not None else None for p in pts]


def test_jac_madd_matches_oracle_and_flags():
    P1s = [_rand_pt() for _ in range(3)]
    Q = _rand_pt()
    neg_last = (P1s[-1][0], CURVE_P - P1s[-1][1])
    # generic adds, plus P1 == P2 (exceptional) and P1 == -P2 (H=0, Z3=0)
    p1 = _jac_points(P1s + [Q, neg_last])
    p2s = [_rand_pt() for _ in range(3)] + [Q, P1s[-1]]
    x2s = [fp.to_mont(pt[0], _FS) for pt in p2s]
    y2s = [fp.to_mont(pt[1], _FS) for pt in p2s]
    res, H = p256._jac_madd(p1, _to_fl(x2s), _to_fl(y2s))
    h0 = list(np.asarray(fp.l_is_zero_mod_p(H, _FS)))
    assert h0 == [False, False, False, True, True]
    got = _from_jac(p256._jac_clamp(res))
    for i in range(3):
        assert got[i] == curve.point_add(P1s[i], p2s[i])
    # P1 == -P2: the formula yields Z3 = 2*Z1*H = 0 -> identity (correct)
    assert got[4] is None


def test_jac_add_matches_oracle_and_flags():
    A = [_rand_pt() for _ in range(3)]
    B = [_rand_pt() for _ in range(3)]
    same = _rand_pt()
    neg = (same[0], CURVE_P - same[1])
    p1 = _jac_points(A + [same, same])
    # give the second operand a non-trivial Z: lift via dbl of [k/2]-ish
    p2 = _jac_points(B + [same, neg])
    res, H = p256._jac_add(p1, p2)
    h0 = list(np.asarray(fp.l_is_zero_mod_p(H, _FS)))
    assert h0 == [False, False, False, True, True]
    got = _from_jac(p256._jac_clamp(res))
    for i in range(3):
        assert got[i] == curve.point_add(A[i], B[i])
    assert got[4] is None  # P1 == -P2 -> Z3 = stuff * H = 0

    # second operand with Z != 1 (table entries are real Jacobian points)
    dblB = tuple(fp.l_wrap(c.limbs, p256._JB)
                 for c in p256._jac_clamp(p256._jac_dbl(_jac_points(B))))
    res2, _ = p256._jac_add(_jac_points(A), dblB)
    got2 = _from_jac(p256._jac_clamp(res2))
    for i in range(3):
        assert got2[i] == curve.point_add(A[i], curve.point_add(B[i], B[i]))


def test_jac_identity_is_dbl_fixed_point():
    """(R, R, 0) — the ladder's identity encoding — must be an exact
    value-level fixed point of the doubling program."""
    I = p256._jac_identity(np.zeros((2,), np.int32))
    out = p256._jac_clamp(p256._jac_dbl(I))
    assert _fl_ints(out[0]) == [_FS.r_mod_p] * 2
    assert _fl_ints(out[1]) == [_FS.r_mod_p] * 2
    assert _fl_ints(out[2]) == [0] * 2


def test_jac_qtable_matches_scalar_mults():
    k1, k2 = rng.randrange(1, CURVE_N), rng.randrange(1, CURVE_N)
    Q1, Q2 = curve.point_mul(k1, curve.G), curve.point_mul(k2, curve.G)
    qx = _to_fl([fp.to_mont(Q1[0], _FS), fp.to_mont(Q2[0], _FS)])
    qy = _to_fl([fp.to_mont(Q1[1], _FS), fp.to_mont(Q2[1], _FS)])
    entries = p256._jac_qtable(qx, qy)
    assert len(entries) == 15
    for k, e in enumerate(entries, start=1):
        assert _from_jac(e) == [curve.point_mul(k, Q1),
                                curve.point_mul(k, Q2)]


# --- the ladder round logic (short crafted ladders, eager) -----------------

def _run_ladder(d1_rows, d2_rows, Q, r_vals=None, rn_vals=None):
    """d1/d2: list of per-round digit lists; Q: affine pubkey point.
    Returns (ok, exc, expected_points) where expected is computed via the
    host oracle from the digit values."""
    n_rounds = len(d1_rows)
    n = len(d1_rows[0])
    d1 = np.asarray(d1_rows, dtype=np.int32)
    d2 = np.asarray(d2_rows, dtype=np.int32)
    qx = np.stack([fp.int_to_limbs(fp.to_mont(Q[0], _FS))] * n, axis=1)
    qy = np.stack([fp.int_to_limbs(fp.to_mont(Q[1], _FS))] * n, axis=1)
    if r_vals is None:
        r_vals = [1] * n
    rm = fp.ints_to_limbs([fp.to_mont(r % CURVE_P, _FS) for r in r_vals])
    rn = [(r + CURVE_N) % CURVE_P for r in r_vals]
    rnm = fp.ints_to_limbs([fp.to_mont(v, _FS) for v in rn])
    rn_ok = np.asarray([r + CURVE_N < CURVE_P for r in r_vals]) \
        if rn_vals is None else np.asarray(rn_vals)
    valid = np.ones(n, dtype=bool)
    ok, exc = p256._jac_verify_eager(d1, d2, qx, qy, rm, rnm, rn_ok, valid,
                                     n_rounds=n_rounds)
    expected = []
    for j in range(n):
        u1 = u2 = 0
        for k in range(n_rounds):
            u1 = (u1 << 4) + int(d1[k, j])
            u2 = (u2 << 4) + int(d2[k, j])
        pt = curve.point_add(curve.point_mul(u1, curve.G),
                             curve.point_mul(u2, Q))
        expected.append(pt)
    return ok, exc, expected


def test_short_ladder_verdicts_match_oracle():
    """Random 3-round ladders: accept iff x(u1 G + u2 Q) == r."""
    Q = _rand_pt()
    n = 12
    d1 = [[rng.randrange(16) for _ in range(n)] for _ in range(3)]
    d2 = [[rng.randrange(16) for _ in range(n)] for _ in range(3)]
    # lane 0: all-zero digits -> identity -> reject
    for row in d1:
        row[0] = 0
    for row in d2:
        row[0] = 0
    # compute expected points first, then set r = x(R) on even lanes
    _, _, expected = _run_ladder(d1, d2, Q)
    r_vals = []
    for j, pt in enumerate(expected):
        if pt is not None and j % 2 == 0:
            r_vals.append(pt[0])          # correct x -> accept
        else:
            r_vals.append((1 if pt is None else pt[0] + 1) % CURVE_P)
    ok, exc, _ = _run_ladder(d1, d2, Q, r_vals=r_vals)
    assert not exc.any()
    for j, pt in enumerate(expected):
        want = pt is not None and j % 2 == 0
        assert bool(ok[j]) == want, (j, pt)


def test_ladder_collision_lanes_are_flagged():
    """Crafted digits that collide the accumulator with a table pick must
    set the exception flag (the host-fallback trigger), and never a
    verdict of True off a garbage point."""
    G = curve.G
    # Q = G: after the G-add of round 0 the accumulator is [j]G; a Q-pick
    # of digit j collides (P1 == P2, needs doubling).
    d1 = [[3, 7, 0, 5]]
    d2 = [[3, 7, 5, 0]]
    ok, exc, _ = _run_ladder(d1, d2, G)
    assert list(exc) == [True, True, False, False]
    # Q = -G: same digits give P1 == -P2 (result would be the identity).
    negG = (G[0], CURVE_P - G[1])
    ok, exc, _ = _run_ladder(d1, d2, negG)
    assert list(exc) == [True, True, False, False]
    # multi-round: acc = [16]G meets Q-pick [1]*(-[16]G)
    neg16 = curve.point_mul(16, G)
    neg16 = (neg16[0], CURVE_P - neg16[1])
    d1 = [[1, 1], [0, 0]]
    d2 = [[0, 0], [1, 0]]
    ok, exc, _ = _run_ladder(d1, d2, neg16)
    assert list(exc) == [True, False]


def test_ladder_identity_reentry_paths():
    """u1-only, u2-only and staggered-start lanes all take the acc_inf
    select paths; verdicts still match the oracle."""
    Q = _rand_pt()
    d1 = [[0, 9, 0, 2], [4, 0, 0, 0]]
    d2 = [[5, 0, 0, 0], [0, 3, 7, 0]]
    _, _, expected = _run_ladder(d1, d2, Q)
    r_vals = [pt[0] for pt in expected]
    ok, exc, _ = _run_ladder(d1, d2, Q, r_vals=r_vals)
    assert not exc.any()
    assert list(ok) == [True, True, True, True]


def test_ladder_rn_wraparound_acceptance():
    """The X ≡ (r+n)·Z² branch: points with x(R) >= n have density ~2⁻³²
    (unfindable by search), so drive the congruence directly — r is
    crafted as x(R) − n, which only the wraparound branch accepts, and
    only when rn_ok says r + n < p."""
    k = 0x1a7
    pt = curve.point_mul(k, curve.G)
    digits = [(k >> 8) & 0xF, (k >> 4) & 0xF, k & 0xF]
    d1 = [[d, d] for d in digits]
    d2 = [[0, 0]] * 3
    r = (pt[0] - CURVE_N) % CURVE_P
    ok, exc, _ = _run_ladder(d1, d2, curve.G, r_vals=[r, r],
                             rn_vals=[True, False])
    assert not exc.any()
    assert list(ok) == [True, False]


def test_ladder_fuzz_random_digits_vs_oracle():
    """Randomized 4-round ladders across many lanes:
    verdicts must match the oracle point exactly, with zero spurious
    exception flags (the digit space is tiny, so collisions would need
    acc ≡ pick mod n — impossible below wraparound)."""
    Q = _rand_pt()
    n, rounds = 24, 4
    d1 = [[rng.randrange(16) for _ in range(n)] for _ in range(rounds)]
    d2 = [[rng.randrange(16) for _ in range(n)] for _ in range(rounds)]
    _, _, expected = _run_ladder(d1, d2, Q)
    r_vals = []
    for j, pt in enumerate(expected):
        if pt is None:
            r_vals.append(1)
        elif j % 3 == 0:
            r_vals.append((pt[0] + 1) % CURVE_P)   # wrong x -> reject
        else:
            r_vals.append(pt[0])
    ok, exc, _ = _run_ladder(d1, d2, Q, r_vals=r_vals)
    assert not exc.any()
    for j, pt in enumerate(expected):
        want = pt is not None and j % 3 != 0
        assert bool(ok[j]) == want, (j, pt)


def test_full_ladder_real_signatures_eager():
    """The eager twin at full 256-bit scale with real signature-derived
    digits — the exact data shape the Pallas kernel sees on TPU."""
    import hashlib

    from upow_tpu.crypto import fp as _fp

    msgs, sigs, pubs = [], [], []
    for i in range(8):
        d, pub = curve.keygen(rng=6200 + i)
        m = bytes([i]) * 12
        r, s = curve.sign(m, d)
        if i % 3 == 2:
            s = (s + 1) % CURVE_N
        msgs.append(m)
        sigs.append((r, s))
        pubs.append(pub)
    want = [curve.verify(sig, m, pk) for sig, m, pk in zip(sigs, msgs, pubs)]

    u1s, u2s, rms, rnms, rn_oks = [], [], [], [], []
    for m, (r, s) in zip(msgs, sigs):
        z = int.from_bytes(hashlib.sha256(m).digest(), "big")
        sw = pow(s, -1, CURVE_N)
        u1s.append(z * sw % CURVE_N)
        u2s.append(r * sw % CURVE_N)
        rms.append(fp.to_mont(r, _FS))
        rnms.append(fp.to_mont((r + CURVE_N) % CURVE_P, _FS))
        rn_oks.append(r + CURVE_N < CURVE_P)

    def digits(xs):
        return np.asarray(
            [[(x >> (4 * (63 - k))) & 15 for x in xs] for k in range(64)],
            dtype=np.int32)

    d1, d2 = digits(u1s), digits(u2s)
    # the device extractor must agree with the host split
    limbs = _fp.ints_to_limbs(u1s)
    assert np.array_equal(np.asarray(p256._digits_from_limbs(limbs)), d1)
    qx = _fp.ints_to_limbs([fp.to_mont(pk[0], _FS) for pk in pubs])
    qy = _fp.ints_to_limbs([fp.to_mont(pk[1], _FS) for pk in pubs])
    rm = _fp.ints_to_limbs(rms)
    rnm = _fp.ints_to_limbs(rnms)
    ok, exc = p256._jac_verify_eager(
        d1, d2, qx, qy, rm, rnm, np.asarray(rn_oks),
        np.ones(len(msgs), dtype=bool))
    assert not exc.any()
    assert list(ok) == want


# --- what Python traces and what the compilers are handed -----------------

def _count_equations(jaxpr, seen):
    """(traced, inline): equations of ``jaxpr`` and below, a sub-jaxpr
    bound more than once counted once / at every binding (a loop's body
    is one binding: the compiler is handed it once)."""
    traced = inline = 0
    for eqn in jaxpr.eqns:
        traced += 1
        inline += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if not hasattr(sub, "eqns"):
                    continue
                if id(sub) not in seen:
                    t, seen[id(sub)] = _count_equations(sub, seen)
                    traced += t
                inline += seen[id(sub)]
    return traced, inline


def test_equation_budget_of_the_dispatched_program():
    """A ``device=tpu`` node's first dispatch of a shape is Python
    tracing the program, Python lowering it and the compilers' own time,
    and all three follow these counts (PERF.md section 6, PR 46: 493,446
    equations traced and 439,371 handed to Mosaic inline took the chip's
    host five minutes).  The program a node dispatches at 8,192 lanes:
    the field arithmetic traced once, the Q table's additions and the
    round's doublings one loop body each."""
    import jax
    import jax.numpy as jnp

    packed = jax.ShapeDtypeStruct((42, 8192), jnp.uint32)
    prep = lambda x: p256._scalar_prep(*p256._unpack_fused(x))
    ladder_args = jax.eval_shape(prep, packed)
    prep_traced, prep_inline = _count_equations(
        jax.make_jaxpr(prep)(packed).jaxpr, {})
    ladder_traced, ladder_inline = _count_equations(
        jax.make_jaxpr(lambda *a: p256._verify_device_pallas_jac(
            *a, tile=p256._pick_tile(8192)))(*ladder_args).jaxpr, {})
    print(f"traced by Python: prep {prep_traced} ladder {ladder_traced}; "
          f"inline: prep {prep_inline} ladder {ladder_inline}")
    assert ladder_traced <= 35_000
    assert prep_traced + ladder_traced <= 90_000
    # inline, the ladder was 439,371 with its table and doublings unrolled
    assert ladder_inline <= 110_000
    # the prep was 55,061 either way; the inversion step's four squarings
    # are the unrolled part that is left (a loop costs the device time)
    assert prep_inline <= 25_000


def test_unlike_batch_sizes_in_one_padded_shape_share_a_jit_entry(monkeypatch):
    """100 and 120 signatures both pad to 128 lanes: the dispatch hands
    jit the same shape and the same static tile, so the second compiles
    nothing; 130 signatures are the next shape."""
    import jax
    import jax.numpy as jnp

    d, pub = curve.keygen(rng=7300)
    sig = curve.sign(b"shape", d)
    digest = b"\x11" * 32

    @jax.jit
    def stand_in(packed):
        return jnp.zeros((2, packed.shape[1]), dtype=bool)

    tiles = []

    def fused(packed, tile):
        tiles.append(tile)
        return stand_in(packed)

    monkeypatch.setattr(p256, "_prep_and_verify_pallas_jac", fused)
    for n in (100, 120):
        got = p256.verify_batch_prehashed(
            [digest] * n, [sig] * n, [pub] * n, backend="pallas",
            scalar_prep="device")
        assert got.shape == (n,)
    assert stand_in._cache_size() == 1 and tiles == [128, 128]
    p256.verify_batch_prehashed([digest] * 130, [sig] * 130, [pub] * 130,
                                backend="pallas", scalar_prep="device")
    assert stand_in._cache_size() == 2 and tiles[-1] == 256


def test_pallas_backend_takes_the_device_prep_only():
    d, pub = curve.keygen(rng=7301)
    with pytest.raises(ValueError, match="device scalar prep"):
        p256.verify_batch_prehashed(
            [b"\x22" * 32], [curve.sign(b"x", d)], [pub],
            backend="pallas", scalar_prep="host")


# --- wrapper fallback plumbing --------------------------------------------

def test_exception_lanes_fall_back_to_host_oracle(monkeypatch):
    """verify_batch_prehashed must re-verify flagged lanes on the host and
    splice the oracle verdicts over the kernel output."""
    import hashlib

    msgs, sigs, pubs = [], [], []
    for i in range(5):
        d, pub = curve.keygen(rng=7100 + i)
        m = bytes([i]) * 9
        r, s = curve.sign(m, d)
        if i == 3:
            s = (s + 1) % CURVE_N  # invalid lane
        msgs.append(m)
        sigs.append((r, s))
        pubs.append(pub)
    digests = [hashlib.sha256(m).digest() for m in msgs]
    want = [curve.verify(sig, m, pk) for sig, m, pk in zip(sigs, msgs, pubs)]

    calls = []

    def fake_kernel(packed, tile):
        n = packed.shape[1]
        # kernel "flags" lanes 1 and 3 and returns garbage verdicts there
        ok = np.zeros(n, dtype=bool)
        exc = np.zeros(n, dtype=bool)
        ok[0], ok[2], ok[4] = want[0], want[2], want[4]
        ok[1] = not want[1]
        exc[1], exc[3] = True, True
        return np.stack([ok, exc])

    real_host = p256._host_verify_prehashed

    def spy_host(*a):
        calls.append(a)
        return real_host(*a)

    monkeypatch.setattr(p256, "_prep_and_verify_pallas_jac", fake_kernel)
    monkeypatch.setattr(p256, "_host_verify_prehashed", spy_host)
    got = p256.verify_batch_prehashed(digests, sigs, pubs, pad_block=128,
                                      backend="pallas",
                                      scalar_prep="device")
    assert list(got) == want
    assert len(calls) == 2  # exactly the flagged lanes


def test_host_verify_prehashed_matches_curve_verify():
    import hashlib

    d, pub = curve.keygen(rng=8123)
    m = b"host oracle parity"
    r, s = curve.sign(m, d)
    z = int.from_bytes(hashlib.sha256(m).digest(), "big")
    assert p256._host_verify_prehashed(z, r, s, *pub) is True
    assert p256._host_verify_prehashed(z, r, (s + 1) % CURVE_N, *pub) is False
    assert p256._host_verify_prehashed(z, 0, s, *pub) is False
    assert p256._host_verify_prehashed(z, r, s, 123, 456) is False
    # (r, n-s) malleability twin accepted, like the device path
    assert p256._host_verify_prehashed(z, r, CURVE_N - s, *pub) is True
