"""Differential tests: TPU batched P-256 verify vs the pure-Python curve.

The host implementation in upow_tpu.core.curve is itself tested against
OpenSSL in test_core_tx.py; here it serves as the oracle for the limb
field arithmetic, the complete-addition formulas, and the full batched
verdicts — including adversarial/invalid signatures (the consensus
surface: transaction_input.py:100-109 decides block validity).
"""

import os
import random

import numpy as np
import pytest

from upow_tpu.core import curve
from upow_tpu.core.constants import CURVE_N, CURVE_P
from upow_tpu.crypto import fp
from upow_tpu.crypto import p256

rng = random.Random(99)

_FS = fp.make_field(CURVE_P)


def _fe(xs) -> fp.FE:
    return fp.from_ints(xs, _FS)


def _canon_ints(x: fp.FE):
    return fp.limbs_to_ints(np.asarray(fp.canon(x, _FS)))


# --- field arithmetic -----------------------------------------------------

def _rand_fe():
    return rng.randrange(CURVE_P)


def test_fp_mont_mul_matches_bigint():
    xs = [_rand_fe() for _ in range(8)] + [0, 1, CURVE_P - 1]
    ys = [_rand_fe() for _ in range(8)] + [CURVE_P - 1, 1, CURVE_P - 1]
    a = _fe([fp.to_mont(x, _FS) for x in xs])
    b = _fe([fp.to_mont(y, _FS) for y in ys])
    got = _canon_ints(fp.mont_mul(a, b, _FS))
    want = [fp.to_mont(x * y % CURVE_P, _FS) for x, y in zip(xs, ys)]
    assert got == want


def test_fp_add_sub_edges_and_chains():
    xs = [0, 1, CURVE_P - 1, CURVE_P - 1, 12345, 0]
    ys = [0, CURVE_P - 1, CURVE_P - 1, 1, 54321, 1]
    a, b = _fe(xs), _fe(ys)
    assert _canon_ints(fp.add(a, b)) == [(x + y) % CURVE_P for x, y in zip(xs, ys)]
    assert _canon_ints(fp.sub(a, b, _FS)) == [(x - y) % CURVE_P for x, y in zip(xs, ys)]
    # chained lazy ops stay exact mod p: ((a+b)*2 - b) * (a - b) * R^-1
    t = fp.sub(fp.add(fp.add(a, b), fp.add(a, b)), b, _FS)
    u = fp.sub(a, b, _FS)
    got = _canon_ints(fp.mont_mul(t, u, _FS))
    want = [
        ((2 * (x + y) - y) * (x - y) * pow(1 << fp.R_BITS, -1, CURVE_P)) % CURVE_P
        for x, y in zip(xs, ys)
    ]
    assert got == want


def test_fp_sub_deep_nesting_keeps_bounds_finite():
    """Repeated sub/add chains must stay exact and within the bound cap."""
    xs = [_rand_fe() for _ in range(4)]
    ys = [_rand_fe() for _ in range(4)]
    a, b = _fe(xs), _fe(ys)
    t, want = a, list(xs)
    for _ in range(6):
        t = fp.sub(fp.add(t, t), b, _FS)
        want = [(2 * w - y) % CURVE_P for w, y in zip(want, ys)]
    # wash the bound back down through a multiply by R (== identity)
    one_r2 = _fe([_FS.r2_mod_p] * 4)
    t = fp.mont_mul(t, one_r2, _FS)
    want = [w * (1 << fp.R_BITS) % CURVE_P for w in want]
    assert _canon_ints(t) == want


# --- complete point addition ---------------------------------------------

def _to_proj_batch(points):
    """affine (x,y) list (None = infinity) -> Proj of Montgomery FEs."""
    xs = [fp.to_mont(0 if p is None else p[0], _FS) for p in points]
    ys = [fp.to_mont(1 if p is None else p[1], _FS) for p in points]
    zs = [fp.to_mont(0 if p is None else 1, _FS) for p in points]
    return tuple(_fe(v) for v in (xs, ys, zs))


def _from_proj_batch(P):
    """device Proj -> affine (x, y) list via host inversion (None = inf)."""
    X, Y, Z = (_canon_ints(c) for c in P)
    out = []
    rinv = pow(1 << fp.R_BITS, -1, CURVE_P)
    for x, y, z in zip(X, Y, Z):
        x, y, z = (v * rinv % CURVE_P for v in (x, y, z))
        if z == 0:
            out.append(None)
        else:
            zi = pow(z, -1, CURVE_P)
            out.append((x * zi % CURVE_P, y * zi % CURVE_P))
    return out


def test_complete_add_random_and_edge_cases():
    G = curve.G
    P1 = curve.point_mul(rng.randrange(1, CURVE_N), G)
    P2 = curve.point_mul(rng.randrange(1, CURVE_N), G)
    neg_P1 = (P1[0], CURVE_P - P1[1])
    cases = [
        (P1, P2),          # generic
        (P1, P1),          # doubling through the *addition* formula
        (P1, neg_P1),      # inverse -> infinity
        (None, P1),        # identity left
        (P1, None),        # identity right
        (None, None),      # identity both
        (G, G),            # doubling the generator
        (neg_P1, P1),      # inverse, flipped
    ]
    A = _to_proj_batch([c[0] for c in cases])
    B = _to_proj_batch([c[1] for c in cases])
    b_m = fp.const(p256._B_M, len(cases), CURVE_P)
    got = _from_proj_batch(p256._point_add_complete(A, B, b_m))
    want = [curve.point_add(a, b) for a, b in cases]
    assert got == want


def test_complete_add_chain_matches_scalar_mul():
    """Fold the addition formula 16 times; compare against point_mul."""
    G = curve.G
    P = _to_proj_batch([G])
    b_m = fp.const(p256._B_M, 1, CURVE_P)
    acc = _to_proj_batch([None])
    for _ in range(16):
        acc = p256._clamp_point(p256._point_add_complete(acc, P, b_m))
    assert _from_proj_batch(acc) == [curve.point_mul(16, G)]


# --- full verify ----------------------------------------------------------

def test_verify_batch_valid_and_invalid():
    msgs, sigs, pubs, expect = [], [], [], []

    for i in range(6):
        d, pub = curve.keygen(rng=rng.randrange(1, CURVE_N))
        msg = bytes([i]) * (i + 7)
        r, s = curve.sign(msg, d)
        msgs.append(msg)
        sigs.append((r, s))
        pubs.append(pub)
        expect.append(True)

    d, pub = curve.keygen(rng=rng.randrange(1, CURVE_N))
    r, s = curve.sign(b"good message", d)
    # tampered message
    msgs.append(b"evil message"); sigs.append((r, s)); pubs.append(pub); expect.append(False)
    # tampered r / s
    msgs.append(b"good message"); sigs.append(((r + 1) % CURVE_N, s)); pubs.append(pub); expect.append(False)
    msgs.append(b"good message"); sigs.append((r, (s + 1) % CURVE_N)); pubs.append(pub); expect.append(False)
    # wrong key
    _, pub2 = curve.keygen(rng=rng.randrange(1, CURVE_N))
    msgs.append(b"good message"); sigs.append((r, s)); pubs.append(pub2); expect.append(False)
    # out-of-range r/s
    msgs.append(b"good message"); sigs.append((0, s)); pubs.append(pub); expect.append(False)
    msgs.append(b"good message"); sigs.append((r, CURVE_N)); pubs.append(pub); expect.append(False)
    # pubkey not on curve
    msgs.append(b"good message"); sigs.append((r, s)); pubs.append((123, 456)); expect.append(False)
    # (r, n-s) malleability twin is a valid signature under plain ECDSA
    msgs.append(b"good message"); sigs.append((r, CURVE_N - s)); pubs.append(pub); expect.append(True)
    # the original, to close the batch
    msgs.append(b"good message"); sigs.append((r, s)); pubs.append(pub); expect.append(True)

    got = p256.verify_batch(msgs, sigs, pubs)
    oracle = [
        curve.verify(sig, m, p) if isinstance(p, tuple) else False
        for sig, m, p in zip(sigs, msgs, pubs)
    ]
    assert list(got) == oracle == expect


def test_verify_batch_empty():
    assert p256.verify_batch([], [], []).shape == (0,)


# --- device-side scalar prep ----------------------------------------------

def test_digits_from_limbs_matches_host():
    xs = [rng.randrange(CURVE_N) for _ in range(12)] + [0, 1, CURVE_N - 1]
    limbs = np.asarray(fp.ints_to_limbs(xs))
    got = np.asarray(p256._digits_from_limbs(limbs))
    want = p256._scalar_digits(xs)
    assert np.array_equal(got, want)


def test_mod_n_inversion_matches_pow():
    ns = p256._NS
    xs = [rng.randrange(1, CURVE_N) for _ in range(6)] + [1, CURVE_N - 1]
    x_m = fp.FE(np.asarray(fp.ints_to_limbs([fp.to_mont(x, ns) for x in xs])),
                p256._SCALAR_BOUND)
    inv_m = p256._mod_n_inv_mont(x_m)
    got = fp.limbs_to_ints(np.asarray(fp.canon(inv_m, ns)))
    want = [fp.to_mont(pow(x, -1, CURVE_N), ns) for x in xs]
    assert got == want


@pytest.mark.skipif(not os.environ.get("UPOW_SLOW_TESTS"),
                    reason="composed prep+ladder program is a ~1 min CPU "
                           "execute; set UPOW_SLOW_TESTS=1 to include "
                           "(the TPU path is exercised by chip_smoke.py)")
def test_device_scalar_prep_full_differential():
    """scalar_prep="device" (the TPU production path: inversion, u1/u2,
    Montgomery conversion, on-curve and digit extraction all on device)
    must agree with host prep and the host curve oracle — including
    encodings the host path short-circuits before the device sees."""
    from upow_tpu.core.constants import CURVE_GX, CURVE_GY

    cases = []
    for i in range(10):
        d, pub = curve.keygen(rng=900 + i)
        m = bytes([i]) * 11
        r, s = curve.sign(m, d)
        cases.append((m, (r, s), pub))
    d0, pub0 = curve.keygen(rng=77)
    m0 = b"prep"
    r0, s0 = curve.sign(m0, d0)
    cases += [
        (m0, (0, s0), pub0),
        (m0, (r0, 0), pub0),
        (m0, (CURVE_N, s0), pub0),
        (m0, (r0, CURVE_N + 5), pub0),
        (m0, (r0, s0), (0, 0)),
        (m0, (r0, s0), (CURVE_GX, CURVE_GY + 1)),   # off-curve
        (m0, (r0, s0), (CURVE_P + 1, 1)),           # coordinate >= p, off-curve
        (m0, (r0, CURVE_N - s0), pub0),             # malleability twin: valid
        # consensus parity: fastecdsa computes mod p, so (x+p, y) encodes
        # the same on-curve point and the reference ACCEPTS it — both our
        # paths must too (host reduces via to_mont/is_on_curve, device via
        # Montgomery reduction; coord() handles the >= 2^256 packing)
        (m0, (r0, s0), (pub0[0] + CURVE_P, pub0[1])),
        (m0, (r0, s0), (pub0[0], pub0[1] + CURVE_P)),
        # hostile API inputs: negative / oversized ints must yield False,
        # not an exception (the host path's documented short-circuit)
        (m0, (-1, s0), pub0),
        (m0, (r0, 1 << 280), pub0),
        (m0, (r0, s0), (-pub0[0], pub0[1])),
    ]
    msgs = [c[0] for c in cases]
    sigs = [c[1] for c in cases]
    pubs = [c[2] for c in cases]
    import hashlib

    digests = [hashlib.sha256(m).digest() for m in msgs]
    # oversized digest (sha512-length): device path must reduce mod n like
    # the host's z*w % n, not raise
    digests.append(hashlib.sha512(m0).digest())
    sigs.append((r0, s0)); pubs.append(pub0)
    want = [curve.verify(sig, m, pk) for sig, m, pk in zip(sigs, msgs, pubs)]
    want.append(bool(p256.verify_batch_prehashed(
        [hashlib.sha512(m0).digest()], [(r0, s0)], [pub0], pad_block=8,
        backend="jnp", scalar_prep="host")[0]))
    got = p256.verify_batch_prehashed(digests, sigs, pubs, pad_block=8,
                                      backend="jnp", scalar_prep="device")
    assert list(got) == want


# --- limb-list layout (Pallas kernel data path) ----------------------------
# The list ops are plain functions; testing them directly covers the
# kernel's field arithmetic without an interpret-mode pallas_call (the
# Jacobian kernel's ~100,000 inline operations take XLA:CPU more than
# half an hour).  The assembled kernel itself is exercised on real TPU
# by chip_smoke.py and compiled for a described v5e by
# tests/test_tpu_compile.py.

def _to_fl(xs, bound):
    limbs = fp.ints_to_limbs(xs)
    return fp.l_wrap([np.asarray(limbs[i]) for i in range(fp.NUM_LIMBS)],
                     bound)


def _fl_ints(a, fs=None):
    limbs = np.stack([np.asarray(x) for x in fp.l_canon(a, fs or _FS)])
    return fp.limbs_to_ints(limbs)


def test_limb_list_field_ops_match_bigint():
    xs = [rng.randrange(CURVE_P) for _ in range(6)] + [0, 1, CURVE_P - 1]
    ys = [rng.randrange(CURVE_P) for _ in range(6)] + [CURVE_P - 1, 1,
                                                       CURVE_P - 1]
    a = _to_fl([fp.to_mont(x, _FS) for x in xs], CURVE_P)
    b = _to_fl([fp.to_mont(y, _FS) for y in ys], CURVE_P)
    mont = lambda v: fp.to_mont(v % CURVE_P, _FS)
    assert _fl_ints(fp.l_mont_mul(a, b, _FS)) == [
        mont(x * y) for x, y in zip(xs, ys)]
    assert _fl_ints(fp.l_add(a, b)) == [
        (mont(x) + mont(y)) % CURVE_P for x, y in zip(xs, ys)]
    assert _fl_ints(fp.l_sub(a, b, _FS)) == [
        (mont(x) - mont(y)) % CURVE_P for x, y in zip(xs, ys)]
    zero = _to_fl([0, CURVE_P], CURVE_P + 1)
    nz = _to_fl([1, CURVE_P - 1], CURVE_P)
    assert list(np.asarray(fp.l_is_zero_mod_p(zero, _FS))) == [True, True]
    assert list(np.asarray(fp.l_is_zero_mod_p(nz, _FS))) == [False, False]


def test_limb_list_mont_sqr_matches_mul():
    xs = [rng.randrange(CURVE_P) for _ in range(8)] + [0, 1, CURVE_P - 1]
    a = _to_fl([fp.to_mont(x, _FS) for x in xs], CURVE_P)
    got = _fl_ints(fp.l_mont_sqr(a, _FS))
    want = _fl_ints(fp.l_mont_mul(a, a, _FS))
    assert got == want
    # lazy (unreduced) inputs square correctly too
    b = fp.l_add(a, a)
    assert _fl_ints(fp.l_mont_sqr(b, _FS)) == _fl_ints(fp.l_mont_mul(b, b, _FS))


# the ladder multiplies canonical limbs whose value may reach the loop
# bound 64p; the worst limb tuple a caller can hand over is every limb
# at 2^13 - 1 under that value
_EDGE_VALUES = [0, 1, CURVE_P - 1, CURVE_P, 64 * CURVE_P - 1,
                (1 << 262) - 1]


@pytest.mark.parametrize("fn,field,shape", [
    ("mul", "p", (16,)),
    ("sqr", "p", (16,)),
    ("reduce", "p", (16,)),
    ("mul", "p", (2, 8)),     # a kernel tile's rank: limbs are 2-D there
    ("mul", "n", (16,)),
    ("sqr", "n", (16,)),
])
def test_traced_once_matches_inlined(fn, field, shape):
    """The field arithmetic of the ladder is traced once a signature
    (function, limb shape, field) and bound at every call; numpy limbs
    still run the Python loops as they stand.  Both routes must give the
    same limbs, bit for bit, and the value Montgomery's rule says."""
    import jax.numpy as jnp

    fs = _FS if field == "p" else p256._NS
    n = int(np.prod(shape))
    xs = _EDGE_VALUES + [rng.randrange(64 * CURVE_P) for _ in range(n - 6)]
    ys = xs[3:] + xs[:3]
    a = [np.asarray(l).reshape(shape) for l in fp.ints_to_limbs(xs)]
    b = [np.asarray(l).reshape(shape) for l in fp.ints_to_limbs(ys)]
    r_inv = pow(1 << fp.R_BITS, -1, fs.p)
    if fn == "reduce":
        # the rows of a product, as _mont_mul_limbs hands them over
        rows = [sum(a[i] * b[k - i] for i in range(fp.NUM_LIMBS)
                    if 0 <= k - i < fp.NUM_LIMBS)
                for k in range(2 * fp.NUM_LIMBS - 1)]
        inlined = fp._mont_reduce_rows(tuple(rows), fs=fs)
        traced = fp._mont_reduce_rows(tuple(map(jnp.asarray, rows)), fs=fs)
        want = [x * y * r_inv % fs.p for x, y in zip(xs, ys)]
    elif fn == "mul":
        inlined = fp._mont_mul_limbs(tuple(a), tuple(b), fs=fs)
        traced = fp._mont_mul_limbs(tuple(map(jnp.asarray, a)),
                                    tuple(map(jnp.asarray, b)), fs=fs)
        want = [x * y * r_inv % fs.p for x, y in zip(xs, ys)]
    else:
        inlined = fp._mont_sqr_limbs(tuple(a), fs=fs)
        traced = fp._mont_sqr_limbs(tuple(map(jnp.asarray, a)), fs=fs)
        want = [x * x * r_inv % fs.p for x in xs]
    assert all(isinstance(l, np.ndarray) for l in inlined)
    assert not any(isinstance(l, np.ndarray) for l in traced)
    for l_inl, l_tr in zip(inlined, traced):
        assert np.array_equal(l_inl, np.asarray(l_tr))
    got = _fl_ints(fp.l_wrap([l.reshape(-1) for l in inlined], 3 * fs.p),
                   fs)
    assert got == want


def test_traced_once_is_one_trace_a_signature():
    """Every product of one limb shape and field binds the same jaxpr,
    in one program and in the next; another field has its own."""
    import jax
    import jax.numpy as jnp

    a = tuple(jnp.zeros((24,), jnp.int32) for _ in range(fp.NUM_LIMBS))

    def bound_jaxprs(fs, products):
        def program(x):
            for _ in range(products):
                x = fp._mont_mul_limbs(x, a, fs=fs)
            return x

        eqns = jax.make_jaxpr(program)(a).jaxpr.eqns
        assert len(eqns) == products    # a product is one equation
        return {id(e.params["jaxpr"]) for e in eqns}

    first = bound_jaxprs(_FS, 3)
    assert len(first) == 1
    assert bound_jaxprs(_FS, 5) == first
    assert bound_jaxprs(p256._NS, 2).isdisjoint(first)


def test_dispatch_frame_keeps_what_runs_below_it_in_one_stack_chunk():
    """``_pallas_or_jnp`` calls the program from ``_one_stack_chunk``.
    Without it a loop that happens to stand at the end of one of
    CPython's 16 KiB frame chunks maps, faults in and unmaps a chunk at
    every call it makes (here: one page fault a call at one starting
    depth in about 130); the lowering of the ladder makes ten million
    calls, and a node's worker thread started it at such a depth
    (PERF.md section 6, PR 46)."""
    import resource
    import sys

    def tiny(a):
        return a

    def hot():
        for _ in range(2000):
            tiny(1)

    def at_depth(d, fn):
        return fn() if d == 0 else at_depth(d - 1, fn)

    def faults(fn):
        before = resource.getrusage(resource.RUSAGE_THREAD).ru_minflt
        fn()
        return resource.getrusage(resource.RUSAGE_THREAD).ru_minflt - before

    worst = max(faults(lambda: at_depth(
        d, lambda: p256._one_stack_chunk(hot))) for d in range(160))
    assert worst < 200, worst

    seen = []
    p256._pallas_or_jnp(
        lambda: seen.append(sys._getframe(1).f_code) or np.ones(3, bool),
        lambda: np.zeros(3, bool))
    assert seen == [p256._one_stack_chunk.__code__]


def test_device_prep_input_sanitation_fast():
    """The device-prep branch's host-side packing (z mod n for oversized
    digests, coord mod p, sane() clamps) — checked against the limb
    arrays actually shipped, with the device program stubbed out so the
    test costs no XLA compile."""
    import hashlib

    d0, pub0 = curve.keygen(rng=31)
    m0 = b"sanitize"
    r0, s0 = curve.sign(m0, d0)
    digests = [hashlib.sha512(m0).digest(),      # z >= 2^256 -> z mod n
               hashlib.sha256(m0).digest()]
    sigs = [(r0, s0), (-1, 1 << 280)]            # hostile r/s -> sane() 0
    pubs = [(pub0[0] + (1 << 257), -5), pub0]    # coords -> mod p

    captured = {}

    def stub(packed):
        z, r, s, qx, qy, range_ok, rn_ok = p256._unpack_fused(packed)
        captured.update(z=np.asarray(z), r=np.asarray(r), s=np.asarray(s),
                        qx=np.asarray(qx), qy=np.asarray(qy),
                        range_ok=np.asarray(range_ok))
        import jax.numpy as jnp

        return jnp.zeros(z.shape[1], dtype=bool)

    orig = p256._prep_and_verify_jnp
    p256._prep_and_verify_jnp = stub
    try:
        p256.verify_batch_prehashed(digests, sigs, pubs, pad_block=8,
                                    backend="jnp", scalar_prep="device")
    finally:
        p256._prep_and_verify_jnp = orig

    def lane(arr, j):  # (8, N) packed uint32 words -> int
        return int.from_bytes(np.asarray(arr[:, j]).astype("<u4").tobytes(),
                              "little")
    z512 = int.from_bytes(digests[0], "big")
    assert lane(captured["z"], 0) == z512 % CURVE_N
    assert lane(captured["z"], 1) == int.from_bytes(digests[1], "big")
    assert lane(captured["qx"], 0) == (pub0[0] + (1 << 257)) % CURVE_P
    assert lane(captured["qy"], 0) == (-5) % CURVE_P
    assert lane(captured["r"], 1) == 0 and lane(captured["s"], 1) == 0
    assert list(captured["range_ok"][:2]) == [True, False]


def test_packed_word_unpack_matches_limbs():
    """(8, N) uint32 wire format -> limbs must equal the host packer for
    the full 256-bit range (incl. the top limbs that spill past word 8)."""
    import random as _random

    r = _random.Random(4)
    xs = [r.randrange(1 << 256) for _ in range(40)] + [0, 1, (1 << 256) - 1]
    import jax.numpy as jnp

    w = jnp.asarray(p256._pack_words(xs, 3))  # with padding lanes
    got = np.asarray(p256._words_to_limbs(w))
    want = np.pad(fp.ints_to_limbs(xs), ((0, 0), (0, 3)))
    assert np.array_equal(got, want)


def test_point_mul_G_jacobian_matches_generic_ladder():
    """The fixed-base Jacobian table walk (wallet signing hot loop) must
    equal the generic affine double-and-add for random and edge scalars,
    including oversized keys (reduced mod n)."""
    import random as _random

    from upow_tpu.core import curve
    from upow_tpu.core.constants import CURVE_N

    rng = _random.Random(0xEC)
    scalars = [rng.randrange(1, CURVE_N) for _ in range(40)]
    scalars += [1, 2, 255, 256, 257, 0xFF00, (1 << 248) * 255,
                CURVE_N - 1, CURVE_N, CURVE_N + 5, (1 << 256) - 1]
    for k in scalars:
        assert curve.point_mul_G(k) == curve.point_mul(k % CURVE_N, curve.G), k


def test_point_mul_jacobian_matches_affine_ladder():
    """The Jacobian MSB ladder must equal the affine oracle for random
    AND adversarial scalars — verify scalars are attacker-influenced, so
    the mid-ladder identity cases (accumulator hitting ±p) are reachable
    and must resolve exactly."""
    import random as _random

    from upow_tpu.core import curve
    from upow_tpu.core.constants import CURVE_N

    rng = _random.Random(0xAD)
    n = CURVE_N
    _, p = curve.keygen(rng=0xABC)
    scalars = [rng.randrange(1, n) for _ in range(25)]
    scalars += [1, 2, 3, n - 1, n, n + 1, n + 2,
                ((n + 1) // 2 << 1) | 1,        # doubling branch
                ((n - 1) // 2 << 1) | 1,        # cancellation -> infinity
                ((((n - 1) // 2 << 1) | 1) << 3) | 5,  # restart after it
                (n - 1) << 4 | 0xF]
    for k in scalars:
        assert curve.point_mul(k, p) == \
            curve._point_mul_affine_ladder(k, p), k
    assert curve.point_mul(5, None) is None
    assert curve.point_mul(0, p) is None

