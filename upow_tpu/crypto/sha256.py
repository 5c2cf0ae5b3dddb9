"""SHA-256 for TPU proof-of-work: midstate split + batched final-block search.

The uPow header puts the 4-byte nonce at the very end (header.py), so a
mining template factors as

    sha256(header) = compress(tail_block(nonce), midstate(prefix_blocks))

where ``midstate`` covers every complete 64-byte block of the prefix (host,
once per template) and only ONE compression runs per nonce on device
(reference hot loop: /root/reference/miner.py:83-98 does the full hash per
nonce in Python).  Of that compression, the rounds and schedule words no
nonce byte reaches are the same for every nonce of a job: the host
finishes them once a job too (:func:`make_template`), and the search
programs start at the first round a nonce reaches (:func:`_search_digest`).

Three implementations share the same round logic:

* :func:`pow_search_jnp` — pure jax.numpy, runs anywhere (CPU tests, and a
  perfectly good XLA:TPU program in its own right).
* :func:`pow_search_pallas` — Pallas TPU kernel, tiled over the nonce batch.
* :func:`_compress_py` — pure-Python compression for host-side midstate.

Hit detection runs on device: the PoW rule (manager.py:130-151) — digest
must start with the last ``int(difficulty)`` hex chars of the previous
hash, fractional part restricts the next nibble — compiles down to two
masked u32 compares plus a nibble bound, precomputed by :func:`target_spec`.
"""

from __future__ import annotations

import functools
import time
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np

# --- constants -----------------------------------------------------------

_K = np.array(
    [
        0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5, 0x3956C25B, 0x59F111F1,
        0x923F82A4, 0xAB1C5ED5, 0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
        0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174, 0xE49B69C1, 0xEFBE4786,
        0x0FC19DC6, 0x240CA1CC, 0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
        0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7, 0xC6E00BF3, 0xD5A79147,
        0x06CA6351, 0x14292967, 0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
        0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85, 0xA2BFE8A1, 0xA81A664B,
        0xC24B8B70, 0xC76C51A3, 0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
        0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5, 0x391C0CB3, 0x4ED8AA4A,
        0x5B9CCA4F, 0x682E6FF3, 0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
        0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
    ],
    dtype=np.uint32,
)

_H0 = np.array(
    [0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
     0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19],
    dtype=np.uint32,
)

SENTINEL = np.uint32(0xFFFFFFFF)  # "no hit" marker; nonce space is capped below it


# --- pure-Python compression (host midstate) -----------------------------

def _rotr_py(x: int, n: int) -> int:
    return ((x >> n) | (x << (32 - n))) & 0xFFFFFFFF


def _small_sigma_py(x: int, r1: int, r2: int, shift: int) -> int:
    return _rotr_py(x, r1) ^ _rotr_py(x, r2) ^ (x >> shift)


#: w[i] = w[i-16] + s0(w[i-15]) + w[i-7] + s1(w[i-2]): each term's tap and
#: its small sigma's (rotate, rotate, shift), None for the word itself
_SCHEDULE = ((16, None), (15, (7, 18, 3)), (7, None), (2, (17, 19, 10)))


def _schedule_terms(w, i: int, small_sigma, keep=lambda j: True):
    """The terms of schedule word ``i``'s recurrence over ``w`` whose
    word ``keep`` keeps (all four by default), on the host
    (``_small_sigma_py``) or traced (``_small_sigma``)."""
    return [w[i - tap] if sigma is None else small_sigma(w[i - tap], *sigma)
            for tap, sigma in _SCHEDULE if keep(i - tap)]


def _round_py(state: Sequence[int], kw: int) -> Tuple[int, ...]:
    """One round on the host; ``kw`` is ``K[i] + w[i]``."""
    a, b, c, d, e, f, g, h = state
    s1 = _rotr_py(e, 6) ^ _rotr_py(e, 11) ^ _rotr_py(e, 25)
    ch = (e & f) ^ (~e & g)
    t1 = (h + s1 + ch + kw) & 0xFFFFFFFF
    s0 = _rotr_py(a, 2) ^ _rotr_py(a, 13) ^ _rotr_py(a, 22)
    maj = (a & b) ^ (a & c) ^ (b & c)
    t2 = (s0 + maj) & 0xFFFFFFFF
    return (t1 + t2) & 0xFFFFFFFF, a, b, c, (d + t1) & 0xFFFFFFFF, e, f, g


def _compress_py(state: Sequence[int], block: bytes) -> Tuple[int, ...]:
    """One SHA-256 compression on the host (64-byte block)."""
    # Host-only midstate prep (never traced); uint64 gives headroom for
    # the Python-int schedule additions below.
    w = list(np.frombuffer(block, dtype=">u4").astype(np.uint64))  # upowlint: disable=DT001
    w = [int(x) for x in w]
    for i in range(16, 64):
        w.append(sum(_schedule_terms(w, i, _small_sigma_py)) & 0xFFFFFFFF)
    out = tuple(state)
    for i in range(64):
        out = _round_py(out, int(_K[i]) + w[i])
    return tuple((x + y) & 0xFFFFFFFF for x, y in zip(state, out))


def sha256_py(message: bytes) -> bytes:
    """Full pure-Python sha256 (test oracle for the compression)."""
    state = tuple(int(x) for x in _H0)
    padded = message + b"\x80" + b"\x00" * ((55 - len(message)) % 64) + (8 * len(message)).to_bytes(8, "big")
    for off in range(0, len(padded), 64):
        state = _compress_py(state, padded[off:off + 64])
    return b"".join(s.to_bytes(4, "big") for s in state)


# --- template preparation (host) -----------------------------------------

class SearchTemplate(NamedTuple):
    """Everything the device kernel needs for one mining template: the
    job's nonce-free part of the tail block's compression, done once
    here on the host, and where the nonce lands.

    midstate      : (16,) uint32 — [0:8] the state after the prefix's
                    whole blocks (the digest's feed-forward); [8:16] the
                    working variables a..h after the rounds no nonce
                    reaches, run from it
    tail_words    : (80,) uint32 — [0:16] the final block with nonce
                    bytes zeroed, padding + length already applied;
                    [16:80] the hoisted message schedule, word ``16 + i``
                    for round ``i``: ``K[i] + w[i]`` where no nonce
                    reaches ``w[i]``, else the sum of ``w[i]``'s
                    nonce-free terms (:func:`nonce_reach` says which)
    nonce_spec    : 4×(word_index, left_shift) — where each little-endian
                    nonce byte lands in the tail words (static per header
                    version: v2 108-byte header → all four bytes in w10;
                    v1 138-byte header → split across w1/w2)

    What is hoisted follows from ``nonce_spec`` alone: a v2 header's
    nonce is w10, so rounds 0-9 and schedule words 16, 18, 20 and 22 are
    finished here and words 17, 19, 21 and 23-38 have a hoisted part; a
    v1 header's nonce starts in w1, so round 0, no whole word, and a
    hoisted part of words 16-31.
    """

    midstate: np.ndarray
    tail_words: np.ndarray
    nonce_spec: Tuple[Tuple[int, int], ...]


@functools.lru_cache(maxsize=None)
def nonce_reach(nonce_spec) -> Tuple[bool, ...]:
    """For each of the 64 schedule words, whether a nonce byte reaches
    it: the tail words ``nonce_spec`` names and every later word whose
    recurrence reads one.  Every other word, and every round before the
    first such word, is the same for all of a job's nonces."""
    reached = [False] * 64
    for widx, _ in nonce_spec:
        reached[widx] = True
    for i in range(16, 64):
        reached[i] = any(reached[i - tap] for tap, _ in _SCHEDULE)
    return tuple(reached)


def hoisted_counts(nonce_spec) -> Tuple[int, int]:
    """(rounds of the 64, schedule words of the 48) that
    :func:`make_template` finishes on the host for ``nonce_spec``."""
    reached = nonce_reach(nonce_spec)
    return reached.index(True), 48 - sum(reached[16:])


def _hoist(midstate: Sequence[int], tail_words: Sequence[int], nonce_spec):
    """The tail block's nonce-free work, in Python integers: (a..h after
    the rounds before the first word a nonce reaches, the 64 hoisted
    schedule words of :class:`SearchTemplate`)."""
    reached = nonce_reach(nonce_spec)
    # nonce bytes are zero in tail_words, so a reached word holds exactly
    # its nonce-free part; no unreached word reads one
    w = [int(x) for x in tail_words]
    for i in range(16, 64):
        w.append(sum(_schedule_terms(
            w, i, _small_sigma_py, lambda j: not reached[j])) & 0xFFFFFFFF)
    sched = [x if reached[i] else (x + int(_K[i])) & 0xFFFFFFFF
             for i, x in enumerate(w)]
    state = tuple(int(x) for x in midstate)
    for i in range(reached.index(True)):
        state = _round_py(state, sched[i])
    return state, sched


def make_template(prefix: bytes) -> SearchTemplate:
    """Build a search template from the header prefix (header minus nonce).

    ``prefix`` is ``BlockHeader.prefix_bytes()`` — 104 bytes for v2, 134
    for v1 (header.py).  The full message is ``prefix + nonce(4, LE)``.
    """
    total_len = len(prefix) + 4
    n_full = len(prefix) // 64
    # in-block message (rem + nonce) must leave room for 0x80 AND the
    # 8-byte length field: rem + 4 + 1 <= 56, i.e. in-block total <= 55
    # (at exactly 56 the 0x80 would be overwritten by the length field)
    if total_len - n_full * 64 > 55:
        raise ValueError("tail would span two blocks — unsupported header size")
    state = tuple(int(x) for x in _H0)
    for i in range(n_full):
        state = _compress_py(state, prefix[i * 64:(i + 1) * 64])

    tail = bytearray(64)
    rem = prefix[n_full * 64:]
    tail[: len(rem)] = rem
    nonce_off = len(rem)  # nonce occupies tail[nonce_off : nonce_off+4]
    tail[nonce_off + 4] = 0x80
    tail[56:64] = (8 * total_len).to_bytes(8, "big")

    # little-endian nonce byte j = (nonce >> 8j) & 0xFF lands at tail byte
    # nonce_off + j, i.e. word (nonce_off+j)//4, big-endian byte slot
    # (nonce_off+j)%4 → left shift 8*(3 - slot).
    nonce_spec = tuple(
        ((nonce_off + j) // 4, 8 * (3 - (nonce_off + j) % 4)) for j in range(4)
    )
    tail_words = np.frombuffer(bytes(tail), dtype=">u4").astype(np.uint32)
    hoisted_state, sched = _hoist(state, tail_words, nonce_spec)
    return SearchTemplate(
        np.array(state + hoisted_state, dtype=np.uint32),
        np.concatenate([tail_words, np.array(sched, dtype=np.uint32)]),
        nonce_spec)


class TargetSpec(NamedTuple):
    """PoW acceptance test compiled to u32 compares (manager.py:130-151).

    hit ⇔ (h0 & mask0)==val0 ∧ (h1 & mask1)==val1 ∧ next-nibble < charset
    (charset check skipped when charset == 16).
    """

    mask0: np.uint32
    val0: np.uint32
    mask1: np.uint32
    val1: np.uint32
    nibble_word: int      # which digest word holds the fractional nibble
    nibble_shift: int     # right-shift to land it in the low 4 bits
    charset: int          # allowed-charset size; 16 disables the check


def target_spec(previous_hash: str, difficulty) -> TargetSpec:
    from ..core.difficulty import pow_target

    prefix, k, charset = pow_target(previous_hash, difficulty)
    if k > 16:
        raise ValueError(f"difficulty prefix of {k} hex chars exceeds 2 digest words")
    p0, p1 = prefix[:8], prefix[8:]
    mask0 = ((1 << 4 * len(p0)) - 1) << (32 - 4 * len(p0)) if p0 else 0
    val0 = int(p0, 16) << (32 - 4 * len(p0)) if p0 else 0
    mask1 = ((1 << 4 * len(p1)) - 1) << (32 - 4 * len(p1)) if p1 else 0
    val1 = int(p1, 16) << (32 - 4 * len(p1)) if p1 else 0
    return TargetSpec(
        np.uint32(mask0), np.uint32(val0), np.uint32(mask1), np.uint32(val1),
        nibble_word=k // 8, nibble_shift=28 - 4 * (k % 8), charset=charset,
    )


# --- shared jnp round logic ----------------------------------------------

def _rotr(x, n: int):
    return (x >> n) | (x << (32 - n))


def _small_sigma(x, r1: int, r2: int, shift: int):
    return _rotr(x, r1) ^ _rotr(x, r2) ^ (x >> shift)


def _sum(*terms):
    """``terms`` added up, the scalars among them first: in a search
    program a hoisted word or a not yet mixed state variable is a scalar
    (an SMEM read, the scalar unit), and stays one until a vector meets
    it."""
    scalars = [t for t in terms if jnp.ndim(t) == 0]
    vectors = [t for t in terms if jnp.ndim(t) != 0]
    return functools.reduce(lambda x, y: x + y, scalars + vectors)


def _round(state, bc, *kw):
    """One round on ``state`` (a..h); ``kw`` are the terms of
    ``K[i] + w[i]``, ``bc`` is ``b ^ c``.  Ch and Maj take three
    operations each: ``g ^ (e & (f ^ g))``, and ``b ^ ((a ^ b) & (b ^ c))``
    whose ``a ^ b`` is the next round's ``b ^ c``, returned beside the
    new state."""
    a, b, c, d, e, f, g, h = state
    s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
    ch = g ^ (e & (f ^ g))
    t1 = _sum(h, *kw, s1, ch)
    s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
    ab = a ^ b
    maj = b ^ (ab & bc)
    return (_sum(t1, s0, maj), a, b, c, _sum(d, t1), e, f, g), ab


def _unrolled(unroll: bool | None) -> bool:
    """:func:`_compress_tail`'s default: unrolled exactly when the
    default backend is a real accelerator."""
    if unroll is None:
        from ..device.runtime import get_runtime

        unroll = get_runtime().platform() not in (None, "cpu")
    return unroll


def _feed_forward(midstate, state):
    return tuple(jnp.asarray(m, jnp.uint32) + x
                 for m, x in zip(midstate, state))


def _compress_tail(midstate, w, unroll: bool | None = None):
    """One compression over message words ``w`` (list of 16 u32 arrays),
    starting from ``midstate`` (tuple of 8 u32 arrays/scalars).

    Two compilations of the same math:

    * ``unroll=True`` — 64 rounds + 48 schedule extensions flattened into
      straight-line code.  Fastest on TPU (Mosaic/XLA:TPU vectorise it
      flat and compile it quickly) but XLA:CPU's pass pipeline goes
      super-linear on the unrolled graph (its algebraic simplifier logs
      "circular simplification loop"; minutes of compile on small hosts).
    * ``unroll=False`` — a 64-iteration ``lax.fori_loop`` whose body does
      one round plus one schedule extension over a rolling 16-word
      window.  Tiny HLO: compiles in seconds anywhere.  Used on CPU
      (tests, the multichip dryrun) where compile time dominates.

    Default: unrolled exactly when the default backend is a real
    accelerator.
    """
    if not _unrolled(unroll):
        return _feed_forward(midstate, _rounds_rolled(midstate, w, 0))
    w = list(w)
    state, bc = tuple(midstate), midstate[1] ^ midstate[2]
    for i in range(64):
        if i >= 16:
            w.append(_sum(*_schedule_terms(w, i, _small_sigma)))
        state, bc = _round(state, bc, jnp.uint32(_K[i]), w[i])
    return _feed_forward(midstate, state)


def _rounds_rolled(state, window, first: int):
    """Rounds ``first`` to 63 as a ``lax.fori_loop`` (the rolled form of
    :func:`_compress_tail`, see its docstring), from ``state`` (a..h
    before round ``first``) and ``window`` = ``w[first] .. w[first+15]``.
    Returns a..h after round 63.

    Invariant: at the start of round ``i`` the window holds
    ``w[i] .. w[i+15]``; the body consumes ``window[0]`` and appends
    ``w[i+16] = w[i] + s0(w[i+1]) + w[i+9] + s1(w[i+14])`` (garbage past
    round 47, never read).  The carried state has a ninth row, ``b ^ c``
    (:func:`_round`)."""
    shape = jnp.broadcast_shapes(*(jnp.shape(x) for x in window))
    window = jnp.stack(
        [jnp.broadcast_to(x, shape).astype(jnp.uint32) for x in window])
    state = jnp.stack([
        jnp.broadcast_to(jnp.asarray(x, jnp.uint32), shape)
        for x in (*state, state[1] ^ state[2])])
    k_arr = jnp.asarray(_K)

    def body(i, carry):
        st, win = carry
        new, ab = _round(tuple(st[j] for j in range(8)), st[8],
                         k_arr[i], win[0])
        wnew = _sum(*_schedule_terms(win, 16, _small_sigma))
        return (jnp.stack([*new, ab]),
                jnp.concatenate([win[1:], wnew[None]], axis=0))

    st, _ = jax.lax.fori_loop(first, 64, body, (state, window))
    return tuple(st[j] for j in range(8))


def _nonce_bytes(nonces, nonce_spec) -> dict:
    """``nonces``' little-endian bytes where the header keeps them, by
    tail word index and or-ed together, with nothing of the tail itself.
    Byte ``j`` (bits ``8j`` up) goes to bits ``shift`` up by one shift,
    and a mask unless that shift already dropped every other bit.
    Bitwise, so the bytes of ``x | y`` are the bytes of ``x`` or-ed with
    the bytes of ``y``: a scalar's and a lane constant's (the kernel)."""
    parts: dict = {}
    for j, (widx, shift) in enumerate(nonce_spec):
        move = shift - 8 * j
        byte = (nonces << jnp.uint32(move) if move > 0 else
                nonces >> jnp.uint32(-move) if move < 0 else nonces)
        if abs(move) != 24:
            byte = byte & jnp.uint32(0xFF << shift)
        parts.setdefault(widx, []).append(byte)
    return {widx: functools.reduce(lambda x, y: x | y, bytes_)
            for widx, bytes_ in parts.items()}


def _nonce_words(tail_words, nonces, nonce_spec) -> dict:
    """The tail words a nonce byte lands in, by index, each with its
    nonce bytes in place (:func:`_nonce_bytes`); the tail word itself is
    or-ed in only where it has a byte of its own (none in a v2 header's
    w10)."""
    held = [widx for widx, _ in nonce_spec]
    return {widx: bytes_ if held.count(widx) == 4
            else tail_words[widx] | bytes_
            for widx, bytes_ in _nonce_bytes(nonces, nonce_spec).items()}


def _build_w(tail_words, nonces, nonce_spec):
    """The 16 tail words of every nonce, as full arrays."""
    w = _nonce_words(tail_words, nonces, nonce_spec)
    return [w[i] if i in w else jnp.broadcast_to(tail_words[i], nonces.shape)
            for i in range(16)]


def _search_state(mid, tail, w, nonce_spec):
    """a..h after round 63 of the tail block's compression, unrolled,
    from the first round a nonce reaches on a :class:`SearchTemplate`'s
    hoisted state.  ``w`` holds the tail words a nonce lands in
    (:func:`_nonce_words`); only the schedule words a nonce reaches are
    built, each from its hoisted part, and every other word enters its
    round as one scalar with ``K[i]`` folded in."""
    reached = nonce_reach(nonce_spec)
    w = dict(w)
    state = tuple(mid[8 + j] for j in range(8))
    bc = state[1] ^ state[2]
    for i in range(reached.index(True), 64):
        if not reached[i]:
            state, bc = _round(state, bc, tail[16 + i])
            continue
        if i >= 16:
            terms = _schedule_terms(w, i, _small_sigma, lambda j: reached[j])
            if len(terms) < len(_SCHEDULE):  # else no term is nonce-free
                terms.append(tail[16 + i])
            w[i] = _sum(*terms)
        state, bc = _round(state, bc, jnp.uint32(_K[i]), w[i])
    return state


def _search_digest(mid, tail, nonces, nonce_spec, unroll: bool | None = None):
    """The digest words of ``nonces``' headers from a
    :class:`SearchTemplate`'s two arrays (``mid``, ``tail``: anything
    indexable by word, a traced array or an SMEM ref): the compression
    of the tail block from the first round a nonce reaches, on the
    hoisted state.

    Unrolled (:func:`_compress_tail`'s rule) it is :func:`_search_state`,
    the hashing body the Pallas kernels run.  Rolled (a CPU), the loop
    starts at that round over a window built in full from the plain tail
    words."""
    midstate = tuple(mid[j] for j in range(8))
    if _unrolled(unroll):
        return _feed_forward(midstate, _search_state(
            mid, tail, _nonce_words(tail, nonces, nonce_spec), nonce_spec))
    first = nonce_reach(nonce_spec).index(True)
    w = _build_w(tail, nonces, nonce_spec)
    for i in range(16, 16 + first):
        w.append(_sum(*_schedule_terms(w, i, _small_sigma)))
    return _feed_forward(midstate, _rounds_rolled(
        tuple(mid[8 + j] for j in range(8)), w[first:], first))


def _range_span(base, limit, batch: int):
    """How many nonces from ``base`` up a search program of ``batch``
    lanes may answer for: ``[base, min(limit, base + batch))``, counted
    from ``base`` (``limit - base`` in u32), so a range that ends at
    2^32 - 1, whose surplus lanes wrap past 2^32, needs no care."""
    return jnp.minimum(limit - base, jnp.uint32(min(batch, 0xFFFFFFFF)))


def _lanes_in_range(nonces, base, limit, batch: int):
    """The range mask every search program shares: a lane may answer
    only for a nonce of :func:`_range_span`.  Counted from ``base`` too:
    a lane below it (a tile that starts before an unaligned ``base``)
    wraps to a count no span reaches."""
    return nonces - base < _range_span(base, limit, batch)


def search_span(nonce_base: int, batch: int, limit=None) -> np.ndarray:
    """``[base, limit)`` as the (2,) u32 operand of the static-target
    programs, taken mod 2^32 (the mask counts from ``base``).  ``limit``
    None is the whole program, ``nonce_base + batch``."""
    if limit is None:
        limit = nonce_base + batch
    return np.array([int(nonce_base) & 0xFFFFFFFF, int(limit) & 0xFFFFFFFF],
                    dtype=np.uint32)


def _hit_nonce(digest, nonces, mask0, val0, mask1, val1, spec: TargetSpec,
               valid=None):
    ok = (digest[0] & mask0) == val0
    ok &= (digest[1] & mask1) == val1
    if spec.charset < 16:
        nib = (digest[spec.nibble_word] >> jnp.uint32(spec.nibble_shift)) & jnp.uint32(0xF)
        ok &= nib < jnp.uint32(spec.charset)
    if valid is not None:
        ok &= valid
    return jnp.min(jnp.where(ok, nonces, jnp.uint32(SENTINEL)))


def pack_target(spec: TargetSpec) -> np.ndarray:
    """Pack a :class:`TargetSpec` into the (7,) u32 vector consumed by
    :func:`_hit_nonce_dynamic` — [mask0, val0, mask1, val1, nibble_word,
    nibble_shift, charset].  Every field rides as runtime data, so the
    resident mesh program re-dispatches on a new chain tip / difficulty
    without recompiling."""
    return np.array(
        [spec.mask0, spec.val0, spec.mask1, spec.val1,
         spec.nibble_word, spec.nibble_shift, spec.charset],
        dtype=np.uint32,
    )


def _hit_nonce_dynamic(digest, nonces, target, valid=None):
    """Data-dependent twin of :func:`_hit_nonce` for the resident mesh
    search program: the Python-static ``charset < 16`` branch and the
    static digest-word index become traced ops so the whole target is a
    dynamic argument (see :func:`pack_target`).  ``valid`` masks lanes
    beyond the shard's planned range on tail rounds."""
    ok = (digest[0] & target[0]) == target[1]
    ok &= (digest[1] & target[2]) == target[3]
    # nibble_word = k // 8 for k <= 16 hex chars, so only words 0..2 can
    # ever hold the fractional nibble; charset == 16 disables the check.
    word = jnp.take(jnp.stack([digest[0], digest[1], digest[2]]),
                    target[4].astype(jnp.int32), axis=0)
    nib = (word >> target[5]) & jnp.uint32(0xF)
    ok &= (target[6] >= jnp.uint32(16)) | (nib < target[6])
    if valid is not None:
        ok &= valid
    return jnp.min(jnp.where(ok, nonces, jnp.uint32(SENTINEL)))


@functools.partial(jax.jit, static_argnames=("batch", "nonce_spec", "spec"))
def _pow_search_jnp(midstate, tail_words, span, batch: int,
                    nonce_spec, spec: TargetSpec):
    with jax.named_scope("upow.sha256_search"):
        base, limit = span[0], span[1]
        lin = jnp.arange(batch, dtype=jnp.uint32)
        nonces = base + lin
        digest = _search_digest(midstate, tail_words, nonces, nonce_spec)
        t = [jnp.uint32(x)
             for x in (spec.mask0, spec.val0, spec.mask1, spec.val1)]
        valid = _lanes_in_range(nonces, base, limit, batch)
        return _hit_nonce(digest, nonces, *t, spec, valid)


def pow_search_jnp(template: SearchTemplate, spec: TargetSpec,
                   nonce_base: int, batch: int, limit=None):
    """Search ``[nonce_base, min(limit, nonce_base + batch))`` with one
    program of ``batch`` lanes — returns min hit or SENTINEL.  ``limit``
    is data (:func:`search_span`): a round shorter than ``batch`` runs
    the program its job's whole rounds run."""
    return _pow_search_jnp(
        jnp.asarray(template.midstate), jnp.asarray(template.tail_words),
        search_span(nonce_base, batch, limit), batch,
        template.nonce_spec, spec,
    )


# --- Pallas TPU kernel ----------------------------------------------------

#: (tile_rows, 128) tiles a grid step of the search kernel hashes.  A
#: step costs 0.30 us of its own on a v5e whatever it holds (the grid's
#: bookkeeping, the lane constants, the one reduction to a scalar)
#: beside 3.51 us a tile, and a step that holds a candidate hashes its
#: tiles twice: at 64 the step's own cost is 0.13% of it, and a step in
#: 32 runs twice at difficulty 6.0, one in 512 at 7.0 (PERF.md section
#: 6, PR 41, has the chip's rounds at 1, 4, 16 and 64).
TILES_PER_STEP = 64


def _search_steps(batch: int, tile_rows: int) -> int:
    """Grid steps of the search kernel over ``batch`` lanes."""
    tiles = -(-batch // (tile_rows * 128))
    return -(-tiles // TILES_PER_STEP)


def _search_step(mid_ref, tail_ref, span_ref, target, out_ref, *, batch: int,
                 tile_rows: int, nonce_spec, axis=None):
    """One grid step of both Pallas kernels: :data:`TILES_PER_STEP`
    tiles of (tile_rows, 128) nonces, hashed by :func:`_search_state`
    (always unrolled: the rolled form would capture the K table as a
    pallas_call constant, and Mosaic compiles the flat rounds fast).
    ``mid_ref`` and ``tail_ref`` hold a :class:`SearchTemplate`'s two
    arrays, ``span_ref[0, :2]`` is ``[base, limit)``, all SMEM scalars;
    ``target`` is :func:`pack_target`'s seven words, indexable.
    ``out_ref`` holds :func:`answer_words`, min-accumulated over the
    sequential grid: the lowest hit, and the steps that took the exact
    pass in this shard's slot, the shard being its index along the mesh
    axis ``axis`` the kernel runs under (none: the only one).

    Tiles are counted from ``base`` rounded down to a tile, so a tile's
    first nonce ``b`` has no bit of a lane's index ``lin``: the nonce is
    ``b | lin`` and its words are the words of ``b``, on the scalar
    unit, or-ed with the words of ``lin``, built once a step.  The step
    runs the tiles ``[base, limit)`` touches and no other (a loop bound
    on the scalar unit; the grid's last step takes the one tile more an
    unaligned base adds).

    A lane's common path is the rounds and the first masked compare,
    kept in a carried vector.  Only a step in which some lane passed it
    runs its tiles again (the same loop, so the binary holds one hashing
    body) with the whole test behind a scalar predicate: second word,
    nibble, range, lowest nonce."""
    from jax.experimental import pallas as pl

    tile = tile_rows * 128
    if tile & (tile - 1):
        raise ValueError(f"a tile of {tile} lanes is no power of two")
    shift, low = tile.bit_length() - 1, jnp.uint32(tile - 1)
    step, steps = pl.program_id(0), _search_steps(batch, tile_rows)
    base, limit = span_ref[0, 0], span_ref[0, 1]
    span = _range_span(base, limit, batch)
    start = base & ~low
    # the tiles [base, base + span) touches: the last nonce's tile and
    # those before it, with no sum that could pass 2^32; an empty range
    # touches none
    last = span - jnp.uint32(1)
    n_tiles = jnp.where(
        span == jnp.uint32(0), jnp.uint32(0),
        (last >> shift) + (((last & low) + (base & low)) >> shift)
        + jnp.uint32(1)).astype(jnp.int32)
    first_tile = step * TILES_PER_STEP
    end_tile = jnp.where(
        step == steps - 1, n_tiles,
        jnp.minimum(first_tile + TILES_PER_STEP, n_tiles))

    lin = (jax.lax.broadcasted_iota(jnp.uint32, (tile_rows, 128), 0)
           * jnp.uint32(128)
           + jax.lax.broadcasted_iota(jnp.uint32, (tile_rows, 128), 1))
    lane_words = _nonce_bytes(lin, nonce_spec)

    # one SMEM row for the whole grid (a (1,1)-blocked (grid,1) output
    # is not a legal Mosaic block shape)
    slot = 1 + (0 if axis is None else jax.lax.axis_index(axis))

    @pl.when(step == 0)
    def _init():
        out_ref[0, 0] = jnp.int32(0x7FFFFFFF)  # SENTINEL, flipped
        for other in range(1, out_ref.shape[1]):
            out_ref[0, other] = jnp.int32(0)

    # the unrolled rounds are some 2,700 jnp operations to trace.  They
    # are traced once, here, to a jaxpr whose primitives the loops below
    # bind: traced where they run, inside three nested loop bodies, they
    # took the chip's host 7.8 s of the miner's arm where this takes 4.5
    # and the one-tile kernel took 4.0 (PERF.md section 6, PR 41)
    def tile_state(*head):
        state = _search_state(
            mid_ref, tail_ref,
            {i: h | lane_words[i] for i, h in zip(lane_words, head)},
            nonce_spec)
        return state[:3]        # a, b, c: the digest's first three words

    tile_state = jax.extend.core.jaxpr_as_fun(jax.make_jaxpr(tile_state)(
        *[jax.ShapeDtypeStruct((), jnp.uint32)] * len(lane_words)))

    def hash_tiles(exact):
        def one_tile(t, seen):
            b = start + (t.astype(jnp.uint32) << jnp.uint32(shift))
            head = _nonce_words(tail_ref, b, nonce_spec)
            state = tile_state(*[head[i] for i in lane_words])
            h0 = mid_ref[0] + state[0]
            passed = (h0 & target[0]) == target[1]

            @pl.when(exact)
            def _exact():
                h1, h2 = mid_ref[1] + state[1], mid_ref[2] + state[2]
                ok = passed & ((h1 & target[2]) == target[3])
                # nibble_word = k // 8 for k <= 16 hex chars: 0, 1 or 2
                word = jnp.where(target[4] == jnp.uint32(0), h0,
                                 jnp.where(target[4] == jnp.uint32(1), h1, h2))
                # a nibble is under 16, so charset >= 16 passes every
                # lane as _hit_nonce_dynamic's explicit test does
                ok &= ((word >> target[5]) & jnp.uint32(0xF)) < target[6]
                nonces = b | lin
                ok &= _lanes_in_range(nonces, base, limit, batch)
                # Mosaic has no unsigned reductions: reduce flipped
                out_ref[0, 0] = jnp.minimum(out_ref[0, 0], jnp.min(_flipped(
                    jnp.where(ok, nonces, jnp.uint32(SENTINEL)))))

            return jnp.where(passed, jnp.int32(1), seen)

        return jnp.max(jax.lax.fori_loop(
            first_tile, end_tile, one_tile,
            jnp.zeros((tile_rows, 128), jnp.int32)))

    def one_pass(carry):
        done, _ = carry

        @pl.when(done == 1)
        def _count():
            out_ref[0, slot] = out_ref[0, slot] - 1

        return done + 1, hash_tiles(done == 1)

    # the common pass, then the exact one where it saw a candidate
    jax.lax.while_loop(lambda carry: carry[0] <= carry[1], one_pass,
                       (jnp.int32(0), jnp.int32(0)))


def _pallas_kernel(mid_ref, tail_ref, span_ref, out_ref, *,
                   spec: TargetSpec, **static):
    """Static-target kernel: :func:`_search_step` with the target
    compiled in; the range is SMEM data, so one program a tip serves
    every round of a job, the short last one too."""
    _search_step(mid_ref, tail_ref, span_ref,
                 [jnp.uint32(x) for x in pack_target(spec)], out_ref,
                 **static)


def _pallas_kernel_data(mid_ref, tail_ref, span_ref, tgt_ref, out_ref,
                        **static):
    """Data-target kernel: :func:`_search_step` with the packed target
    (:func:`pack_target`) in SMEM too, so one compiled kernel serves
    every job, tip and difficulty.  Each ref may be longer than the
    words read (:func:`resident_operand`)."""
    _search_step(mid_ref, tail_ref, span_ref, tgt_ref, out_ref, **static)


def _flipped(nonces):
    """u32 to s32 with the sign bit flipped, which keeps the order: the
    form in which a search program min-reduces and returns its hit."""
    return jax.lax.bitcast_convert_type(
        nonces ^ jnp.uint32(0x80000000), jnp.int32)


def answer_words(hit, shards: int = 1):
    """What a search program returns for the lowest hit ``hit`` (u32, or
    SENTINEL), (1 + shards,) s32: the hit :func:`_flipped`, then a slot
    a shard that holds minus the steps of that shard's kernel that took
    the exact pass (none here: the kernel fills its own).  One signed
    min over the shards (the mesh program's ``pmin``) leaves the lowest
    hit and every shard's count, with no operation but the kernel and
    the collective; :class:`SearchAnswer` reads them on the host, in the
    round's one transfer."""
    return jnp.concatenate(
        [_flipped(hit).reshape(1), jnp.zeros(shards, jnp.int32)])


class SearchAnswer:
    """A search program's :func:`answer_words`, still on the device.
    ``int()`` waits for them, as it would for a bare scalar: the lowest
    hit or SENTINEL, with the exact steps of every shard added to
    ``kernel.<kernel>.exact_steps`` on the way."""

    def __init__(self, words, kernel: str):
        self._words, self._kernel, self._hit = words, kernel, None

    def ready(self) -> bool:
        """Whether ``int()`` would return without waiting: asked of the
        array's host side, no transfer."""
        return self._hit is not None or self._words.is_ready()

    def __int__(self) -> int:
        if self._hit is None:
            from ..telemetry import device as _ktel

            hit, *slots = (int(x) for x in np.asarray(self._words))
            _ktel.record_exact_steps(self._kernel, -sum(slots))
            self._hit = hit + 0x80000000
        return self._hit


def _search_call(kernel, operands, *, batch: int, tile_rows: int,
                 nonce_spec, interpret: bool, axis=None):
    """Run a search kernel over ``batch`` lanes, as one of the shards
    along the mesh axis ``axis`` (or alone): its :func:`answer_words`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shards = 1 if axis is None else jax.lax.psum(1, axis)  # the axis' size
    return pl.pallas_call(
        functools.partial(kernel, batch=batch, tile_rows=tile_rows,
                          nonce_spec=nonce_spec, axis=axis),
        grid=(_search_steps(batch, tile_rows),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * len(operands),
        out_specs=pl.BlockSpec((1, 1 + shards), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1 + shards), jnp.int32),
        interpret=interpret,
    )(*operands).reshape(1 + shards)


#: words of each array the resident program is given.  Left at their own
#: sizes (8, 16, 2 and 7 words), a custom call's operands past the second
#: are staged into scalar memory by XLA:TPU itself, a copy-start /
#: copy-done pair apiece on every execution: device operations beside the
#: kernel's one, and nine profiler events a chip a round, which at the
#: pod's hundreds of rounds a second decide whether a 45 s capture can be
#: stopped at all (PERF.md section 5).  An operand of a page is handed to
#: the kernel where it lies, and Mosaic's own prologue loads it.
RESIDENT_OPERAND_WORDS = 1024


def resident_operand(words) -> np.ndarray:
    """``words`` (u32, 1-D, or 2-D rows) zero-padded along the last axis
    to :data:`RESIDENT_OPERAND_WORDS`: the form in which the mesh engine
    hands midstate, tail, target and ranges to the resident program."""
    words = np.asarray(words, dtype=np.uint32)
    out = np.zeros(words.shape[:-1] + (RESIDENT_OPERAND_WORDS,), np.uint32)
    out[..., :words.shape[-1]] = words
    return out


def pow_search_pallas_data(midstate, tail_words, span, target, *,
                           batch: int, nonce_spec, tile_rows: int = 64,
                           interpret: bool = False, axis=None):
    """Pallas search of ``[base, min(limit, base + batch))`` against a
    packed runtime ``target``: :func:`answer_words`.  ``span`` is
    (1, >= 2) u32, ``[base, limit, ...]``: a shard's row of the resident
    mesh program's ranges, the shard one of those along the mesh axis
    ``axis``.
    Traced inside the caller's jit
    (``parallel.mesh._pow_search_mesh_resident``): no jit of its own.
    ``batch`` need be no multiple of anything, nor ``base``: the kernel
    runs the tiles the range touches."""
    return _search_call(
        _pallas_kernel_data, (midstate, tail_words, span, target),
        batch=batch, tile_rows=tile_rows, nonce_spec=nonce_spec,
        interpret=interpret, axis=axis)


@functools.partial(jax.jit, static_argnames=("batch", "tile_rows", "nonce_spec", "spec", "interpret"))
def _pow_search_pallas(midstate, tail_words, span, batch: int,
                       tile_rows: int, nonce_spec, spec: TargetSpec,
                       interpret: bool):
    with jax.named_scope("upow.sha256_search"):
        return _search_call(
            functools.partial(_pallas_kernel, spec=spec),
            (midstate, tail_words, span.reshape(1, 2)),
            batch=batch, tile_rows=tile_rows, nonce_spec=nonce_spec,
            interpret=interpret)


def pow_search_pallas(template: SearchTemplate, spec: TargetSpec,
                      nonce_base: int, batch: int, limit=None,
                      tile_rows: int = 64, interpret: bool = False):
    """Pallas-tiled search; same contract as :func:`pow_search_jnp`, the
    answer a :class:`SearchAnswer`."""
    return SearchAnswer(_pow_search_pallas(
        jnp.asarray(template.midstate), jnp.asarray(template.tail_words),
        search_span(nonce_base, batch, limit), batch, tile_rows,
        template.nonce_spec, spec, interpret,
    ), "sha256_search")


# --- batched fixed-length digests (txids, tests) --------------------------

@functools.partial(jax.jit, static_argnames=("n_blocks",))
def _sha256_blocks_jnp(words, n_blocks: int):
    """words: (batch, n_blocks*16) u32 big-endian message words, already
    padded.  Returns (batch, 8) u32 digests."""
    state = tuple(jnp.broadcast_to(jnp.uint32(h), words.shape[:1]) for h in _H0)
    for b in range(n_blocks):
        w = [words[:, b * 16 + i] for i in range(16)]
        state = _compress_tail(state, w)
    return jnp.stack(state, axis=1)


_TXID_AUTO_CHOICE = None  # resolved once per process, by measurement
_TXID_SAMPLE_SALT = 0  # per-call integrity-sample roam counter


def txid_batch(payloads: Sequence[bytes], backend: str = "auto",
               min_batch: int = 256) -> list:
    """Batched txids (hex digests) for a sync page / block accept
    (reference manager.py:365-378 hashes every tx serially).

    ``backend``:
      host    — hashlib per payload (the baseline),
      device  — one :func:`sha256_batch_jnp` dispatch per length bucket,
      auto    — measured crossover, resolved ONCE per process: time both
                on the first big-enough batch and keep the winner.  On
                any CPU host the host path wins by orders of magnitude;
                on a chip the device only pays for very large pages —
                measuring beats guessing either way.

    Device digests feed consensus (txids), so a host-side integrity
    sample (8 indices, roaming per call) guards every device batch; any
    mismatch falls back to hashlib for the whole batch.  The sample is
    probabilistic — the deterministic backstop is merkle_root's use of
    the seeded memos as leaves, which surfaces any corrupt seed as a
    header mismatch (and app.create_blocks then retries the page with
    host hashing).
    """
    import hashlib as _hl

    def host(ps):
        return [_hl.sha256(p).hexdigest() for p in ps]

    if backend == "host" or len(payloads) < min_batch:
        return host(payloads)
    if backend == "auto":
        global _TXID_AUTO_CHOICE
        if _TXID_AUTO_CHOICE is None:
            _TXID_AUTO_CHOICE, measured = _measure_txid_crossover(
                payloads, host)
            if measured is not None:
                return measured  # the measurement already hashed this batch
        backend = _TXID_AUTO_CHOICE
        if backend == "host":
            return host(payloads)
    try:
        digests = sha256_batch_jnp(payloads)
    except Exception as e:  # device sick mid-run: the node must not stall
        import logging

        logging.getLogger("upow_tpu.crypto").warning(
            "device txid batch failed (%s); host fallback", e)
        return host(payloads)
    out = [d.hex() for d in digests]
    # sample indices randomized per batch: seeded from the payloads plus
    # a per-call counter, so a RETRY of the same page samples different
    # lanes — fixed first/middle/last (or a payload-only seed) would let
    # a persistent fault in any unsampled lane seed the same wrong txid
    # every retry, wedging sync until the device recovers
    import random as _random

    global _TXID_SAMPLE_SALT
    _TXID_SAMPLE_SALT += 1
    seed = int.from_bytes(
        _hl.sha256(payloads[0] + payloads[-1] +
                   len(payloads).to_bytes(4, "big") +
                   _TXID_SAMPLE_SALT.to_bytes(8, "big")).digest()[:8], "big")
    n_samples = min(len(out), 8)
    for i in _random.Random(seed).sample(range(len(out)), n_samples):
        if _hl.sha256(payloads[i]).hexdigest() != out[i]:
            import logging

            logging.getLogger("upow_tpu.crypto").warning(
                "device txid digest mismatch at sample %d; "
                "host fallback for this batch", i)
            return host(payloads)
    return out


def _measure_txid_crossover(payloads, host_fn):
    """Time hashlib vs the device batch on real payloads; pick the
    winner for the rest of the process.  A hung/failed device resolves
    to host (the same thread-boxed probe discipline as verify).

    Returns ``(choice, digests_or_None)`` — the measurement already
    hashed the batch, so the host digests are handed back to avoid a
    second full pass on the first sync page (device digests are NOT
    reused: they haven't been integrity-sampled).
    """
    import logging
    import time as _t

    from ..device.runtime import get_runtime

    log = logging.getLogger("upow_tpu.crypto")
    runtime = get_runtime()
    # Operational timeouts/timing below are not consensus data.
    if runtime.platform() in (None, "cpu"):  # upowlint: disable=CP001
        log.info("txid auto: no accelerator; host hashing")
        return "host", None
    t0 = _t.perf_counter()
    host_digests = host_fn(payloads)
    t_host = _t.perf_counter() - t0

    def device_once():
        return sha256_batch_jnp(payloads)

    status, _ = runtime.run_boxed(  # compile warmup
        # operational timeout, not a consensus value
        device_once, 240.0, kernel="sha256_txid",  # upowlint: disable=CP001
        source="index")
    if status != "ok":
        log.warning("txid auto: device probe %s; host hashing", status)
        return "host", host_digests
    t0 = _t.perf_counter()
    status, _ = runtime.run_boxed(
        # operational timeout, not a consensus value
        device_once, 60.0, kernel="sha256_txid",  # upowlint: disable=CP001
        source="index")
    t_dev = _t.perf_counter() - t0
    if status != "ok":
        log.warning("txid auto: device re-run %s; host hashing", status)
        return "host", host_digests
    choice = "device" if t_dev < t_host else "host"
    log.info("txid auto: host %.1fms vs device %.1fms for %d payloads -> %s",
             t_host * 1e3, t_dev * 1e3, len(payloads), choice)  # upowlint: disable=CP001
    # either way the verified-correct host digests serve this batch
    return choice, host_digests


def sha256_batch_jnp(messages: Sequence[bytes]) -> list:
    """Batched sha256 of equal-or-bucketed-length messages on device.

    Messages are bucketed by padded block count; each bucket is one jit'd
    call.  Used for on-device txid batches (manager.py:365-378 hashes every
    tx); odd stragglers cost one extra bucket, not a recompile per length.
    """
    from ..telemetry import device as _ktel

    out: list = [None] * len(messages)
    buckets: dict = {}
    for idx, m in enumerate(messages):
        n_blocks = (len(m) + 8) // 64 + 1
        buckets.setdefault(n_blocks, []).append(idx)
    for n_blocks, idxs in buckets.items():
        rows = np.zeros((len(idxs), n_blocks * 16), dtype=np.uint32)
        for r, idx in enumerate(idxs):
            m = messages[idx]
            padded = (m + b"\x80" + b"\x00" * ((55 - len(m)) % 64)
                      + (8 * len(m)).to_bytes(8, "big"))
            rows[r] = np.frombuffer(padded, dtype=">u4").astype(np.uint32)
        # occupancy for this kernel = message bytes vs dispatched block
        # bytes (sha padding waste); jit retraces per (rows, n_blocks)
        t0 = time.perf_counter()
        digests = np.asarray(_sha256_blocks_jnp(jnp.asarray(rows), n_blocks))
        _ktel.record_batch(
            "sha256_txid",
            real=sum(len(messages[idx]) for idx in idxs),
            padded=len(idxs) * n_blocks * 64,
            seconds=time.perf_counter() - t0,
            compile_key=(len(idxs), n_blocks))
        for r, idx in enumerate(idxs):
            out[idx] = b"".join(int(x).to_bytes(4, "big") for x in digests[r])
    return out
