"""Differential tests: TPU sha256 search kernels vs hashlib + reference rule.

Covers: pure-Python compression, midstate-split templates for both header
versions (108-byte v2, 138-byte v1 — manager.py:385-398), hit detection at
integer and fractional difficulty, Pallas kernel (interpret mode on CPU),
and the bucketed batch hasher.
"""

import hashlib
import random

import numpy as np
import pytest

from upow_tpu.core.difficulty import check_pow_hash
from upow_tpu.crypto import (
    SENTINEL,
    make_template,
    pow_search_jnp,
    pow_search_pallas,
    sha256_batch_jnp,
    sha256_py,
    target_spec,
)

rng = random.Random(1234)


def _rand_bytes(n):
    return bytes(rng.randrange(256) for _ in range(n))


@pytest.mark.parametrize("size", [0, 1, 55, 56, 63, 64, 65, 104, 107, 108, 127, 138, 200, 1000])
def test_sha256_py_matches_hashlib(size):
    msg = _rand_bytes(size)
    assert sha256_py(msg) == hashlib.sha256(msg).digest()


@pytest.mark.parametrize("prefix_len", [104, 134])  # v2 / v1 header prefixes
def test_template_digest_matches_hashlib(prefix_len):
    """Find the nonce the kernel reports and recompute its hash on host."""
    prefix = _rand_bytes(prefix_len)
    template = make_template(prefix)
    # difficulty 1: prev hash whose last char is the target prefix
    prev_hash = _rand_bytes(32).hex()
    spec = target_spec(prev_hash, 1)
    hit = int(pow_search_jnp(template, spec, nonce_base=0, batch=4096))
    brute = next(
        (n for n in range(4096)
         if check_pow_hash(hashlib.sha256(prefix + n.to_bytes(4, "little")).hexdigest(), prev_hash, 1)),
        int(SENTINEL),
    )
    assert hit == brute


@pytest.mark.parametrize("difficulty", ["1", "2", "1.3", "2.7", "1.5"])
def test_search_jnp_matches_bruteforce(difficulty):
    prefix = _rand_bytes(104)
    template = make_template(prefix)
    prev_hash = _rand_bytes(32).hex()
    spec = target_spec(prev_hash, difficulty)
    batch = 8192
    hit = int(pow_search_jnp(template, spec, nonce_base=0, batch=batch))
    brute = next(
        (n for n in range(batch)
         if check_pow_hash(hashlib.sha256(prefix + n.to_bytes(4, "little")).hexdigest(),
                           prev_hash, difficulty)),
        int(SENTINEL),
    )
    assert hit == brute


def test_search_nonce_base_offset():
    """Hits found in a window that does not start at zero."""
    prefix = _rand_bytes(104)
    template = make_template(prefix)
    prev_hash = _rand_bytes(32).hex()
    spec = target_spec(prev_hash, 1)
    base = 1 << 20
    hit = int(pow_search_jnp(template, spec, nonce_base=base, batch=4096))
    assert hit >= base
    digest = hashlib.sha256(prefix + hit.to_bytes(4, "little")).hexdigest()
    assert check_pow_hash(digest, prev_hash, 1)


def test_search_no_hit_returns_sentinel():
    prefix = _rand_bytes(104)
    template = make_template(prefix)
    # difficulty 8 in a 1k window: astronomically unlikely
    spec = target_spec(_rand_bytes(32).hex(), 8)
    assert int(pow_search_jnp(template, spec, nonce_base=0, batch=1024)) == int(SENTINEL)


@pytest.mark.parametrize("difficulty", ["1", "1.4"])
def test_pallas_matches_jnp(difficulty):
    prefix = _rand_bytes(104)
    template = make_template(prefix)
    prev_hash = _rand_bytes(32).hex()
    spec = target_spec(prev_hash, difficulty)
    # interpret mode executes per-op Python: keep the batch small, but
    # larger than one tile (tile_rows=8 -> 1024 lanes) to exercise the grid
    batch = 2048
    a = int(pow_search_jnp(template, spec, nonce_base=0, batch=batch))
    b = int(pow_search_pallas(template, spec, nonce_base=0, batch=batch,
                              tile_rows=8, interpret=True))
    assert a == b


def test_v1_header_nonce_split_across_words():
    """138-byte v1 header: nonce bytes straddle w1/w2 of the tail block."""
    prefix = _rand_bytes(134)
    template = make_template(prefix)
    widxs = sorted({w for w, _ in template.nonce_spec})
    assert widxs == [1, 2]
    prev_hash = _rand_bytes(32).hex()
    spec = target_spec(prev_hash, 1)
    hit = int(pow_search_jnp(template, spec, nonce_base=0, batch=4096))
    if hit != int(SENTINEL):
        digest = hashlib.sha256(prefix + hit.to_bytes(4, "little")).hexdigest()
        assert check_pow_hash(digest, prev_hash, 1)


def test_sha256_batch_jnp_mixed_lengths():
    msgs = [_rand_bytes(n) for n in [0, 3, 55, 56, 64, 120, 250, 250, 300, 1000]]
    got = sha256_batch_jnp(msgs)
    for m, d in zip(msgs, got):
        assert d == hashlib.sha256(m).digest()


def test_txid_batch_device_matches_hashlib():
    from upow_tpu.crypto.sha256 import txid_batch

    payloads = [_rand_bytes(n) for n in [120, 250, 250, 300, 400, 400, 1000]]
    host = txid_batch(payloads, backend="host")
    dev = txid_batch(payloads, backend="device", min_batch=1)
    assert host == dev
    assert host == [hashlib.sha256(p).hexdigest() for p in payloads]


def test_txid_batch_small_batches_stay_host(monkeypatch):
    """Below min_batch the device path must never be dispatched."""
    import upow_tpu.crypto.sha256 as sha_mod

    def boom(_msgs):
        raise AssertionError("device path dispatched for a small batch")

    monkeypatch.setattr(sha_mod, "sha256_batch_jnp", boom)
    payloads = [_rand_bytes(64) for _ in range(8)]
    got = sha_mod.txid_batch(payloads, backend="device", min_batch=64)
    assert got == [hashlib.sha256(p).hexdigest() for p in payloads]


def test_txid_batch_integrity_sample_falls_back(monkeypatch):
    """A device batch returning a wrong digest must be discarded wholesale
    (txids are consensus — one silent corruption would fork the node)."""
    import upow_tpu.crypto.sha256 as sha_mod

    payloads = [_rand_bytes(100) for _ in range(6)]

    def corrupt(msgs):
        out = [hashlib.sha256(m).digest() for m in msgs]
        out[0] = b"\x00" * 32
        return out

    monkeypatch.setattr(sha_mod, "sha256_batch_jnp", corrupt)
    got = sha_mod.txid_batch(payloads, backend="device", min_batch=1)
    assert got == [hashlib.sha256(p).hexdigest() for p in payloads]


# --- the data-target Pallas kernel (the resident mesh program's body) ------

_DATA_TILE_ROWS = 8          # interpret mode: 1,024 lanes a tile
_DATA_TILE = _DATA_TILE_ROWS * 128
_U32_END = (1 << 32) - 1     # engine.MAX_SEARCH_END: SENTINEL is never searched
_STEP_TILES = 80             # a batch of two grid steps: 64 tiles and 16


def _digest_hex(prefix: bytes, nonce: int) -> str:
    return hashlib.sha256(prefix + nonce.to_bytes(4, "little")).hexdigest()


def _prev_hash_hit_by(prefix: bytes, nonce: int, k: int) -> str:
    """A previous hash whose last ``k`` chars are the first ``k`` of the
    real digest of ``nonce``: a target of any depth with a known hit."""
    return "0" * (64 - k) + _digest_hex(prefix, nonce)[:k]


def _nonce_with_nibble_below(prefix: bytes, lo: int, hi: int, pos: int,
                             count: int) -> int:
    """The first nonce of [lo, hi) whose digest's hex char ``pos`` is
    among the first ``count`` of the charset."""
    return next(n for n in range(lo, hi)
                if int(_digest_hex(prefix, n)[pos], 16) < count)


def _data_case(name):
    """(prefix, previous_hash, difficulty, base, limit, batch) of one case
    of the data-target kernel's test, built from a seed of its own."""
    r = random.Random(f"data-kernel-{name}")
    prefix = bytes(r.randrange(256) for _ in range(104))
    prev = bytes(r.randrange(256) for _ in range(32)).hex()
    base, batch = r.randrange(1 << 28), 2 * _DATA_TILE
    if name == "charset16":
        return prefix, prev, "1", base, base + batch, batch
    if name == "nibble_word0":           # 1 char and a fractional second
        return prefix, prev, "1.5", base, base + batch, batch
    if name == "nibble_word1":           # 9 chars: mask1 set, nibble in h1
        n = _nonce_with_nibble_below(prefix, base + 1100, base + batch, 9, 8)
        return (prefix, _prev_hash_hit_by(prefix, n, 9), "9.5",
                base, base + batch, batch)
    if name == "nibble_word2":           # 16 chars: both masks full, h2
        n = _nonce_with_nibble_below(prefix, base + 300, base + batch, 16, 12)
        return (prefix, _prev_hash_hit_by(prefix, n, 16), "16.3",
                base, base + batch, batch)
    if name == "mask1_11.0":             # the window's own compare shape
        return (prefix, _prev_hash_hit_by(prefix, base + 1777, 11), "11.0",
                base, base + batch, batch)
    if name == "limit_mid_tile":         # hits past the limit are not hits
        return prefix, prev, "1", base, base + _DATA_TILE + 476, batch
    if name == "limit_before_only_hit":  # the known hit lies past the limit
        return (prefix, _prev_hash_hit_by(prefix, base + 1500, 11), "11.0",
                base, base + 1500, batch)
    if name == "empty_shard":
        return prefix, prev, "1", base, base, batch
    if name == "ends_at_2^32-1":         # lanes past the limit wrap past 2^32
        return prefix, prev, "1", _U32_END - 1500, _U32_END, batch
    if name == "ragged_batch":           # no multiple of the tile, limit past it
        return prefix, prev, "1", base, base + 3 * _DATA_TILE, _DATA_TILE + 476
    if name == "two_tiles_lower_wins":
        return prefix, prev, "2", base, base + batch, batch
    return _step_case(name, r, prefix, prev)


def _planted(prefix: bytes, nonce: int, difficulty: str):
    """(previous hash, difficulty): a target of ``difficulty``'s depth
    that ``nonce``'s digest meets in its whole chars."""
    return _prev_hash_hit_by(prefix, nonce, int(float(difficulty))), difficulty


def _step_case(name, r, prefix, prev):
    """Cases of a batch of ``_STEP_TILES`` tiles, two grid steps of the
    kernel (ISSUE 41): where the range lies on the tiles, where the hits
    lie in the step, and how deep the target is."""
    tile, batch = _DATA_TILE, _STEP_TILES * _DATA_TILE
    aligned = r.randrange(1 << 18) * tile
    base = aligned + 300 + r.randrange(400)      # inside its first tile
    if name == "unaligned_base":                 # 81 tiles touched, hits in most
        return prefix, prev, "2", base, base + batch, batch
    if name == "unaligned_only_hit_in_the_tile_past_the_grid":
        return (prefix, *_planted(prefix, base + batch - 3, "11.0"),
                base, base + batch, batch)
    if name == "shorter_than_a_tile":            # hits below base and past limit
        return prefix, prev, "1", base, base + 200, batch
    if name == "unaligned_ends_at_2^32-1":
        return prefix, prev, "2", _U32_END - 5000, _U32_END, batch
    if name == "two_hits_in_tiles_of_one_step":
        return prefix, prev, "3", aligned, aligned + batch, batch
    if name == "hit_below_base_not_answered":    # hashed in base's tile
        return (prefix, *_planted(prefix, base - 5, "11.0"),
                base, base + batch, batch)
    if name == "hit_at_limit_not_answered":
        limit = base + 3 * tile + 77
        return prefix, *_planted(prefix, limit, "11.0"), base, limit, batch
    if name == "every_step_exact_at_1.0":
        return prefix, prev, "1.0", aligned, aligned + batch, batch
    if name == "hit_in_the_second_step_at_6.0":
        return (prefix, *_planted(prefix, aligned + 70 * tile + 5, "6.0"),
                aligned, aligned + batch, batch)
    if name == "nibble_in_word0_at_7.5":
        n = _nonce_with_nibble_below(prefix, base, base + batch, 7, 8)
        return prefix, *_planted(prefix, n, "7.5"), base, base + batch, batch
    if name == "nibble_in_word1_at_8.3":
        n = _nonce_with_nibble_below(prefix, base, base + batch, 8, 12)
        return prefix, *_planted(prefix, n, "8.3"), base, base + batch, batch
    if name == "v1_header":                      # the nonce over w1 / w2
        prefix = bytes(r.randrange(256) for _ in range(134))
        return prefix, prev, "2", base, base + 2 * tile, 2 * tile
    raise KeyError(name)


def _exact_steps(prefix, prev, difficulty, base, limit, batch) -> int:
    """The kernel's ``exact_steps`` by its rule, from hashlib: the grid
    steps in whose tiles some lane's digest starts with the first word's
    share of the target, lanes outside ``[base, limit)`` too.  Tiles are
    counted from ``base`` rounded down to a tile; a step holds
    ``TILES_PER_STEP`` of them, the grid's last what is left."""
    from upow_tpu.crypto.sha256 import TILES_PER_STEP

    chars = min(int(float(difficulty)), 8)
    want = prev[-int(float(difficulty)):][:chars]
    start = base - base % _DATA_TILE
    span = min((limit - base) % (1 << 32), batch)
    tiles = -(-(base - start + span) // _DATA_TILE) if span else 0
    steps = -(-(-(-batch // _DATA_TILE)) // TILES_PER_STEP)
    count = 0
    for step in range(steps):
        first = step * TILES_PER_STEP
        end = tiles if step == steps - 1 else min(first + TILES_PER_STEP, tiles)
        count += any(
            _digest_hex(prefix, n).startswith(want)
            for n in range(start + first * _DATA_TILE, start + end * _DATA_TILE))
    return count


@pytest.fixture(scope="module")
def data_kernels():
    """The data-target kernel (interpret mode) and its jnp twin — the
    resident program's other body — jitted once per batch: target and
    range are data, so every case of one batch shares one compile."""
    import functools

    import jax
    import jax.numpy as jnp

    from upow_tpu.crypto import sha256 as sk

    @functools.partial(jax.jit, static_argnames=("batch", "nonce_spec"))
    def pallas(mid, tail, base, limit, tgt, batch, nonce_spec):
        """(lowest hit, exact steps) out of the kernel's answer words."""
        hit, steps = sk.pow_search_pallas_data(
            mid, tail, jnp.stack([base, limit], axis=1), tgt, batch=batch,
            nonce_spec=nonce_spec, tile_rows=_DATA_TILE_ROWS, interpret=True)
        return (jax.lax.bitcast_convert_type(hit, jnp.uint32)
                ^ jnp.uint32(1 << 31)), -steps

    @functools.partial(jax.jit, static_argnames=("batch", "nonce_spec"))
    def twin(mid, tail, base, limit, tgt, batch, nonce_spec):
        nonces = base[0] + jnp.arange(batch, dtype=jnp.uint32)
        valid = (nonces >= base[0]) & (nonces < limit[0])
        digest = sk._compress_tail(
            tuple(mid[i] for i in range(8)),
            sk._build_w(tail, nonces, nonce_spec), unroll=False)
        return sk._hit_nonce_dynamic(digest, nonces, tgt, valid)

    return pallas, twin


@pytest.mark.parametrize("name", [
    "charset16", "nibble_word0", "nibble_word1", "nibble_word2",
    "mask1_11.0", "limit_mid_tile", "limit_before_only_hit", "empty_shard",
    "ends_at_2^32-1", "ragged_batch", "two_tiles_lower_wins",
    "unaligned_base", "unaligned_only_hit_in_the_tile_past_the_grid",
    "shorter_than_a_tile", "unaligned_ends_at_2^32-1",
    "two_hits_in_tiles_of_one_step", "hit_below_base_not_answered",
    "hit_at_limit_not_answered", "every_step_exact_at_1.0",
    "hit_in_the_second_step_at_6.0", "nibble_in_word0_at_7.5",
    "nibble_in_word1_at_8.3", "v1_header"])
def test_pallas_data_target_kernel(data_kernels, name):
    """Target and [base, limit) as SMEM data: the kernel's answer is
    hashlib's lowest hit of the range under the protocol's rule, and
    ``_hit_nonce_dynamic``'s on the jnp digest, wherever the range lies
    on the kernel's tiles and steps; its second word counts the steps
    that took the exact pass."""
    import jax.numpy as jnp

    from upow_tpu.crypto import sha256 as sk
    from upow_tpu.crypto.sha256 import pack_target

    prefix, prev, difficulty, base, limit, batch = _data_case(name)
    template = make_template(prefix)
    spec = target_spec(prev, difficulty)
    end = min(limit, base + batch)
    hits = [n for n in range(base, end)
            if check_pow_hash(_digest_hex(prefix, n), prev, difficulty)]
    want = hits[0] if hits else int(SENTINEL)

    # each case really is the case its name says
    if name.startswith("nibble_word"):
        assert spec.nibble_word == int(name[-1]) and spec.charset < 16
    if name in ("nibble_word1", "nibble_word2", "mask1_11.0"):
        assert int(spec.mask1) != 0 and len(hits) == 1
    if name == "limit_mid_tile":
        assert hits and (limit - base) % _DATA_TILE
        assert any(check_pow_hash(_digest_hex(prefix, n), prev, difficulty)
                   for n in range(limit, base + batch))
    if name == "limit_before_only_hit":
        assert not hits and check_pow_hash(
            _digest_hex(prefix, limit), prev, difficulty)
    if name == "ends_at_2^32-1":
        assert hits and base + batch > 1 << 32
    if name == "ragged_batch":
        assert batch % _DATA_TILE and limit > base + batch
    if name == "two_tiles_lower_wins":
        assert len({(n - base) // _DATA_TILE for n in hits}) >= 2
    step = sk.TILES_PER_STEP * _DATA_TILE
    if name.startswith("unaligned") or name == "shorter_than_a_tile":
        assert base % _DATA_TILE and hits
    if name == "unaligned_only_hit_in_the_tile_past_the_grid":
        assert hits == [base + batch - 3]
        assert (hits[0] - base + base % _DATA_TILE) // _DATA_TILE == _STEP_TILES
    if name == "shorter_than_a_tile":
        assert limit // _DATA_TILE == base // _DATA_TILE
        assert all(any(check_pow_hash(_digest_hex(prefix, n), prev, difficulty)
                       for n in outside)
                   for outside in (range(base - base % _DATA_TILE, base),
                                   range(limit, limit + 200)))
    if name == "unaligned_ends_at_2^32-1":
        assert base + batch > 1 << 32 and limit == _U32_END
    if name == "two_hits_in_tiles_of_one_step":
        assert len({(n - base) // _DATA_TILE for n in hits
                    if (n - base) // step == (hits[0] - base) // step}) >= 2
    if name in ("hit_below_base_not_answered", "hit_at_limit_not_answered"):
        outside = base - 5 if "below" in name else limit
        assert not hits and check_pow_hash(
            _digest_hex(prefix, outside), prev, difficulty)
    if name == "hit_in_the_second_step_at_6.0":
        assert len(hits) == 1 and (hits[0] - base) // step == 1
    if name.startswith("nibble_in_word"):
        assert spec.nibble_word == int(name[14]) and spec.charset < 16
        assert len(hits) == 1
    if name == "v1_header":
        assert hits and {w for w, _ in template.nonce_spec} == {1, 2}
    want_steps = _exact_steps(prefix, prev, difficulty, base, limit, batch)
    if name == "every_step_exact_at_1.0":
        assert want_steps == 2
    if name in ("hit_below_base_not_answered", "hit_at_limit_not_answered",
                "hit_in_the_second_step_at_6.0", "mask1_11.0"):
        assert want_steps == 1      # the planted digest's step alone
    if name == "empty_shard":
        assert want_steps == 0      # no tile is run

    args = (jnp.asarray(template.midstate), jnp.asarray(template.tail_words),
            jnp.array([base], jnp.uint32), jnp.array([limit], jnp.uint32),
            jnp.asarray(pack_target(spec)))
    pallas, twin = data_kernels
    got, steps = pallas(*args, batch=batch, nonce_spec=template.nonce_spec)
    assert int(got) == want
    assert int(got) == int(twin(*args, batch=batch,
                                nonce_spec=template.nonce_spec))
    assert int(steps) == want_steps


# --- the static-target programs: the range's end as data --------------------

_STATIC_BATCH = 4 * _DATA_TILE   # one program of 4,096 lanes, four grid steps


def _static_case(name):
    """(prefix, previous_hash, difficulty, base, limit, batch) of one case
    of the static-target programs' test.  Cases of one difficulty share
    prefix and previous hash, so the target, which is compiled in, is one
    program for all of them: only ``[base, limit)`` differs, as data."""
    dense = name != "hit_at_limit_not_answered"
    r = random.Random("static-kernel-" + ("dense" if dense else "sparse"))
    prefix = bytes(r.randrange(256) for _ in range(104))
    prev = bytes(r.randrange(256) for _ in range(32)).hex()
    base, batch = r.randrange(1 << 28), _STATIC_BATCH
    if name == "limit_inside_a_tile":
        return prefix, prev, "1", base, base + 2 * _DATA_TILE + 476, batch
    if name == "limit_on_a_tile_edge":
        return prefix, prev, "1", base, base + 2 * _DATA_TILE, batch
    if name == "limit_is_base":              # no lane may answer
        return prefix, prev, "1", base, base, batch
    if name == "full_round":                 # the old contract
        return prefix, prev, "1", base, base + batch, batch
    if name == "full_round_no_limit_given":
        return prefix, prev, "1", base, None, batch
    if name == "hit_at_limit_not_answered":  # the range's only hit is its end
        first = next(n for n in range(base, base + batch) if check_pow_hash(
            _digest_hex(prefix, n), prev, "2"))
        return prefix, prev, "2", base, first, batch
    if name == "ends_at_2^32-1":             # the default range's last round
        return prefix, prev, "1", (1 << 32) - batch, _U32_END, batch
    if name == "sentinels_neighbour":        # 2^32 - 2, the last nonce tried
        return prefix, prev, "1", _U32_END - 3, _U32_END, batch
    raise KeyError(name)


@pytest.mark.parametrize("program", ["pallas", "jnp"])
@pytest.mark.parametrize("name", [
    "limit_inside_a_tile", "limit_on_a_tile_edge", "limit_is_base",
    "full_round", "full_round_no_limit_given", "hit_at_limit_not_answered",
    "ends_at_2^32-1", "sentinels_neighbour"])
def test_static_search_takes_the_ranges_end_as_data(program, name):
    """``pow_search_pallas`` (interpret mode) and its twin
    ``pow_search_jnp``: one program of ``batch`` lanes answers hashlib's
    lowest hit of ``[base, limit)`` under the protocol's rule; lanes at
    or past the limit never answer, wherever the limit falls."""
    prefix, prev, difficulty, base, limit, batch = _static_case(name)
    template = make_template(prefix)
    spec = target_spec(prev, difficulty)
    end = base + batch if limit is None else min(limit, base + batch)
    hits = [n for n in range(base, end)
            if check_pow_hash(_digest_hex(prefix, n), prev, difficulty)]
    want = hits[0] if hits else int(SENTINEL)

    # each case really is the case its name says
    past = [n for n in range(end, min(base + batch, _U32_END))
            if check_pow_hash(_digest_hex(prefix, n), prev, difficulty)]
    if name == "limit_inside_a_tile":
        assert hits and past and (limit - base) % _DATA_TILE
    if name == "limit_on_a_tile_edge":
        assert hits and past and (limit - base) % _DATA_TILE == 0
    if name == "limit_is_base":
        assert not hits and past
    if name == "hit_at_limit_not_answered":
        assert not hits and past[0] == limit
    if name == "ends_at_2^32-1":
        assert hits and base + batch == 1 << 32 and limit == _U32_END
    if name == "sentinels_neighbour":
        assert base + batch > 1 << 32 and end - base == 3

    if program == "pallas":
        got = pow_search_pallas(template, spec, nonce_base=base, batch=batch,
                                limit=limit, tile_rows=_DATA_TILE_ROWS,
                                interpret=True)
    else:
        got = pow_search_jnp(template, spec, nonce_base=base, batch=batch,
                             limit=limit)
    assert int(got) == want


def test_static_pallas_takes_a_batch_of_no_whole_tiles():
    """The grid rounds the batch up (no assert on ``batch % tile``): the
    surplus lanes of the last tile are hashed and never answer."""
    prefix, prev, _d, base, _limit, _batch = _static_case("full_round")
    template, spec = make_template(prefix), target_spec(prev, "3")
    batch = _DATA_TILE + 476
    hits = [n for n in range(base, base + (1 << 16))
            if check_pow_hash(_digest_hex(prefix, n), prev, "3")]
    # a hit with none in the ``batch`` nonces below it
    hit = next(h for below, h in zip(hits, hits[1:]) if h - below > batch)

    def search(start):
        return int(pow_search_pallas(
            template, spec, nonce_base=start, batch=batch,
            tile_rows=_DATA_TILE_ROWS, interpret=True))

    assert search(hit - batch) == int(SENTINEL)   # the first surplus lane
    assert search(hit - batch + 1) == hit         # the batch's last lane


# --- the hoisted template: what the host finishes once a job ----------------

_EDGE_NONCES = [0, 1, 0xFF, 0x100, 0x00FFFFFF, 1 << 31, (1 << 32) - 2]


@pytest.fixture(scope="module")
def edge_digests():
    """{(prefix length, form): (prefix, digests)} of ``_search_digest`` at
    the edge nonces, from the template's two arrays: unrolled (the kernels'
    form, every hoisted word read; op by op here, no jit) and rolled."""
    import jax.numpy as jnp

    from upow_tpu.crypto import sha256 as sk

    r = random.Random("hoisted-template")
    out = {}
    for prefix_len in (104, 134):
        prefix = bytes(r.randrange(256) for _ in range(prefix_len))
        template = make_template(prefix)
        for form, unroll in (("unrolled", True), ("rolled", False)):
            digest = sk._search_digest(
                jnp.asarray(template.midstate),
                jnp.asarray(template.tail_words),
                jnp.asarray(_EDGE_NONCES, dtype=jnp.uint32),
                template.nonce_spec, unroll=unroll)
            out[prefix_len, form] = (prefix, np.stack(
                [np.asarray(word) for word in digest], axis=1))
    return out


@pytest.mark.parametrize("form", ["unrolled", "rolled"])
@pytest.mark.parametrize("nonce", _EDGE_NONCES)
@pytest.mark.parametrize("prefix_len", [104, 134])
def test_hoisted_template_digest_matches_hashlib(edge_digests, prefix_len,
                                                 nonce, form):
    """All eight digest words, from the hoisted state and schedule, are
    hashlib's: at every nonce byte's carry and at the u32 edge."""
    prefix, digests = edge_digests[prefix_len, form]
    got = b"".join(int(word).to_bytes(4, "big")
                   for word in digests[_EDGE_NONCES.index(nonce)])
    assert got == hashlib.sha256(prefix + nonce.to_bytes(4, "little")).digest()


@pytest.mark.parametrize("prefix_len,rounds,words,first_words", [
    (104, 10, 4, [16, 18, 20, 22]),   # v2: the nonce is w10
    (134, 1, 0, []),                  # v1: the nonce starts in w1
])
def test_template_hoists_what_no_nonce_reaches(prefix_len, rounds, words,
                                               first_words):
    """What the host finishes follows from where the nonce lands; the
    template keeps the plain midstate and tail block beside it."""
    from upow_tpu.crypto import sha256 as sk

    prefix = _rand_bytes(prefix_len)
    template = make_template(prefix)
    reached = sk.nonce_reach(template.nonce_spec)
    assert sk.hoisted_counts(template.nonce_spec) == (rounds, words)
    assert [i for i in range(16, 64) if not reached[i]] == first_words
    assert reached.index(True) == min(w for w, _ in template.nonce_spec)
    assert template.midstate.shape == (16,)
    assert template.tail_words.shape == (80,)

    # [0:8] / [0:16]: the state after the whole blocks, the plain tail
    state = tuple(int(x) for x in sk._H0)
    for off in range(0, prefix_len - prefix_len % 64, 64):
        state = sk._compress_py(state, prefix[off:off + 64])
    assert tuple(int(x) for x in template.midstate[:8]) == state
    block = (prefix[prefix_len - prefix_len % 64:] + bytes(4) + b"\x80")
    block += bytes(56 - len(block)) + (8 * (prefix_len + 4)).to_bytes(8, "big")
    tail = np.frombuffer(block, dtype=">u4")
    assert (template.tail_words[:16] == tail).all()

    # [8:16]: the plain rounds before the nonce's first word; a finished
    # word carries K[i] + w[i] of the plain schedule of the zero nonce
    w = [int(x) for x in tail]
    for i in range(16, 64):
        w.append(sum(sk._schedule_terms(w, i, sk._small_sigma_py))
                 & 0xFFFFFFFF)
    for i in range(rounds):
        state = sk._round_py(state, int(sk._K[i]) + w[i])
    assert tuple(int(x) for x in template.midstate[8:]) == state
    for i in range(64):
        if not reached[i]:
            assert int(template.tail_words[16 + i]) == \
                (int(sk._K[i]) + w[i]) & 0xFFFFFFFF


_BIT_TRIPLES = [(x, y, z) for x in (0, 0xFFFFFFFF) for y in (0, 0xFFFFFFFF)
                for z in (0, 0xFFFFFFFF)]


@pytest.mark.parametrize("triple", _BIT_TRIPLES + ["random"],
                         ids=lambda t: t if t == "random" else
                         "".join("1" if x else "0" for x in t))
def test_round_ch_maj_in_three_operations_match_the_plain_forms(triple):
    """``_round``'s Ch ``g ^ (e & (f ^ g))`` and Maj ``b ^ ((a ^ b) &
    (b ^ c))`` against the host round's ``(e & f) ^ (~e & g)`` and
    ``(a & b) ^ (a & c) ^ (b & c)``: every bit triple, and random words."""
    import jax.numpy as jnp

    from upow_tpu.crypto import sha256 as sk

    r = random.Random("ch-maj")
    if triple == "random":
        states = [tuple(r.getrandbits(32) for _ in range(8))
                  for _ in range(64)]
    else:  # (a, b, c) and (e, f, g) each run through the triple
        x, y, z = triple
        states = [(x, y, z, r.getrandbits(32), x, y, z, r.getrandbits(32))]
    kw = r.getrandbits(32)
    lanes = tuple(jnp.asarray([s[j] for s in states], dtype=jnp.uint32)
                  for j in range(8))
    new, ab = sk._round(lanes, lanes[1] ^ lanes[2], jnp.uint32(kw))
    for lane, state in enumerate(states):
        assert tuple(int(word[lane]) for word in new) == \
            sk._round_py(state, kw)
        assert int(ab[lane]) == state[0] ^ state[1]


@pytest.mark.parametrize("form", ["unrolled", "rolled"])
def test_compress_tail_of_words_that_all_differ_matches_hashlib(form):
    """The txid path's compression (every word differs by lane, nothing
    to hoist), in both of its forms, two blocks deep."""
    import jax.numpy as jnp

    from upow_tpu.crypto import sha256 as sk

    msgs = [_rand_bytes(100) for _ in range(5)]
    rows = np.stack([np.frombuffer(
        m + b"\x80" + bytes(19) + (800).to_bytes(8, "big"), dtype=">u4")
        for m in msgs]).astype(np.uint32)
    state = tuple(jnp.full((len(msgs),), h, jnp.uint32) for h in sk._H0)
    for block in range(2):
        state = sk._compress_tail(
            state, [jnp.asarray(rows[:, block * 16 + i]) for i in range(16)],
            unroll=form == "unrolled")
    for lane, m in enumerate(msgs):
        got = b"".join(int(word[lane]).to_bytes(4, "big") for word in state)
        assert got == hashlib.sha256(m).digest()


def test_pallas_v1_header_answers_hashlibs_lowest_hit():
    """The kernel on a v1 header (one round hoisted, the nonce over
    w1/w2, both tail words with bytes of their own), interpret mode."""
    r = random.Random("pallas-v1")
    prefix = bytes(r.randrange(256) for _ in range(134))
    prev = bytes(r.randrange(256) for _ in range(32)).hex()
    base, batch = 0x00FFFC00, 2 * _DATA_TILE   # a carry into the third byte
    want = next(n for n in range(base, base + batch)
                if check_pow_hash(_digest_hex(prefix, n), prev, "1"))
    got = pow_search_pallas(make_template(prefix), target_spec(prev, "1"),
                            nonce_base=base, batch=batch,
                            tile_rows=_DATA_TILE_ROWS, interpret=True)
    assert int(got) == want
