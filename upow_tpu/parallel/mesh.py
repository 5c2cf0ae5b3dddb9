"""Device-mesh scale-out for the two hot kernels (SURVEY.md §2.3).

The reference's only true parallel compute is the embarrassingly-parallel
nonce-space search (miner.py:126-156: N processes striding the nonce
space) and the per-signature verification loop (manager.py:628-632,
serial).  Their TPU-native scale-out:

* **Nonce search** — the nonce space is block-partitioned across the mesh
  ("dp" axis); every chip runs the same midstate kernel on its own range
  and a single ``pmin`` collective over ICI reduces the per-chip hit
  nonces to a global winner.  Multi-slice/multi-host scale-out assigns
  disjoint base ranges per slice via :func:`shard_bounds` (coordinator
  hands out ranges; no communication until a hit — DCN never sees the
  hot loop).
* **Batch signature verify** — pure data parallelism: the (21, N) limb
  arrays are sharded on the batch axis; the verify program contains no
  cross-lane ops, so XLA partitions it with zero collectives.

Unit tests exercise both on a virtual 8-device CPU mesh (conftest.py);
the same code drives a real v5e-8 (or larger) ICI mesh unchanged.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..crypto import sha256 as sha_kernel
from ..crypto.sha256 import SearchTemplate, TargetSpec


def make_mesh(devices=None) -> Mesh:
    """1-D data-parallel mesh over the given devices (default: the
    armed runtime's view — enumeration goes through the device owner so
    an unreachable device surfaces as an arm failure, not a hang here)."""
    if devices is None:
        from ..device.runtime import get_runtime

        devices = get_runtime().devices()
    return Mesh(np.array(devices), axis_names=("dp",))


def shard_bounds(total_lo: int, total_hi: int, index: int, count: int) -> Tuple[int, int]:
    """Disjoint [lo, hi) nonce range for shard ``index`` of ``count``.

    Used at the slice/host level (DCN coordinator) the way the reference
    assigns worker strides (miner.py:140-148) — but in contiguous blocks,
    which keeps each device's batch a single iota.
    """
    span = total_hi - total_lo
    return (total_lo + span * index // count, total_lo + span * (index + 1) // count)


def shard_map_compat():
    """(shard_map, disable-check kwargs) across jax versions: >= 0.8 has
    jax.shard_map with check_vma; older ships the experimental module
    with check_rep."""
    try:
        from jax import shard_map  # jax >= 0.8
        return shard_map, {"check_vma": False}
    except ImportError:  # pragma: no cover - older jax
        from jax.experimental.shard_map import shard_map
        return shard_map, {"check_rep": False}


@functools.partial(
    jax.jit, static_argnames=("batch_per_device", "nonce_spec", "spec", "mesh")
)
def _pow_search_mesh(midstate, tail_words, nonce_base, batch_per_device: int,
                     nonce_spec, spec: TargetSpec, mesh: Mesh):
    shard_map, check_kw = shard_map_compat()

    def per_device(mid, tail, base):
        idx = jax.lax.axis_index("dp")
        my_base = base[0] + jnp.uint32(idx) * jnp.uint32(batch_per_device)
        nonces = my_base + jnp.arange(batch_per_device, dtype=jnp.uint32)
        digest = sha_kernel._search_digest(mid, tail, nonces, nonce_spec)
        t = [jnp.uint32(x) for x in (spec.mask0, spec.val0, spec.mask1, spec.val1)]
        hit = sha_kernel._hit_nonce(digest, nonces, *t, spec)
        return jax.lax.pmin(hit.reshape(1), "dp")

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(), P(), P()),
        out_specs=P(),
        **check_kw,
    )(midstate, tail_words, nonce_base.reshape(1))[0]


def resident_body(mesh: Mesh, interpret: bool = False) -> str:
    """Which per-shard body :func:`_pow_search_mesh_resident` builds for
    ``mesh``: ``"pallas"`` on TPU devices (or in a test's interpret
    mode), ``"jnp"`` on anything else — chosen from the platform of the
    mesh's own devices, by no option.  On a TPU nothing leads back to
    the jnp body: a kernel Mosaic refuses fails the compile."""
    on_tpu = mesh.devices.flat[0].platform == "tpu"
    return "pallas" if on_tpu or interpret else "jnp"


@functools.partial(
    jax.jit,
    static_argnames=("batch_per_device", "nonce_spec", "mesh", "interpret"),
)
def _pow_search_mesh_resident(midstate, tail_words, ranges, target,
                              batch_per_device: int, nonce_spec, mesh: Mesh,
                              interpret: bool = False):
    """Resident mesh search: one compiled SPMD program per (batch,
    nonce_spec, mesh) whose template AND target ride as runtime data.

    Unlike :func:`_pow_search_mesh` (which bakes the :class:`TargetSpec`
    into the jit key), every job-specific field — midstate, tail words,
    per-shard [base, limit) ranges, packed target — is a traced array, so
    a new job / chain-tip / difficulty change is a pure dispatch: zero
    recompilation (asserted by the mine_mesh compile-cache counters).

    ``ranges`` is (n_devices, >= 2) u32, a ``[base, limit, ...]`` row a
    shard, sharded over "dp": shard i scans ``[base, min(limit, base +
    batch_per_device))``, so uneven ``shard_bounds`` spans and tail
    rounds need no recompile either.  An empty shard passes ``base ==
    limit``.  Every array may be longer than the words read: the mesh
    engine pads each to a page (``sha256.resident_operand``), which keeps
    XLA:TPU from staging them into scalar memory with a copy operation
    apiece on every round.

    The per-shard body is :func:`resident_body`'s: on a TPU mesh the
    Pallas kernel with target and range in SMEM
    (``sha256.pow_search_pallas_data``), elsewhere the jnp body below —
    the CPU path of ``--device mesh`` and the plain twin the tests
    compare the kernel against.  ``interpret`` runs the Pallas body in
    interpret mode on any platform (tests), in the smallest (8, 128)
    tiles, so that a test-sized shard spans more than one.

    Returns ``sha256.answer_words`` after the program's one collective,
    a signed ``pmin`` over "dp": the lowest hit of all shards and each
    shard's exact steps, which the kernel leaves in that form.
    """
    shard_map, check_kw = shard_map_compat()

    def per_device_jnp(mid, tail, span, tgt):
        my_base, my_limit = span[0, 0], span[0, 1]
        nonces = my_base + jnp.arange(batch_per_device, dtype=jnp.uint32)
        # u32 wrap past 2**32 makes a lane compare below my_base: both
        # wrapped and past-limit lanes drop out of the same mask
        valid = (nonces >= my_base) & (nonces < my_limit)
        digest = sha_kernel._search_digest(mid, tail, nonces, nonce_spec)
        hit = sha_kernel._hit_nonce_dynamic(digest, nonces, tgt, valid)
        return jax.lax.pmin(sha_kernel.answer_words(hit, mesh.size), "dp")

    def per_device_pallas(mid, tail, span, tgt):
        return jax.lax.pmin(sha_kernel.pow_search_pallas_data(
            mid, tail, span, tgt, batch=batch_per_device,
            nonce_spec=nonce_spec, tile_rows=8 if interpret else 64,
            interpret=interpret, axis="dp"), "dp")

    pallas = resident_body(mesh, interpret) == "pallas"
    with jax.named_scope("upow.sha256_search"):
        return shard_map(
            per_device_pallas if pallas else per_device_jnp,
            mesh=mesh,
            in_specs=(P(), P(), P("dp"), P()),
            out_specs=P(),
            **check_kw,
        )(midstate, tail_words, ranges, target)


def pow_search_resident(midstate, tail_words, ranges, target,
                        batch_per_device: int, nonce_spec,
                        mesh: Optional[Mesh] = None,
                        interpret: bool = False):
    """Dispatch the resident program over explicit per-shard ranges.

    Arguments are already device-typed arrays (the mesh engine keeps the
    template resident and only swaps these between jobs); returns
    ``sha256.answer_words`` after the ``pmin`` collective: the global
    minimum hit nonce (or SENTINEL) and each shard's exact steps, still
    on the device (``sha256.SearchAnswer`` reads them).
    """
    mesh = mesh or make_mesh()
    return _pow_search_mesh_resident(
        midstate, tail_words, ranges, target,
        batch_per_device, nonce_spec, mesh, interpret,
    )


def pow_search_sharded(template: SearchTemplate, spec: TargetSpec,
                       nonce_base: int, batch_per_device: int,
                       mesh: Optional[Mesh] = None):
    """Search ``n_devices * batch_per_device`` nonces starting at
    ``nonce_base``, one contiguous block per chip; returns the global
    minimum hit (or SENTINEL) after an ICI ``pmin``."""
    mesh = mesh or make_mesh()
    return _pow_search_mesh(
        jnp.asarray(template.midstate), jnp.asarray(template.tail_words),
        jnp.uint32(nonce_base).reshape(()), batch_per_device,
        template.nonce_spec, spec, mesh,
    )


def shard_batch_arrays(mesh: Mesh, *arrays):
    """Place arrays with their last (batch) axis sharded over the mesh.

    For the verify kernel: inputs are (21, N) limbs / (N,) masks with N a
    multiple of the device count; XLA then runs the whole program SPMD
    with no collectives (it is elementwise over the batch).
    """
    out = []
    for a in arrays:
        spec = P(*([None] * (a.ndim - 1) + ["dp"]))
        # data placement onto an already-armed mesh, not a dispatch —
        # callers reach this from inside runtime-submitted work
        out.append(jax.device_put(  # upowlint: disable=DR001
            a, NamedSharding(mesh, spec)))
    return tuple(out)
