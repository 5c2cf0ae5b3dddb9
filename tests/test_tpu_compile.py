"""The kernels of the served path, compiled for a described TPU v5e.

No chip is attached here; the TPU compiler is.  Each case lowers the
jitted program at the shape the node or the miner really dispatches and
compiles it for one device of a ``v5e:2x2`` topology (the mesh search:
for all four), so what Mosaic or XLA:TPU would refuse on the chip —
tiling, fast-memory budget, an unpartitionable kernel — fails here, at
no chip time.  A compile that passes says nothing about results or
speed; ``chip_smoke.py`` is the run on the chip.

Only one process may hold the TPU library, and it keeps it until it
exits, so the topology is described inside this file's module fixture
(never at import), every compile runs in the test's own process, and all
cases live in this one file so one xdist worker gets them.

The whole fused verify program (scalar prep + ladder,
``p256._prep_and_verify_pallas_jac``) is compiled at the two shapes a
validator meets first, 8,192 lanes for a block and 128 for a burst: a
minute and half a minute here since PR 46 (the field arithmetic traced
once; the ladder alone took two minutes a shape before), most of it
XLA:TPU's time over the scalar prep.
"""

import os
import re
from decimal import Decimal

import numpy as np
import pytest

# the compiler otherwise logs under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without the chip (the next run warns
    and recompiles): keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# ------------------------------------------------------------ sha256 ----

@pytest.mark.parametrize("difficulty,prefix_len", [
    ("6.0", 104), ("6.3", 104),
    ("6.0", 134),   # a v1 header: one round hoisted, the nonce over w1/w2
])
def test_sha256_pallas_search_compiles(one_chip, no_compile_cache,
                                       difficulty, prefix_len):
    """The miner's default round: search_batch 2^24, tile_rows 64, at
    the protocol's start difficulty and at a fractional one (the
    charset branch of the kernel); ``[base, limit)`` is one SMEM operand
    of two words, midstate and tail the template's own two arrays."""
    from upow_tpu.config import DeviceConfig
    from upow_tpu.crypto import sha256 as sk

    template = sk.make_template(bytes(prefix_len))
    spec = sk.target_spec("ab" * 32, Decimal(difficulty))
    compiled = sk._pow_search_pallas.lower(
        _shape(template.midstate.shape, jnp.uint32, one_chip),
        _shape(template.tail_words.shape, jnp.uint32, one_chip),
        _shape((2,), jnp.uint32, one_chip),
        batch=DeviceConfig().search_batch, tile_rows=64,
        nonce_spec=template.nonce_spec, spec=spec,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -------------------------------------------------------------- p256 ----

def _ladder_args(n: int, sharding):
    """Operand shapes of the Jacobian ladder kernel for ``n`` lanes:
    what the device scalar prep hands it (traced, not run)."""
    from upow_tpu.crypto import p256

    outs = jax.eval_shape(
        lambda packed: p256._scalar_prep(*p256._unpack_fused(packed)),
        jax.ShapeDtypeStruct((42, n), jnp.uint32))
    return [_shape(o.shape, o.dtype, sharding) for o in outs]


@pytest.mark.parametrize("lanes,tile", [
    (128, 128),     # a small push_tx batch: one sublane row, grid 1
    (2048, 1024),   # a default node's first dispatch: 1024 + 2 canaries
])
def test_p256_jacobian_ladder_kernel_compiles(one_chip, no_compile_cache,
                                              lanes, tile):
    """The kernel alone (a quarter of a minute a shape): what Mosaic
    refuses shows here apart from what XLA:TPU says of the prep."""
    from upow_tpu.crypto import p256

    assert p256._pad_to_block(min(lanes, 1026)) == lanes
    assert p256._pick_tile(lanes) == tile
    compiled = p256._verify_device_pallas_jac.lower(
        *_ladder_args(lanes, one_chip), tile=tile,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lanes,tile", [
    (8192, 1024),   # a full block's 8,160 signatures in one dispatch
    (128, 128),     # a push_tx burst
])
def test_p256_fused_verify_program_compiles(one_chip, no_compile_cache,
                                            lanes, tile):
    """Scalar prep + ladder as the node dispatches them, one program a
    padded shape."""
    from upow_tpu.crypto import p256

    assert p256._pad_to_block(min(lanes, 8160)) == lanes
    assert p256._pick_tile(lanes) == tile
    compiled = p256._prep_and_verify_pallas_jac.lower(
        _shape((42, lanes), jnp.uint32, one_chip), tile=tile).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -------------------------------------------------------- utxo probe ----

def test_utxo_probe_kernel_compiles(one_chip, no_compile_cache):
    """The resident-index probe (plain XLA, no Pallas) at the size of
    one full block: 8,160 outpoints queried against the 16,384-slot
    table the fan-out leaves, default window."""
    from upow_tpu.state import device_index as di

    cap, queries = di._pow2(8160 + 8160), di._pow2(8160)
    lane = _shape((cap,), jnp.int32, one_chip)
    query = _shape((queries,), jnp.int32, one_chip)
    compiled = di._probe_kernel.lower(
        lane, lane, lane, lane, lane, lane,
        _shape((), jnp.int32, one_chip),
        query, query, query, query,
        window=di.PROBE_WINDOW).compile()
    assert compiled.memory_analysis() is not None


def test_utxo_apply_kernel_compiles_at_a_deployments_size(
        one_chip, no_compile_cache):
    """One block's delta applied to the resident lanes (ISSUE 50), at
    the size the cell ``utxo-at-scale`` runs: capacity 2^22 over 4 M
    rows, a slab of 8,161 created rows and 8,160 spent (each padded to
    8,192); the six lanes are donated, so the program needs one
    more copy of them and not two."""
    from upow_tpu.state import device_index as di

    cap, new, gone = 1 << 22, di._pad_len(8161), di._pad_len(8160)
    lane = _shape((cap,), jnp.int32, one_chip)
    slab = _shape((new,), jnp.int32, one_chip)
    spent = _shape((gone,), jnp.int32, one_chip)
    count = _shape((), jnp.int32, one_chip)
    compiled = di._apply_kernel.lower(
        *([lane] * 6), count, *([slab] * 6), count, *([spent] * 5), count,
        window=di.PROBE_WINDOW).compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 6 * 4 * cap
    assert memory.temp_size_in_bytes < 16 * 4 * cap


# ------------------------------------------------------- mesh search ----

@pytest.mark.parametrize("n", [4, 1])
def test_mesh_resident_search_compiles_on_a_mesh_of(n, topo,
                                                    no_compile_cache):
    """`miner --device mesh` over the four chips of one host, and
    `--device tpu`, the same program on a mesh of one: one SPMD program,
    the default round split evenly over the devices, each shard searched
    by the Pallas kernel with target and range as SMEM data (the body is
    chosen from the mesh's platform), the hit reduced by a collective
    where there is more than one shard."""
    from upow_tpu.config import DeviceConfig
    from upow_tpu.crypto import sha256 as sk
    from upow_tpu.parallel import mesh as pm

    devices = topo.devices[:n]
    assert len(devices) == n
    mesh = Mesh(np.array(devices), axis_names=("dp",))
    assert pm.resident_body(mesh) == "pallas"
    rep = NamedSharding(mesh, P())
    dp = NamedSharding(mesh, P("dp"))
    words = sk.RESIDENT_OPERAND_WORDS
    assert sk.resident_operand([[1, 2]] * n).shape == (n, words)
    compiled = pm._pow_search_mesh_resident.lower(
        _shape((words,), jnp.uint32, rep), _shape((words,), jnp.uint32, rep),
        _shape((n, words), jnp.uint32, dp), _shape((words,), jnp.uint32, rep),
        batch_per_device=DeviceConfig().search_batch // n,
        nonce_spec=sk.make_template(bytes(104)).nonce_spec,
        mesh=mesh).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, \
        "each shard must run the Mosaic kernel, not XLA fusions"
    # operands of a page reach the kernel where they lie: no staging
    # copy, so a round is the kernel and the collective and no third
    # device operation (sha256.RESIDENT_OPERAND_WORDS)
    assert "copy-start" not in text and " copy(" not in text, \
        "XLA stages a kernel operand into scalar memory on every round"
    if n > 1:
        assert "all-reduce" in text or "all_reduce" in text, \
            "the pmin over dp must survive as a collective"
    # the kernel leaves its answer in the words the pmin reduces (the hit
    # flipped to s32, a slot a shard for the exact steps): one kernel a
    # shard, one collective, and no XLA operation between or after them
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    collectives = re.findall(
        r"\b(all-reduce|all-gather|all-to-all|collective-permute|"
        r"reduce-scatter)(?:-start)?\(", text)
    assert collectives == (["all-reduce"] if n > 1 else []), \
        "the exact steps ride in the hit's collective, not in a second"
    assert " fusion(" not in text, \
        "an XLA fusion beside the kernel is a device operation a round"
