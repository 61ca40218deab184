"""Compile-only checks: the query path compiles for a TPU v5e.

Nothing runs here.  Each case lowers a kernel or a jitted step at the real
tile sizes (256×256, capacity 4096) and compiles it with the TPU compiler
for a v5e chip — or a 2×2 mesh of them — that is described, not attached.
What the chip's compiler would refuse (an unaligned block, a primitive
Mosaic cannot lower, a kernel that cannot be partitioned) fails here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.distributed import make_pod_query_fn
from repro.kernels import distthresh as dt
from repro.kernels import ops

C, Q, CAPACITY, TILE, PODS = 2048, 1024, 4096, 256, 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def chip(topo, no_persistent_cache):
    """Shape factory: arrays placed on one described v5e chip."""
    one = SingleDeviceSharding(topo.devices[0])

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    return shape


def _compiled_kernel(fn, *args, **kwargs):
    compiled = jax.jit(fn, **kwargs).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_dense_kernel_compiles(chip):
    _compiled_kernel(
        lambda e, q, d: dt.distthresh_pallas(
            e, q, d, cand_blk=TILE, qry_blk=TILE, interpret=False),
        chip(C, 8), chip(8, Q), chip())


def test_compact_kernel_compiles(chip):
    _compiled_kernel(
        lambda e, q, d: dt.distthresh_compact_pallas(
            e, q, d, capacity=CAPACITY, cand_blk=TILE, qry_blk=TILE,
            interpret=False),
        chip(C, 8), chip(8, Q), chip())


def test_compact_kernel_armed_compiles(chip):
    _compiled_kernel(
        lambda e, q, d, em, qm, dp: dt.distthresh_compact_pallas(
            e, q, d, capacity=CAPACITY, cand_blk=TILE, qry_blk=TILE,
            interpret=False, e_mbr=em, q_mbr=qm, d_prune=dp),
        chip(C, 8), chip(8, Q), chip(), chip(C // TILE, 8),
        chip(Q // TILE, 8), chip())


def test_live_tile_kernel_compiles(chip):
    slots = (C // TILE) * (Q // TILE)
    _compiled_kernel(
        lambda e, q, d, ti, tj, nl: dt.distthresh_compact_live_pallas(
            e, q, d, ti, tj, nl, capacity=CAPACITY, cand_blk=TILE,
            qry_blk=TILE, interpret=False),
        chip(C, 8), chip(8, Q), chip(), chip(slots, dtype=jnp.int32),
        chip(slots, dtype=jnp.int32), chip(1, dtype=jnp.int32))


def test_jnp_query_block_compiles(chip):
    compiled = ops._query_block_jit.lower(
        chip(C, 8), chip(Q, 8), chip(), capacity=CAPACITY, use_pallas=False,
        interpret=False, cand_blk=TILE, qry_blk=TILE,
        compaction="dense").compile()
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("pruning", ["spatial", "hierarchical"])
def test_pod_step_compiles_on_four_chip_mesh(topo, no_persistent_cache,
                                             pruning):
    """The sparse shard step with the compiled fused kernel, one pod per
    chip of a 2×2 v5e: every chip runs the kernel, and the hit counts meet
    in one all-reduce."""
    mesh = Mesh(np.asarray(topo.devices[:PODS]), ("pod",))

    def shape(dims, spec, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, spec))

    fn = make_pod_query_fn(mesh, CAPACITY, use_pallas=True, interpret=False,
                           cand_blk=TILE, qry_blk=TILE, compaction="fused",
                           pruning=pruning, sparse=True)
    compiled = fn.lower(
        shape((PODS, C, 8), P("pod", None, None)),
        shape((PODS,), P("pod"), jnp.int32),
        shape((PODS,), P("pod"), jnp.int32),
        shape((TILE, 8), P(None, None)),
        shape((), P())).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
    # one program per chip: per-device operands are one pod's block
    assert compiled.input_shardings[0][0].shard_shape((PODS, C, 8)) == (
        1, C, 8)
