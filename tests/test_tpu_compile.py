"""Ahead-of-time compiles of the served drain for a described TPU v5e.

Interpret mode runs the kernel bodies on the CPU but never meets the TPU
compiler, which refuses what interpret mode accepts (unaligned slices,
too much VMEM, an unpartitionable kernel).  These tests compile the fused
RowClone drain for a v5e chip that is described, not attached, at the
pool shapes ``chip_smoke.py`` serves: llama3.2-3b at published widths
(28 layers, 64-token pages, 8 KV heads x 128, bf16), 16 sequences x 16
blocks plus a 16-slot staging ring, ``block_axis=1``.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.cmdqueue import partition_commands
from repro.core.opcodes import (OP_CROSS_POOL_COPY, OP_FPM_COPY, OP_NOP,
                                OP_PSM_COPY, OP_ZERO_INIT)
from repro.core.poolspec import PoolGroup, PoolSpec
from repro.kernels import fused_dispatch as kfd

LAYERS, PAGE, KV_HEADS, HEAD_DIM = 28, 64, 8, 128   # llama3.2-3b
NBLK, STAGE = 16 * 16, 16
DTYPE = jnp.bfloat16
PRIMARY = (True, True, False, False)                # k, v, k_stage, v_stage


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:       # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        # a compile for a described chip can be written to the persistent
        # cache but not read back without one
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)


def _pool_shapes():
    blk = (PAGE, KV_HEADS, HEAD_DIM)
    return [(LAYERS, NBLK) + blk, (LAYERS, NBLK) + blk,
            (LAYERS, STAGE) + blk, (LAYERS, STAGE) + blk]


@pytest.mark.parametrize("overlap", [True, False])
def test_fused_drain_compiles_at_full_width(topo, overlap):
    """One chip: the single-launch drain over K/V pools and their staging
    ring compiles, in place (pools aliased), with the Pallas kernel in
    the program."""
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=DTYPE):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pools = tuple(sds(s) for s in _pool_shapes())
    zeros = tuple(sds((1, PAGE, KV_HEADS, HEAD_DIM)) for _ in pools)
    compiled = kfd._fused_dispatch_jit.lower(
        sds((64, 3), jnp.int32), zeros, pools, block_axis=1,
        interpret=False, primary=PRIMARY, overlap=overlap).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(s)) * 2 for s in _pool_shapes())
    assert mem.alias_size_in_bytes >= pool_bytes     # drained in place
    assert mem.temp_size_in_bytes < pool_bytes // 8


def _shard_plan():
    """A served round's table, partitioned over 4 shards: a CoW copy, a
    cross-slab copy, a zero-init and one stage->KV promotion per pool."""
    blk = (LAYERS, PAGE, KV_HEADS, HEAD_DIM)
    group = PoolGroup([
        PoolSpec("k", NBLK, blk, DTYPE), PoolSpec("v", NBLK, blk, DTYPE),
        PoolSpec("k_stage", STAGE, blk, DTYPE, role="staging", paired="k"),
        PoolSpec("v_stage", STAGE, blk, DTYPE, role="staging", paired="v")])
    ks, vs = 2 * NBLK, 2 * NBLK + STAGE          # global bases of staging
    rows = [(OP_FPM_COPY, 1, 2), (OP_PSM_COPY, 3, NBLK - 1),
            (OP_ZERO_INIT, -1, 70),
            (OP_CROSS_POOL_COPY, ks + 0, 5),
            (OP_CROSS_POOL_COPY, vs + 0, NBLK + 5)]
    plan = partition_commands(rows, n_shards=4, group=group,
                              replicated=(False,) * 4)
    assert plan.deltas, "the plan should carry a cross-slab hop"
    return plan


def test_sharded_drain_compiles_on_four_chips(topo):
    """Four described chips: the one-``shard_map`` collective drain (per
    slab Pallas sub-tables plus the ppermute send/recv plan) compiles at
    full width with pools sharded over a 4-device data mesh."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    plan = _shard_plan()
    runner = kfd._sharded_runner(mesh, ("data",), tuple(plan.deltas), 4, 1,
                                 True, False, PRIMARY, (False,) * 4)

    def sds(arr_or_shape, spec, dtype):
        shape = getattr(arr_or_shape, "shape", arr_or_shape)
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    pools = tuple(sds(s, P(None, "data"), DTYPE) for s in _pool_shapes())
    zeros = tuple(sds((1, PAGE, KV_HEADS, HEAD_DIM), P(), DTYPE)
                  for _ in pools)
    compiled = runner.lower(
        sds(plan.local_tables, P("data", None, None), jnp.int32),
        sds(plan.send_rows, P(None, "data", None), jnp.int32),
        sds(plan.recv_tables, P(None, "data", None, None), jnp.int32),
        zeros, pools).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
    per_device = sum(int(np.prod(s)) * 2 for s in _pool_shapes()) // 4
    assert compiled.memory_analysis().alias_size_in_bytes >= per_device


def test_shard_plan_covers_the_round():
    """The plan compiled above is the served round: every row but the
    cross-slab copy drains in its own slab, the copy rides one hop."""
    plan = _shard_plan()
    assert plan.local_tables.shape[0] == 4
    assert plan.n_transfer == 1 and plan.n_local == 4
    assert int((plan.local_tables[..., 0] != OP_NOP).sum()) == 4
