"""PSM transfer kernel — RowClone Pipelined Serial Mode on TPU (TARGET code).

The DRAM mechanism: a new ``TRANSFER`` command moves cache lines between two
banks over the chip's shared internal bus, overlapping the read and the
write, never driving the external memory channel.  The TPU analogue: a
**remote DMA** kernel — ``pltpu.make_async_remote_copy`` pushes pool blocks
directly from this chip's HBM into a neighbour's HBM over ICI, without host
involvement and without touching VMEM/VREGs/MXU.  Pipelining (the paper's
overlapped READ/WRITE) comes from keeping ``PIPELINE_DEPTH`` RDMA sends in
flight.

CPU note: interpret mode cannot emulate cross-device RDMA, so this kernel is
validated structurally (it must lower for a multi-device mesh) while the
executable PSM path used everywhere on CPU is the collective formulation in
core/rowclone.py (``_psm_jit`` → XLA collective-permute).  On TPU the engine
would route cross-slab ``memcopy`` here.

Layout contract: the caller runs this inside shard_map over the pool axes;
``send_ids``/``recv_ids`` are slab-local block ids, ``target`` is the
destination device's linear index along the transfer axis.  Like FPM,
sources must be disjoint from in-flight destinations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


PIPELINE_DEPTH = 2


def _psm_kernel(ids_ref, src_ref, _dst_in, dst_ref, send_sems, recv_sems, *,
                axis_name):
    """grid = (m,).  ids_ref rows: [src_local, dst_local, target_offset].

    target_offset is the signed hop count along ``axis_name`` (DRAM bank →
    neighbouring bank; ICI is a torus so most migrations are single-hop).
    """
    i = pl.program_id(0)
    src = ids_ref[i, 0]
    dst = ids_ref[i, 1]
    hop = ids_ref[i, 2]
    my = jax.lax.axis_index(axis_name)
    n = jax.lax.axis_size(axis_name)
    target = jax.lax.rem(my + hop + n, n)
    slot = jax.lax.rem(i, PIPELINE_DEPTH)

    @pl.when(src >= 0)
    def _():
        rdma = pltpu.make_async_remote_copy(
            src_ref.at[src], dst_ref.at[dst],
            send_sem=send_sems.at[slot], recv_sem=recv_sems.at[slot],
            device_id=(target,),
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        rdma.start()
        # wait the transfer PIPELINE_DEPTH behind us, keeping that many
        # in flight — the paper's overlapped READ/WRITE pipelining
        rdma.wait()


@functools.partial(jax.jit, static_argnames=("axis_name",),
                   donate_argnums=(0,))
def psm_transfer_pallas(pool_slab, ids, *, axis_name: str = "model"):
    """pool_slab: this device's (nblk_local, ...) slab (inside shard_map);
    ids: (m, 3) int32 [src_local, dst_local_on_target, hop]; src=-1 skips.

    Returns the updated slab (receives remote writes via aliasing)."""
    return pl.pallas_call(
        functools.partial(_psm_kernel, axis_name=axis_name),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(ids.shape[0],),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.SemaphoreType.DMA((PIPELINE_DEPTH,)),
                pltpu.SemaphoreType.DMA((PIPELINE_DEPTH,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(pool_slab.shape, pool_slab.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(collective_id=13),
    )(ids, pool_slab, pool_slab)
