"""Public jit'd wrappers for the Pallas kernels.

Every op takes ``impl``/platform into account: on TPU the Pallas kernel runs
compiled; on CPU the *reference* implementation runs by default (fast,
HLO-small — important inside the 512-device dry-run), while tests force
``interpret=True`` to execute the actual kernel bodies on CPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fpm_copy import fpm_copy_cross_pallas, fpm_copy_pallas
from repro.kernels import fused_dispatch as kfd
from repro.kernels.paged_attention import paged_attention_slab_pallas
from repro.kernels.ssd_chunk import ssd_intra_chunk_pallas
from repro.kernels.zero_init import zero_init_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not _on_tpu()


def _resolve_use_pallas(use_pallas: Optional[bool]) -> bool:
    """The one resolution rule for every op: ``None`` means "Pallas on TPU,
    reference elsewhere"; an explicit bool always wins (tests pass ``True``
    with interpret mode to execute the kernel bodies on CPU)."""
    return _on_tpu() if use_pallas is None else bool(use_pallas)


# ---------------------------------------------------------------------------
# RowClone primitives
# ---------------------------------------------------------------------------

def fpm_copy(pool, ids, *, use_pallas: Optional[bool] = None):
    """In-pool FPM block copy.  ids: (m,2) [src,dst], dst=-1 skips."""
    if _resolve_use_pallas(use_pallas):
        return fpm_copy_pallas(pool, ids, interpret=_interpret())
    return kref.fpm_copy(pool, ids[:, 0], ids[:, 1])


def fpm_copy_cross(dst_pool, src_pool, ids, *, use_pallas: Optional[bool] = None):
    """Pool-to-pool FPM block copy (dst_pool[dst] = src_pool[src])."""
    if _resolve_use_pallas(use_pallas):
        return fpm_copy_cross_pallas(dst_pool, src_pool, ids,
                                     interpret=_interpret())
    return kref.fpm_copy_cross(dst_pool, src_pool, ids[:, 0], ids[:, 1])


def meminit_zero(pool, zero_block, ids, *, use_pallas: Optional[bool] = None):
    """BuZ: DMA-broadcast the reserved zero block into ``ids``."""
    if _resolve_use_pallas(use_pallas):
        return zero_init_pallas(pool, zero_block, ids, interpret=_interpret())
    return kref.zero_init(pool, ids)


@functools.partial(jax.jit, static_argnames=("block_axis", "primary"),
                   donate_argnums=(2,))
def _fused_ref_jit(cmds, zero_blocks, pools, *, block_axis, primary=None):
    return kref.fused_dispatch(pools, zero_blocks, cmds,
                               block_axis=block_axis, primary=primary)


def fused_dispatch(pools, zero_blocks, cmds, *, block_axis: int = 0,
                   use_pallas: Optional[bool] = None,
                   primary: Optional[tuple] = None,
                   overlap: bool = True):
    """One launch for a whole flushed command table over every pool.

    See kernels/fused_dispatch.py for the opcode table and contract.  On
    CPU the jit'd reference executes (one dispatch, HLO-small); tests force
    ``use_pallas=True`` to run the kernel body in interpret mode.
    ``primary`` is the per-pool role vector (True = plain opcodes move the
    block there); pools may carry different block counts — cross-pool rows
    use global prefix-sum-base ids.  ``overlap`` selects the kernel's
    overlapped vs serial DMA drain (a tuned-profile knob; the jnp
    reference has no DMA pipeline, so it ignores it).
    """
    primary = kfd._as_primary(primary, len(pools))
    if _resolve_use_pallas(use_pallas):
        return kfd.fused_dispatch_pallas(pools, zero_blocks, cmds,
                                         block_axis=block_axis,
                                         interpret=_interpret(),
                                         primary=primary, overlap=overlap)
    out = _fused_ref_jit(cmds, tuple(zero_blocks), tuple(pools),
                         block_axis=block_axis, primary=primary)
    kfd.notify_launch(int(cmds.shape[0]), len(out), "fused")
    return tuple(out)


def fused_dispatch_sharded(pools, zero_blocks, plan, *, mesh, pool_axes,
                           block_axis: int = 0,
                           use_pallas: Optional[bool] = None,
                           primary: Optional[tuple] = None,
                           replicated: Optional[tuple] = None):
    """One collective launch for a whole flushed command table across the
    mesh: per-slab fused sub-tables + the cross-slab send/recv plan
    (cmdqueue.ShardPlan; every pool partitions by its own shard size).
    Resolution matches every other op: the per-shard drain runs the Pallas
    kernel body on TPU (or in interpret mode when forced) and the jnp
    reference elsewhere; the inter-slab hops are ppermute collectives
    either way.  ``primary`` as in :func:`fused_dispatch`; ``replicated``
    marks pools held whole on every device (must match the plan)."""
    return kfd.sharded_fused_dispatch(
        pools, zero_blocks, plan, mesh=mesh, pool_axes=pool_axes,
        block_axis=block_axis, use_pallas=_resolve_use_pallas(use_pallas),
        interpret=_interpret(), primary=primary, replicated=replicated)


def baseline_copy(pool, ids):
    """The mechanism RowClone replaces: blocks round-trip the compute
    pipeline.  Used by benchmarks for the Table-1 comparison."""
    return kref.baseline_copy(pool, ids[:, 0], ids[:, 1])


def psm_transfer(pool_slab, ids, *, axis_name: str = "model"):
    """PSM cross-chip RDMA block transfer (TARGET TPU kernel; on CPU the
    engine routes cross-slab copies through the collective path instead —
    see kernels/psm_transfer.py)."""
    from repro.kernels.psm_transfer import psm_transfer_pallas
    return psm_transfer_pallas(pool_slab, ids, axis_name=axis_name)


# ---------------------------------------------------------------------------
# attention / ssd
# ---------------------------------------------------------------------------

def paged_attention_slab(q, k_slab, v_slab, share_mask, base, seq_lens, *,
                         page: int, use_pallas: Optional[bool] = None):
    """Partial decode attention over one pool slab (see kernels/ref.py
    ``paged_attention_slab`` for the full contract)."""
    if _resolve_use_pallas(use_pallas):
        return paged_attention_slab_pallas(q, k_slab, v_slab, share_mask,
                                           base, seq_lens, page=page,
                                           interpret=_interpret())
    return kref.paged_attention_slab(q, k_slab, v_slab, share_mask, base,
                                     seq_lens, page=page)


def flash_attention(q, k, v, *, causal=True, prefix_len=0,
                    use_pallas: Optional[bool] = None):
    """q: (B,H,S,D); k/v: (B,KVH,S,D)."""
    if _resolve_use_pallas(use_pallas):
        return flash_attention_pallas(q, k, v, causal=causal,
                                      prefix_len=prefix_len,
                                      interpret=_interpret())
    B, H, S, D = q.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    out = kref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), pos, pos, jnp.ones((B, S), bool),
        causal=causal, prefix_len=prefix_len)
    return out.transpose(0, 2, 1, 3)


def ssd_intra_chunk(xb, dtb, cum, Bb, Cb, *, use_pallas: Optional[bool] = None):
    """Mamba2 SSD intra-chunk quadratic term (kernels/ssd_chunk.py)."""
    if _resolve_use_pallas(use_pallas):
        return ssd_intra_chunk_pallas(xb, dtb, cum, Bb, Cb,
                                      interpret=_interpret())
    from repro.models.mamba2 import _ssd_intra_chunk_jnp
    return _ssd_intra_chunk_jnp(xb, dtb, cum, Bb, Cb)
