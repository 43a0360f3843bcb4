"""Paged KV-cache device math: append + partial attention + LSE combine.

Pool layout ("subarray slabs", DESIGN.md §2): every attention layer owns K/V
pools of shape ``(nblk, page, KVH, D)``.  The block axis is sharded jointly
over ``(pod, data, model)``: each device holds one *slab* — the RowClone
subarray analogue.  The allocator (core/allocator.py) is placement-aware so a
sequence's blocks live in the mesh row that owns the sequence; decode
attention then needs **zero page movement** — each device sweeps its own slab
and partial results are LSE-combined over the model axis only.

When the batch is too small to shard (long_500k, B=1) the sequence's blocks
spread over the whole mesh and the combine spans all axes — turning the
entire pod into one flash-decoding ring for a single 500k-token sequence.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.attention import lse_combine, paged_attention_slab


# ---------------------------------------------------------------------------
# spec helpers
# ---------------------------------------------------------------------------

def pool_shard_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes (in shard order) that a pool's block axis shards over."""
    return tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)


def pool_shard_count(mesh: Optional[Mesh]) -> int:
    """Device shards of a pool's block axis: joint size of every
    pool-sharding axis present; 1 with no mesh.  The single owner of this
    arithmetic — the engine's sharded dispatch gates on it and the serving
    layer rounds pool sizes with it (``nblk % shards == 0``)."""
    if mesh is None:
        return 1
    axes = pool_shard_axes(mesh)
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def batch_shard_axes(mesh: Mesh, batch: int) -> Tuple[str, ...]:
    """Mesh axes the decode batch shards over: the (pod, data) subset when
    it divides ``batch``, else () (replicated batch — e.g. B=1 long-context
    where the whole pod sweeps for one sequence)."""
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    return dp if dp and batch % size == 0 else ()


def batch_shard_count(mesh: Optional[Mesh], batch: int) -> int:
    """Device groups the decode batch splits into (1 = replicated batch).
    The single owner of this arithmetic for the serving layer: the
    PagedCoWCache uses it to emit LOCAL share-mask columns and to pin each
    sequence's blocks inside its group's slabs."""
    if mesh is None:
        return 1
    axes = batch_shard_axes(mesh, batch)
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def combine_axes(mesh: Mesh, batch_axes: Tuple[str, ...]) -> Tuple[str, ...]:
    """Pool axes over which decode partials must be LSE-combined, given
    the axes the batch ACTUALLY shards over (which may be () even for a
    divisible batch — the share-mask column count is the contract, see
    :func:`paged_attend_append`)."""
    bs = set(batch_axes)
    return tuple(a for a in pool_shard_axes(mesh) if a not in bs)


def pool_spec(mesh: Mesh) -> P:
    """PartitionSpec sharding a flat pool's leading block axis."""
    axes = pool_shard_axes(mesh)
    return P(axes if len(axes) > 1 else (axes[0] if axes else None))


def pool_partition_spec(mesh: Mesh, spec=None, block_axis: int = 0) -> P:
    """PartitionSpec for one pool honoring its ``PoolSpec.sharding`` hint.

    ``spec`` may be a :class:`~repro.core.poolspec.PoolSpec`, a raw hint
    tuple, or None.  Hint semantics: ``None`` (or no spec) = the default
    joint pool axes (``pool_shard_axes``); ``()`` = **replicated** — the
    pool's block axis is held whole on every device (what a small staging
    ring wants: slots stay addressable without rounding the ring up to
    the shard count); a non-empty tuple = exactly those mesh axes (absent
    axes are dropped).  ``block_axis`` positions the sharded dimension
    (serving pools are layer-stacked, block axis 1)."""
    hint = getattr(spec, "sharding", spec)
    if hint is None:
        axes = pool_shard_axes(mesh)
    else:
        axes = tuple(a for a in hint if a in mesh.axis_names)
    return P(*([None] * block_axis),
             axes if len(axes) > 1 else (axes[0] if axes else None))


def _maybe(axes: Tuple[str, ...]):
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


# ---------------------------------------------------------------------------
# pool construction — K/V pools and their staging pools are ONE layout
# decision (same block shape, same dtype, same (pod, data, model) sharding
# of the block axis), so cross-pool promotion commands are always legal
# ---------------------------------------------------------------------------

def make_serving_pools(num_layers: int, nblk: int, page: int, kv_heads: int,
                       head_dim: int, dtype,
                       staging: bool = True,
                       stage_nblk: Optional[int] = None,
                       replicate_staging: bool = False,
                       ckpt_nblk: int = 0,
                       replicate_ckpt: bool = False):
    """Build the serving engine's pools: layer-stacked ``(L, nblk, page,
    KVH, D)`` K/V pools plus (by default) their staging pools.

    The staging pools are where prefill writes land; staged pages promote
    into allocator-owned K/V blocks via ``OP_CROSS_POOL_COPY`` through the
    command queue (RowCloneEngine ``promote_staged``), so every byte of
    bulk movement in a serving round rides one fused launch.

    ``stage_nblk`` sizes the staging pools INDEPENDENTLY of their KV
    twins: ``None`` keeps the full-size twin (every KV block has a staging
    slot), while a small value builds a staging *ring* — just enough slots
    to park the admissions between two flushes — which is what cuts the
    serving engine's resident pool bytes by ~2x (slots recycle every
    round; see launch/serve.py ``max_admit_pages``).  Under a mesh it
    either divides by the same ``pool_shard_count`` as ``nblk`` or sets
    ``replicate_staging=True``: the staging specs get the ``()`` sharding
    hint, the ring is held whole on every device
    (:func:`pool_partition_spec`), and promotions out of it are always
    slab-local in the collective drain — the placement override that
    keeps an oddly-sized ring from rounding up to the shard count.

    ``ckpt_nblk > 0`` adds ``k_spill``/``v_spill`` pools of that many
    blocks (``role="spill"``, paired with K/V): the background checkpoint
    stream's copy window — primary blocks spill into them as cross-pool
    traffic overlapping decode, then stream to disk
    (checkpoint/pool_checkpoint.py).  ``replicate_ckpt`` is the same
    placement override as ``replicate_staging``, for spill windows that
    don't divide the shard count.

    Returns ``(pools, group)``: the name -> array dict plus the
    :class:`~repro.core.poolspec.PoolGroup` describing the engine's
    address space (per-pool block counts, roles, sharding hint) — both go
    straight into the RowCloneEngine constructor.
    """
    from repro.core.poolspec import PoolGroup, PoolSpec
    if stage_nblk is None:
        stage_nblk = nblk
    block_shape = (num_layers, page, kv_heads, head_dim)
    shape = (num_layers, nblk, page, kv_heads, head_dim)
    sshape = (num_layers, stage_nblk, page, kv_heads, head_dim)
    hint = ("pod", "data", "model")
    pools = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    specs = [PoolSpec("k", nblk, block_shape, dtype, sharding=hint),
             PoolSpec("v", nblk, block_shape, dtype, sharding=hint)]
    if staging:
        shint = () if replicate_staging else hint
        pools["k_stage"] = jnp.zeros(sshape, dtype)
        pools["v_stage"] = jnp.zeros(sshape, dtype)
        specs += [PoolSpec("k_stage", stage_nblk, block_shape, dtype,
                           role="staging", paired="k", sharding=shint),
                  PoolSpec("v_stage", stage_nblk, block_shape, dtype,
                           role="staging", paired="v", sharding=shint)]
    if ckpt_nblk > 0:
        chint = () if replicate_ckpt else hint
        cshape = (num_layers, ckpt_nblk, page, kv_heads, head_dim)
        pools["k_spill"] = jnp.zeros(cshape, dtype)
        pools["v_spill"] = jnp.zeros(cshape, dtype)
        specs += [PoolSpec("k_spill", ckpt_nblk, block_shape, dtype,
                           role="spill", paired="k", sharding=chint),
                  PoolSpec("v_spill", ckpt_nblk, block_shape, dtype,
                           role="spill", paired="v", sharding=chint)]
    return pools, PoolGroup(specs)


# ---------------------------------------------------------------------------
# the per-layer decode step
# ---------------------------------------------------------------------------

def paged_attend_append(mesh: Optional[Mesh], q, k_new, v_new, k_pool, v_pool,
                        blk_ids, offsets, share_mask, base, seq_lens,
                        impl: str = "ref", exclusive: bool = False):
    """Append this step's K/V then attend over the paged cache.

    q:        (B, H, D)      new-token queries, post-RoPE
    k_new/v_new: (B, KVH, D) new-token keys/values, post-RoPE
    k_pool/v_pool: (nblk, page, KVH, D) — block axis sharded (pod,data,model)
    blk_ids:  (B,) int32     GLOBAL pool block id receiving this token
    offsets:  (B,) int32     slot within that block
    share_mask: block-readable-by-sequence bitmap, int8.  Its COLUMN COUNT
                is the batch-sharding contract: ``(nblk, B // dp)`` means
                local columns — the batch shards over (pod, data) and row
                ``b``'s columns index the batch group owning block ``b``'s
                shard (every sequence's blocks must live in its own group's
                slabs); ``(nblk, B)`` means global columns — the batch
                stays replicated and partials combine over every pool axis
                (correct for any block placement).
    base:     (nblk,) int32  token offset of block within its sequence
    seq_lens: (B,) int32     sequence length INCLUDING the new token

    Returns (out (B,H,D), k_pool', v_pool').
    """
    page = k_pool.shape[1]
    if mesh is None or int(np.prod(mesh.devices.shape)) == 1:
        return _attend_append_local(q, k_new, v_new, k_pool, v_pool, blk_ids,
                                    offsets, share_mask, base, seq_lens,
                                    page=page, impl=impl,
                                    exclusive=exclusive)

    B = q.shape[0]
    b_axes = batch_shard_axes(mesh, B)
    dp = int(np.prod([mesh.shape[a] for a in b_axes])) if b_axes else 1
    if b_axes and share_mask.shape[1] != B // dp:
        # mask columns are GLOBAL batch numbering: the caller's placement
        # isn't group-aligned, so replicate the batch instead of sharding
        # it (every slab serves every sequence; combine spans all axes)
        b_axes = ()
    bspec = _maybe(b_axes)
    pspec = pool_spec(mesh)
    mspec = P(pspec[0], None)
    comb = combine_axes(mesh, b_axes)

    fn = functools.partial(_attend_append_local, combine=comb,
                           pool_axes=pool_shard_axes(mesh), page=page,
                           impl=impl, exclusive=exclusive)
    mapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(bspec), P(bspec), P(bspec), pspec, pspec,
                  P(bspec), P(bspec), mspec, pspec, P(bspec)),
        out_specs=(P(bspec), pspec, pspec),
        check_vma=False,
    )
    return mapped(q, k_new, v_new, k_pool, v_pool, blk_ids, offsets,
                  share_mask, base, seq_lens)


def _attend_append_local(q, k_new, v_new, k_slab, v_slab, blk_ids, offsets,
                         share_mask, base, seq_lens, combine=(),
                         pool_axes=(), page=64, impl="ref",
                         exclusive=False):
    slab = k_slab.shape[0]
    # blk_ids are global pool row numbers; this device's slab starts at the
    # shard-order offset over ALL axes sharding the pool.
    my0 = _slab_offset(pool_axes, slab) if pool_axes else jnp.int32(0)
    local = blk_ids - my0
    ok = (local >= 0) & (local < slab)
    safe = jnp.where(ok, local, slab)
    k_slab = k_slab.at[safe, offsets].set(k_new.astype(k_slab.dtype),
                                          mode="drop")
    v_slab = v_slab.at[safe, offsets].set(v_new.astype(v_slab.dtype),
                                          mode="drop")
    acc, l, m = paged_attention_slab(q, k_slab, v_slab, share_mask, base,
                                     seq_lens, page=page, impl=impl,
                                     exclusive=exclusive)
    if combine:
        out = lse_combine(acc, l, m, combine)
    else:
        out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype), k_slab, v_slab


def _slab_offset(pool_axes: Tuple[str, ...], slab: int):
    """Global row offset of this device's slab, given the axes sharding the
    block dimension *in shard order*."""
    idx = jnp.int32(0)
    for a in pool_axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx * slab


# ---------------------------------------------------------------------------
# contiguous "identity" allocation used by prefill and the dry-run
# ---------------------------------------------------------------------------

def identity_layout(batch: int, seq_len: int, page: int, dp: int = 1):
    """Block table/share-mask/base for the contiguous layout where sequence
    b's j-th block is pool row b*nblk_per_seq + j.  With the
    (pod,data,model) pool sharding this lands every sequence's blocks in its
    own mesh row — the subarray-aware placement from the paper, as layout
    math.

    Returns (block_table (B, nper), share_mask (nblk, B//dp) int8,
    base (nblk,)).  The mask columns use LOCAL batch numbering when the
    batch will be sharded ``dp`` ways (identity layout shards contiguous
    batch groups, so local index = b % (B/dp))."""
    nper = (seq_len + page - 1) // page
    nblk = batch * nper
    table = np.arange(nblk, dtype=np.int32).reshape(batch, nper)
    owner = np.repeat(np.arange(batch, dtype=np.int32), nper)
    base = np.tile(np.arange(nper, dtype=np.int32) * page, batch)
    b_local = batch // dp if dp > 1 and batch % dp == 0 else batch
    mask = np.zeros((nblk, b_local), np.int8)
    mask[np.arange(nblk), owner % b_local] = 1
    return table, mask, base
