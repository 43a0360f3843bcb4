"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each kernel's test sweeps shapes/dtypes
and asserts allclose against the function here.  They are also the execution
path on CPU (and inside the 512-device dry-run, where interpret-mode Pallas
would bloat the HLO).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# FPM — in-pool block gather-copy (RowClone Fast Parallel Mode analogue)
# ---------------------------------------------------------------------------

def fpm_copy(pool, src_ids, dst_ids):
    """Copy pool[src_ids[i]] -> pool[dst_ids[i]] for all i.

    pool: (nblk, ...) array; src_ids/dst_ids: (m,) int32.  dst ids must be
    disjoint from each other; a dst id of -1 disables that copy (the engine
    pads request lists to a fixed length with -1).
    """
    rows = pool[jnp.clip(src_ids, 0, pool.shape[0] - 1)]
    safe_dst = jnp.where(dst_ids >= 0, dst_ids, pool.shape[0])  # OOB drops
    return pool.at[safe_dst].set(rows, mode="drop")


def fpm_copy_cross(dst_pool, src_pool, src_ids, dst_ids):
    """Pool-to-pool variant (same 'subarray' = same device slab)."""
    rows = src_pool[jnp.clip(src_ids, 0, src_pool.shape[0] - 1)]
    safe_dst = jnp.where(dst_ids >= 0, dst_ids, dst_pool.shape[0])
    return dst_pool.at[safe_dst].set(rows, mode="drop")


# ---------------------------------------------------------------------------
# BuZ — bulk zero via reserved zero row (meminit)
# ---------------------------------------------------------------------------

def zero_init(pool, ids, fill_value=0.0):
    """Zero (or fill) the listed blocks.  ids: (m,) int32, -1 disables."""
    safe = jnp.where(ids >= 0, ids, pool.shape[0])
    fill = jnp.full((ids.shape[0],) + pool.shape[1:], fill_value, pool.dtype)
    return pool.at[safe].set(fill, mode="drop")


# ---------------------------------------------------------------------------
# Fused command-queue dispatch — one call applies a whole flushed command
# table (kernels/fused_dispatch.py) to every pool.  Semantics: gather every
# source row from the PRE-flush pool state, then scatter — equivalent to the
# kernel's sequential DMA drain under the CommandQueue's hazard guards (no
# row reads or rewrites a block an earlier row writes).
# ---------------------------------------------------------------------------

def fused_dispatch(pools, zero_blocks, cmds, block_axis=0, primary=None):
    """pools: sequence of (nblk_p, ...) or (L, nblk_p, ...) — block counts
    may DIFFER per pool; zero_blocks: per-pool (1,) + block_shape; cmds:
    (m, 3) int32 [opcode, src, dst].

    ``primary``: per-pool role vector — plain opcodes (copies, zero-init)
    move the block in every primary pool (all primary pools share one
    block count); *staging* pools only receive ``OP_CROSS_POOL_COPY`` rows
    that name them in a global ``base[pool] + block`` id, where ``base``
    is the prefix sum of the pool block counts (the PoolGroup address
    space).  None = every pool is primary.

    Bitwise compute rows (``OP_AND``/``OP_OR``/``OP_NOT``) carry TWO
    sources packed into the src field — ``src = a * total + b`` over the
    same global-id space (``total`` = sum of the pool block counts;
    ``OP_NOT`` packs ``b == a``) — and a *global-id* dst, so fingerprint
    rows can land in staging pools.  Sources are gathered from the
    pre-flush state and combined as raw bits, so float pools AND/OR/NOT
    their bit patterns."""
    from repro.core.opcodes import (BITWISE_OPS, OP_AND, OP_CROSS_POOL_COPY,
                                    OP_OR, OP_ZERO_INIT)
    from repro.kernels.fused_dispatch import (_as_primary, _bitcast_uint,
                                              _op_in)
    # every select and scatter below moves raw bit patterns: a float
    # select may flush subnormals or requiet NaNs (the bitwise rows make
    # both) where a backend widens 16-bit floats, and the oracle must move
    # bytes exactly as the DMA drain does
    dtypes = [p.dtype for p in pools]
    pools = [_bitcast_uint(p) for p in pools]
    zero_blocks = [_bitcast_uint(z) for z in zero_blocks]
    n = len(pools)
    primary = _as_primary(primary, n)
    ba = block_axis
    sizes = [p.shape[ba] for p in pools]
    bases = []
    run = 0
    for nb in sizes:
        bases.append(run)
        run += nb
    total = run
    op, s, d = cmds[:, 0], cmds[:, 1], cmds[:, 2]
    is_cross = op == OP_CROSS_POOL_COPY
    # membership derives from the core/opcodes.py registry — adding a
    # compute opcode updates this switch without touching the reference
    is_bitwise = _op_in(op, BITWISE_OPS)

    def pool_of(ids):
        """Per-row (base, in_pool[p]) decode of global cross-pool ids."""
        base = jnp.zeros_like(ids)
        inp = []
        for p in range(n):
            m = (ids >= bases[p]) & (ids < bases[p] + sizes[p])
            inp.append(m)
            base = jnp.where(m, bases[p], base)
        return base, inp

    # two-source decode: a/b are plain global ids once unpacked (clamped to
    # zero on non-bitwise rows so the masks below stay well-formed)
    a_g = jnp.where(is_bitwise, s // total, 0)
    b_g = jnp.where(is_bitwise, s % total, 0)
    s_base, s_in = pool_of(s)
    d_base, d_in = pool_of(d)
    a_base, a_in = pool_of(a_g)
    b_base, b_in = pool_of(b_g)
    glb_dst = is_cross | is_bitwise          # rows whose dst is a global id
    s_loc = jnp.where(is_cross, s - s_base, s)
    d_loc = jnp.where(glb_dst, d - d_base, d)
    a_loc = a_g - a_base
    b_loc = b_g - b_base

    def gather(arr, idx):
        cl = jnp.clip(idx, 0, arr.shape[ba] - 1)
        return arr[cl] if ba == 0 else arr[:, cl]

    def expand(cond, rows):
        shape = [1] * rows.ndim
        shape[ba] = cond.shape[0]
        return cond.reshape(shape)

    def gather_global(loc, in_masks, pd):
        """Gather per-row blocks addressed by a global id decoded to
        ``(loc, in_masks)`` — start from the dst pool, override from every
        other pool the id actually names (the cross-pool select idiom)."""
        rows = gather(pools[pd], loc)
        for ps in range(n):
            if ps == pd:
                continue
            rows = jnp.where(expand(in_masks[ps], rows),
                             gather(pools[ps], loc).astype(rows.dtype), rows)
        return rows

    out = []
    for pd in range(n):
        pool = pools[pd]
        rows = gather(pool, s_loc)
        for ps in range(n):
            if ps == pd:
                continue
            sel = is_cross & s_in[ps]
            rows = jnp.where(expand(sel, rows), gather(pools[ps], s_loc),
                             rows)
        zb = zero_blocks[pd].astype(pool.dtype)
        if ba == 0:
            zrows = jnp.broadcast_to(zb, (cmds.shape[0],) + pool.shape[1:])
        else:
            zrows = jnp.broadcast_to(
                zb.reshape((1, 1) + zb.shape[1:]),
                (pool.shape[0], cmds.shape[0]) + pool.shape[2:])
        rows = jnp.where(expand(op == OP_ZERO_INIT, rows), zrows, rows)
        # bitwise compute rows: combine both sources bit-for-bit
        au = gather_global(a_loc, a_in, pd)
        bu = gather_global(b_loc, b_in, pd)
        brows = jnp.where(expand(op == OP_AND, au), au & bu,
                          jnp.where(expand(op == OP_OR, au), au | bu, ~au))
        rows = jnp.where(expand(is_bitwise, rows), brows, rows)
        if primary[pd]:
            valid = (op >= 0) & (d >= 0) & (~glb_dst | d_in[pd])
        else:   # staging pool: only global-id rows addressed to it land
            valid = glb_dst & (d >= 0) & d_in[pd]
        safe = jnp.where(valid, d_loc, sizes[pd])
        out.append(pool.at[safe].set(rows, mode="drop") if ba == 0
                   else pool.at[:, safe].set(rows, mode="drop"))
    return tuple(jax.lax.bitcast_convert_type(o, dt)
                 for o, dt in zip(out, dtypes))


# ---------------------------------------------------------------------------
# Baseline copy — what RowClone replaces: stream blocks through the compute
# pipeline (HBM -> VMEM -> VREG -> VMEM -> HBM).  Numerically identical to
# fpm_copy; exists so benchmarks can compare mechanisms.
# ---------------------------------------------------------------------------

def baseline_copy(pool, src_ids, dst_ids):
    """RowClone-disabled copy: same result as fpm_copy, but the bytes
    round-trip the compute pipeline (identity VPU op keeps it honest)."""
    rows = pool[jnp.clip(src_ids, 0, pool.shape[0] - 1)]
    # force a VPU round-trip: identity arithmetic the compiler must keep
    rows = (rows.astype(jnp.float32) * 1.0).astype(pool.dtype)
    safe_dst = jnp.where(dst_ids >= 0, dst_ids, pool.shape[0])
    return pool.at[safe_dst].set(rows, mode="drop")


# ---------------------------------------------------------------------------
# Paged decode attention — one device slab, flash partials
# ---------------------------------------------------------------------------

def _merge(m, l, acc, m2, l2, acc2):
    m_new = jnp.maximum(m, m2)
    c1 = jnp.exp(m - m_new)
    c2 = jnp.exp(m2 - m_new)
    return m_new, l * c1 + l2 * c2, acc * c1[..., None] + acc2 * c2[..., None]


def _auto_chunk(nblk, B, KVH, group, pg, budget_floats=2 * 1024 * 1024):
    """Largest power-of-two divisor of nblk whose score tile fits budget."""
    per_block = max(B * KVH * group * pg, 1)
    cap = max(budget_floats // per_block, 1)
    chunk = 1
    while chunk * 2 <= min(cap, nblk) and nblk % (chunk * 2) == 0:
        chunk *= 2
    return chunk


def paged_attention_slab(q, k_slab, v_slab, share_mask, base, seq_lens, *,
                         page: int, block_chunk: int = 0,
                         exclusive: bool = False):
    """Partial paged attention over one slab (see models/attention.py doc).

    ``share_mask``: (nblk, B) {0,1} — block readable by sequence b.  CoW
    forks set several columns per block; free blocks have an all-zero row.

    Two modes:
      * all-pairs (default): scores for every (sequence, block) pair, then
        masked — exact for arbitrary CoW sharing; B× extra MXU work hides
        under the HBM-bound KV stream.
      * ``exclusive=True``: every block has ≤1 reader (no sharing active —
        the serving engine knows from refcounts).  Queries are gathered
        per block via a one-hot matmul; score tile shrinks B×
        (EXPERIMENTS.md §Perf iteration 4).

    Returns (acc (B,H,D) fp32, l (B,H) fp32, m (B,H) fp32).
    """
    nblk, pg, KVH, D = k_slab.shape
    B, H, _ = q.shape
    group = H // KVH
    scale = D ** -0.5
    eff_b = 1 if exclusive else B
    chunk = block_chunk or _auto_chunk(nblk, eff_b, KVH, group, pg)
    n_chunks = max(nblk // chunk, 1)
    chunk = nblk // n_chunks

    kc = k_slab.reshape(n_chunks, chunk, pg, KVH, D)
    vc = v_slab.reshape(n_chunks, chunk, pg, KVH, D)
    mc_ = share_mask.reshape(n_chunks, chunk, B)
    bc = base.reshape(n_chunks, chunk)

    qg = q.reshape(B, KVH, group, D).astype(jnp.float32)
    lens_f = seq_lens.astype(jnp.float32)

    def body_allpairs(carry, inp):
        m, l, acc = carry
        kb, vb, mk, bb = inp
        # keep K/V in storage dtype; accumulate in fp32 via the MXU
        s = jnp.einsum("bkgd,cpkd->bckgp", qg.astype(kb.dtype), kb,
                       preferred_element_type=jnp.float32) * scale
        pos = bb[:, None] + jnp.arange(pg, dtype=bb.dtype)[None, :]  # (c,p)
        valid = (mk.T[:, :, None] > 0) & (pos[None] < seq_lens[:, None, None])
        s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
        m_c = s.max(axis=(1, 4))                                 # (B,KVH,g)
        p = jnp.exp(s - m_c[:, None, :, :, None])
        p = jnp.where(valid[:, :, None, None, :], p, 0.0)
        l_c = p.sum(axis=(1, 4))
        acc_c = jnp.einsum("bckgp,cpkd->bkgd", p.astype(vb.dtype), vb,
                           preferred_element_type=jnp.float32)
        return _merge(m, l, acc, m_c, l_c, acc_c), None

    def body_owner(carry, inp):
        m, l, acc = carry
        kb, vb, mk, bb = inp
        oh = mk.astype(jnp.float32)                              # (c,B)
        qb = (oh @ qg.reshape(B, KVH * group * D)) \
            .reshape(chunk, KVH, group, D)                       # q[owner]
        s = jnp.einsum("ckgd,cpkd->ckgp", qb.astype(kb.dtype), kb,
                       preferred_element_type=jnp.float32) * scale
        pos = bb[:, None] + jnp.arange(pg, dtype=bb.dtype)[None, :]
        own_len = (oh @ lens_f[:, None])[:, 0].astype(jnp.int32)
        valid = (mk.sum(-1) > 0)[:, None] & (pos < own_len[:, None])
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_blk = jnp.where((mk.sum(-1) > 0)[:, None, None],
                          s.max(axis=-1), NEG_INF)               # (c,KVH,g)
        m_c = jnp.max(jnp.where(oh.T[:, :, None, None] > 0, m_blk[None],
                                NEG_INF), axis=1)                # (B,KVH,g)
        m_back = (oh @ m_c.reshape(B, KVH * group)) \
            .reshape(chunk, KVH, group)
        p = jnp.exp(s - m_back[..., None])
        p = jnp.where(valid[:, None, None, :], p, 0.0)
        l_c = jnp.einsum("cb,ckg->bkg", oh, p.sum(axis=-1))
        pv = jnp.einsum("ckgp,cpkd->ckgd", p.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32)
        acc_c = jnp.einsum("cb,ckgd->bkgd", oh, pv)
        return _merge(m, l, acc, m_c, l_c, acc_c), None

    body = body_owner if exclusive else body_allpairs
    m0 = jnp.full((B, KVH, group), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KVH, group), jnp.float32)
    a0 = jnp.zeros((B, KVH, group, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, mc_, bc))
    return (acc.reshape(B, H, D), l.reshape(B, H), m.reshape(B, H))


def paged_attention_dense_ref(q, k, v, seq_lens):
    """Oracle-of-the-oracle: dense attention with per-seq valid lengths.

    q: (B,H,D); k,v: (B,S,KVH,D) contiguous caches.  Returns (B,H,D).
    """
    B, H, D = q.shape
    KVH = k.shape[2]
    group = H // KVH
    qg = q.reshape(B, KVH, group, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32)) * D ** -0.5
    pos = jnp.arange(k.shape[1])[None, :]
    s = jnp.where((pos < seq_lens[:, None])[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, D)


# ---------------------------------------------------------------------------
# Flash attention oracle (naive full-matrix attention)
# ---------------------------------------------------------------------------

def flash_attention_ref(q, k, v, pos_q, pos_kv, kv_valid, causal=True,
                        prefix_len=0):
    """Naive full-matrix attention oracle for the flash kernel.

    q: (B,Sq,H,D); k/v: (B,Skv,KVH,D); masks by position + validity."""
    B, Sq, H, D = q.shape
    KVH = k.shape[2]
    group = H // KVH
    qg = q.reshape(B, Sq, KVH, group, D).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bqkgs", qg, k.astype(jnp.float32)) * D ** -0.5
    m = kv_valid[:, None, :]
    if causal:
        allowed = pos_q[:, :, None] >= pos_kv[:, None, :]
        if prefix_len:
            allowed |= (pos_kv < prefix_len)[:, None, :]
        m = m & allowed
    s = jnp.where(m[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqkgs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# SSD (Mamba2) oracle — naive recurrence
# ---------------------------------------------------------------------------

def ssd_ref(x, dt, A, B_mat, C_mat, D_skip):
    """Naive sequential state-space recurrence.

    x:     (B, S, H, P)   inner activations per head
    dt:    (B, S, H)      softplus'd timestep (>0)
    A:     (H,)           negative per-head decay (A = -exp(A_log))
    B_mat: (B, S, N)      input projection (shared across heads, G=1)
    C_mat: (B, S, N)      output projection
    D_skip:(H,)           skip connection
    Returns y: (B, S, H, P)
    """
    Bb, S, H, P = x.shape
    N = B_mat.shape[-1]

    def step(h, inp):
        xt, dtt, bt, ct = inp                       # (B,H,P),(B,H),(B,N),(B,N)
        decay = jnp.exp(dtt * A[None, :])           # (B,H)
        dbx = jnp.einsum("bhp,bn,bh->bhpn", xt, bt, dtt)
        h = h * decay[..., None, None] + dbx
        y = jnp.einsum("bhpn,bn->bhp", h, ct)
        return h, y

    h0 = jnp.zeros((Bb, H, P, N), jnp.float32)
    xs = (x.swapaxes(0, 1).astype(jnp.float32), dt.swapaxes(0, 1),
          B_mat.swapaxes(0, 1).astype(jnp.float32),
          C_mat.swapaxes(0, 1).astype(jnp.float32))
    _, ys = jax.lax.scan(step, h0, xs)
    y = ys.swapaxes(0, 1) + x.astype(jnp.float32) * D_skip[None, None, :, None]
    return y.astype(x.dtype)
