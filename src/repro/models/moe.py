"""Mixture-of-Experts FFN: top-k routing with capacity-buffer dispatch.

Dispatch uses the scatter/gather (fixed-capacity) formulation: tokens are
scattered into a ``(B, E, C, d)`` buffer (experts sharded over ``model``,
batch over ``data``), each expert runs a SwiGLU matmul on its buffer, and
outputs are gathered back with the renormalized top-k weights.  Overflowing
tokens are dropped (standard Switch/GShard semantics).  A load-balance aux
loss and router z-loss are returned alongside.

DeepSeek-style *shared experts* are a dense SwiGLU with hidden size
``num_shared_experts * moe_d_ff`` applied to every token.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import Param, dense_init, init_mlp, swiglu_mlp
from repro.sharding import constrain

CAPACITY_FACTOR = 1.25


def init_moe_ffn(key, cfg: ModelConfig) -> Dict[str, Param]:
    d = cfg.d_model
    E = cfg.num_experts
    f = cfg.moe_d_ff or cfg.d_ff
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    params = {
        "router": dense_init(k1, d, E, ("embed", "experts"), scale=0.02),
        "w_gate": Param(jax.random.normal(k2, (E, d, f)) * d ** -0.5,
                        ("experts", "embed", "ffn")),
        "w_up": Param(jax.random.normal(k3, (E, d, f)) * d ** -0.5,
                      ("experts", "embed", "ffn")),
        "w_down": Param(jax.random.normal(k4, (E, f, d)) * f ** -0.5,
                        ("experts", "ffn", "embed")),
    }
    if cfg.num_shared_experts:
        params["shared"] = init_mlp(k5, d, cfg.num_shared_experts * f)
    return params


def capacity(cfg: ModelConfig, seq_len: int) -> int:
    c = int(math.ceil(seq_len * cfg.top_k / cfg.num_experts * CAPACITY_FACTOR))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU tiling


def moe_ffn(params, x, cfg: ModelConfig, mesh) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B,S,d).  Returns (y, aux_loss).

    Dispatches to the explicit all-to-all shard_map path on a multi-device
    mesh (GSPMD lowers the scatter/gather formulation to per-layer
    replicate+all-reduce — ~200 GB/layer at deepseek scale; see
    EXPERIMENTS.md §Perf iteration 1), else the local dense-dispatch path.
    """
    from repro.sharding.rules import active_rules
    if mesh is None or int(np.prod(mesh.devices.shape)) == 1:
        return _moe_ffn_local(params, x, cfg, mesh)
    tp_mode = active_rules().get("act_seq_tp", (None,))[0] is not None
    if tp_mode and "model" in mesh.axis_names:
        return _moe_ffn_a2a(params, x, cfg, mesh)
    # FSDP: tokens are device-local — run the dispatch inside shard_map
    # (GSPMD's scatter partitioner would otherwise replicate the capacity
    # buffer; see EXPERIMENTS.md §Perf iteration 3).
    return _moe_ffn_fsdp(params, x, cfg, mesh)


def _moe_ffn_local(params, x, cfg: ModelConfig, mesh):
    """Single-device / test path: dense capacity-buffer dispatch."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    C = capacity(cfg, S)
    dtype = x.dtype

    logits = x @ params["router"].astype(dtype)                    # (B,S,E)
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_k, idx_k = jax.lax.top_k(probs, k)                        # (B,S,k)
    gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)

    # position-in-expert via sequential cumsum over the k routing choices
    counts = jnp.zeros((B, 1, E), jnp.int32)
    pos_list, keep_list = [], []
    for j in range(k):
        onehot = jax.nn.one_hot(idx_k[..., j], E, dtype=jnp.int32)  # (B,S,E)
        pos_in_e = jnp.cumsum(onehot, axis=1) - onehot + counts     # (B,S,E)
        pos_j = jnp.sum(pos_in_e * onehot, axis=-1)                 # (B,S)
        keep_list.append(pos_j < C)
        pos_list.append(jnp.minimum(pos_j, C - 1))
        counts = counts + onehot.sum(axis=1, keepdims=True)
    pos_k = jnp.stack(pos_list, -1)                                 # (B,S,k)
    keep_k = jnp.stack(keep_list, -1)                               # (B,S,k)

    # scatter tokens into the capacity buffer
    bidx = jnp.arange(B)[:, None, None] + jnp.zeros_like(idx_k)
    buf = jnp.zeros((B, E, C, d), dtype)
    xb = jnp.broadcast_to(x[:, :, None, :], (B, S, k, d))
    xb = jnp.where(keep_k[..., None], xb, 0)
    buf = buf.at[bidx, idx_k, pos_k].add(xb)
    buf = constrain(buf, mesh, "batch", "act_experts", None, None)

    # per-expert SwiGLU
    g = jnp.einsum("becd,edf->becf", buf, params["w_gate"].astype(dtype))
    u = jnp.einsum("becd,edf->becf", buf, params["w_up"].astype(dtype))
    g = constrain(g, mesh, "batch", "act_experts", None, "act_ffn")
    h = jax.nn.silu(g) * u
    out_buf = jnp.einsum("becf,efd->becd", h, params["w_down"].astype(dtype))
    out_buf = constrain(out_buf, mesh, "batch", "act_experts", None, None)

    # gather back with combine weights
    picked = out_buf[bidx, idx_k, pos_k]                            # (B,S,k,d)
    w = (gate_k * keep_k).astype(dtype)
    y = jnp.einsum("bskd,bsk->bsd", picked, w)

    if cfg.num_shared_experts:
        y = y + swiglu_mlp(x, params["shared"]["w_gate"],
                           params["shared"]["w_up"],
                           params["shared"]["w_down"], mesh)

    # aux losses: switch load-balance + router z-loss
    frac_tokens = jnp.mean(
        (jax.nn.one_hot(idx_k, E).sum(-2) > 0).astype(jnp.float32), axis=(0, 1))
    mean_prob = probs.mean(axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * mean_prob)
    zloss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y, aux + 1e-3 * zloss


def _moe_ffn_fsdp(params, x, cfg: ModelConfig, mesh):
    """FSDP path: batch is sharded over every mesh axis; each device routes
    and runs its own tokens against the (boundary-gathered) expert weights.
    Zero collectives inside; the only wire cost is the ZeRO-3 weight gather.
    """
    from repro.models.paged import batch_shard_axes
    B = x.shape[0]
    bs = batch_shard_axes(mesh, B)
    # fall back when the batch can't shard (decode with tiny batch)
    all_axes = tuple(a for a in ("pod", "data", "model")
                     if a in mesh.axis_names)
    bspec = None
    for cand in (all_axes, tuple(a for a in all_axes if a != "model"),
                 ("data",)):
        present = tuple(a for a in cand if a in mesh.axis_names)
        size = int(np.prod([mesh.shape[a] for a in present])) if present else 1
        if present and B % size == 0:
            bspec = present if len(present) > 1 else present[0]
            break
    if bspec is None:
        return _moe_ffn_local(params, x, cfg, mesh)

    def local_fn(wr, wg, wu, wd, shared, xl):
        p = {"router": wr, "w_gate": wg, "w_up": wu, "w_down": wd}
        if shared is not None:
            p["shared"] = shared
        y, aux = _moe_ffn_local(p, xl, cfg, None)
        return y, jax.lax.pmean(aux, all_axes)

    shared = params.get("shared")
    mapped = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(), P(), P(),
                  None if shared is None else jax.tree_util.tree_map(
                      lambda _: P(), shared),
                  P(bspec, None, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False)
    y, aux = mapped(params["router"], params["w_gate"], params["w_up"],
                    params["w_down"], shared, x)
    # aux was computed per shard on identical-statistics local tokens; it is
    # already a mean — no further normalization needed for the loss scale.
    return y, aux


# ---------------------------------------------------------------------------
# explicit all-to-all dispatch (multi-device path)
# ---------------------------------------------------------------------------

def _route_local(xf, wr, E, k, C):
    """Route N local tokens.  xf: (N,d).  Returns (gate_k, idx_k, pos_k,
    keep_k, probs, logits) with capacity C per expert."""
    logits = (xf @ wr.astype(xf.dtype)).astype(jnp.float32)      # (N,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_k, idx_k = jax.lax.top_k(probs, k)
    gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)
    counts = jnp.zeros((1, E), jnp.int32)
    pos_list, keep_list = [], []
    for j in range(k):
        onehot = jax.nn.one_hot(idx_k[:, j], E, dtype=jnp.int32)
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot + counts
        pos_j = jnp.sum(pos_in_e * onehot, axis=-1)
        keep_list.append(pos_j < C)
        pos_list.append(jnp.minimum(pos_j, C - 1))
        counts = counts + onehot.sum(axis=0, keepdims=True)
    return (gate_k, idx_k, jnp.stack(pos_list, -1),
            jnp.stack(keep_list, -1), probs, logits)


def _moe_ffn_a2a(params, x, cfg: ModelConfig, mesh):
    """shard_map MoE: local routing → one all-to-all to expert shards →
    local expert FFN → all-to-all back → local combine.

    Wire cost per layer ≈ 2 × (token bytes × k × capacity_factor) over the
    model axis — versus GSPMD's replicate+all-reduce lowering of the
    scatter formulation (~200 GB/layer at deepseek-moe scale).
    """
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = "model"
    T = int(mesh.shape[tp])
    dps = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    if S % T or (dp and B % dps) or E % T:
        return _moe_ffn_local(params, x, cfg, mesh)   # decode / odd shapes
    E_l = E // T
    N_loc = (B // dps) * (S // T)
    C = max(8, -(-int(math.ceil(N_loc * k / E * CAPACITY_FACTOR)) // 8) * 8)

    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    n_dev = T * dps

    def local_fn(wr, wg, wu, wd, xl):
        B_l, S_l, _ = xl.shape
        N = B_l * S_l
        xf = xl.reshape(N, d)
        gate_k, idx_k, pos_k, keep_k, probs, logits = _route_local(
            xf, wr, E, k, C)
        # pack local send buffer (E, C, d)
        buf = jnp.zeros((E, C, d), xl.dtype)
        xk = jnp.where(keep_k[..., None], xf[:, None, :], 0)     # (N,k,d)
        buf = buf.at[idx_k, pos_k].add(xk)
        # exchange: peer t receives my slice for its experts
        send = buf.reshape(T, E_l, C, d)
        recv = jax.lax.all_to_all(send, tp, split_axis=0, concat_axis=0,
                                  tiled=True)                    # (T,E_l,C,d)
        tokens = recv.swapaxes(0, 1).reshape(E_l, T * C, d)
        # local expert FFN (weights fully materialized at shard boundary)
        g = jnp.einsum("etd,edf->etf", tokens, wg.astype(tokens.dtype))
        u = jnp.einsum("etd,edf->etf", tokens, wu.astype(tokens.dtype))
        h = jax.nn.silu(g) * u
        out = jnp.einsum("etf,efd->etd", h, wd.astype(tokens.dtype))
        # return to owners
        back = out.reshape(E_l, T, C, d).swapaxes(0, 1)          # (T,E_l,C,d)
        mine = jax.lax.all_to_all(back, tp, split_axis=0, concat_axis=0,
                                  tiled=True).reshape(E, C, d)
        # local combine
        picked = mine[idx_k, pos_k]                              # (N,k,d)
        w = (gate_k * keep_k).astype(xl.dtype)
        y = jnp.einsum("nkd,nk->nd", picked, w).reshape(B_l, S_l, d)
        # aux (global mean via psum)
        frac = jnp.mean((jax.nn.one_hot(idx_k, E).sum(-2) > 0)
                        .astype(jnp.float32), axis=0)
        mean_p = probs.mean(axis=0)
        aux_l = E * jnp.sum(frac * mean_p)
        z_l = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        aux = jax.lax.psum(aux_l + 1e-3 * z_l, dp + (tp,)) / n_dev
        return y, aux

    mapped = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(tp), P(tp), P(tp), P(bspec, tp, None)),
        out_specs=(P(bspec, tp, None), P()),
        check_vma=False)
    y, aux = mapped(params["router"], params["w_gate"], params["w_up"],
                    params["w_down"], x)
    if cfg.num_shared_experts:
        y = y + swiglu_mlp(x, params["shared"]["w_gate"],
                           params["shared"]["w_up"],
                           params["shared"]["w_down"], mesh)
    return y, aux
