"""Decoder transformer stack: GQA attention blocks, scan-over-layers, remat.

One code path serves the dense / moe / vlm families; hybrid and encdec reuse
the same attention block.  Layers are scanned (params stacked on a leading
``layers`` axis) so HLO size — and hence 512-device dry-run compile time —
is O(1) in depth.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import moe as moe_mod
from repro.models.attention import MaskInfo, attention_train, flash_attention
from repro.models.common import (
    Param, apply_rope, dense_init, init_mlp, rms_norm, swiglu_mlp, zeros_init,
)
from repro.models.paged import paged_attend_append
from repro.sharding import attn_strategy, constrain


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attn(key, cfg: ModelConfig) -> Dict[str, Param]:
    d = cfg.d_model
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, d, cfg.q_dim, ("embed", "qkv")),
        "wk": dense_init(k2, d, cfg.kv_dim, ("embed", "qkv")),
        "wv": dense_init(k3, d, cfg.kv_dim, ("embed", "qkv")),
        "wo": dense_init(k4, cfg.q_dim, d, ("qkv", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros_init((cfg.q_dim,), ("qkv",))
        p["bk"] = zeros_init((cfg.kv_dim,), ("qkv",))
        p["bv"] = zeros_init((cfg.kv_dim,), ("qkv",))
    return p


def init_decoder_layer(key, cfg: ModelConfig, cross: bool = False):
    ks = jax.random.split(key, 4)
    p = {
        "ln1": zeros_init((cfg.d_model,), ("norm",)),
        "attn": init_attn(ks[0], cfg),
        "ln2": zeros_init((cfg.d_model,), ("norm",)),
    }
    if cfg.family == "moe":
        p["moe"] = moe_mod.init_moe_ffn(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff)
    if cross:
        p["ln_x"] = zeros_init((cfg.d_model,), ("norm",))
        p["xattn"] = init_attn(ks[2], cfg)
    return p


# ---------------------------------------------------------------------------
# qkv projection helpers
# ---------------------------------------------------------------------------

def _qkv(p, h, cfg: ModelConfig):
    dtype = h.dtype
    q = h @ p["wq"].astype(dtype)
    k = h @ p["wk"].astype(dtype)
    v = h @ p["wv"].astype(dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    return q, k, v


def _heads(x, n, d):
    return x.reshape(x.shape[:-1] + (n, d))


# ---------------------------------------------------------------------------
# train / prefill layer
# ---------------------------------------------------------------------------

def attn_block_train(p, x, pos, cfg: ModelConfig, mesh, info: MaskInfo,
                     strategy: str, return_kv: bool = False):
    B, S, d = x.shape
    h = rms_norm(x, p["ln1"].astype(jnp.float32), cfg.norm_eps)
    q, k, v = _qkv(p["attn"], h, cfg)
    q = _heads(q, cfg.num_heads, cfg.head_dim)
    k = _heads(k, cfg.num_kv_heads, cfg.head_dim)
    v = _heads(v, cfg.num_kv_heads, cfg.head_dim)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    o = attention_train(q, k, v, pos, info, mesh, strategy)
    o = o.reshape(B, S, cfg.q_dim) @ p["attn"]["wo"].astype(x.dtype)
    x = x + o
    return (x, (k, v)) if return_kv else (x, None)


def cross_block_train(p, x, enc_out, cfg: ModelConfig, mesh,
                      return_kv: bool = False):
    """Cross-attention (decoder → encoder output). No RoPE, full mask."""
    B, S, d = x.shape
    h = rms_norm(x, p["ln_x"].astype(jnp.float32), cfg.norm_eps)
    dtype = h.dtype
    q = _heads(h @ p["xattn"]["wq"].astype(dtype), cfg.num_heads, cfg.head_dim)
    k = _heads(enc_out @ p["xattn"]["wk"].astype(dtype),
               cfg.num_kv_heads, cfg.head_dim)
    v = _heads(enc_out @ p["xattn"]["wv"].astype(dtype),
               cfg.num_kv_heads, cfg.head_dim)
    S_src = enc_out.shape[1]
    pos_q = jnp.zeros((B, S), jnp.int32)
    pos_kv = jnp.zeros((B, S_src), jnp.int32)
    o = flash_attention(q, k, v, pos_q, pos_kv,
                        jnp.ones((B, S_src), bool), MaskInfo(causal=False))
    o = o.reshape(B, S, cfg.q_dim) @ p["xattn"]["wo"].astype(x.dtype)
    x = x + o
    return (x, (k, v)) if return_kv else (x, None)


def ffn_block_train(p, x, cfg: ModelConfig, mesh):
    h = rms_norm(x, p["ln2"].astype(jnp.float32), cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = moe_mod.moe_ffn(p["moe"], h, cfg, mesh)
    else:
        y = swiglu_mlp(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"], mesh)
        aux = jnp.float32(0)
    return x + y, aux


def decoder_layer_train(p, x, pos, cfg: ModelConfig, mesh, info: MaskInfo,
                        strategy: str, enc_out=None, return_kv: bool = False):
    x = constrain(x, mesh, "batch", "act_seq_tp", None)
    x, kv = attn_block_train(p, x, pos, cfg, mesh, info, strategy, return_kv)
    xkv = None
    if enc_out is not None:
        x, xkv = cross_block_train(p, x, enc_out, cfg, mesh, return_kv)
    x, aux = ffn_block_train(p, x, cfg, mesh)
    x = constrain(x, mesh, "batch", "act_seq_tp", None)
    return x, aux, kv, xkv


REMAT_POLICIES = {
    "none": None,
    "minimal": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


def decoder_stack_train(stacked, x, pos, cfg: ModelConfig, mesh,
                        info: MaskInfo, enc_out=None,
                        remat: str = "minimal", return_kv: bool = False,
                        num_layers: Optional[int] = None):
    """Scan the layer stack.  stacked: params with leading layer axis.

    Returns (x, aux_sum, kv_stack|None, xkv_stack|None).
    """
    strategy = attn_strategy(cfg.num_heads, mesh) if mesh is not None else "heads"

    def body(carry, layer_params):
        h, aux = carry
        h, a, kv, xkv = decoder_layer_train(
            layer_params, h, pos, cfg, mesh, info, strategy, enc_out,
            return_kv)
        ys = (kv, xkv) if return_kv else None
        return (h, aux + a), ys

    policy = REMAT_POLICIES.get(remat)
    if remat != "none":
        body = jax.checkpoint(body, policy=policy)
    (x, aux), ys = jax.lax.scan(body, (x, jnp.float32(0)), stacked,
                                length=num_layers)
    kv = ys[0] if return_kv else None
    xkv = ys[1] if return_kv else None
    return x, aux, kv, xkv


# ---------------------------------------------------------------------------
# decode layer (single token, paged KV)
# ---------------------------------------------------------------------------

def decoder_layer_decode(p, x, pos, pools, layer, table_ids, offsets,
                         share_mask, base, seq_lens_incl, cfg: ModelConfig,
                         mesh, cross_kv=None, impl: str = "ref",
                         exclusive: bool = False):
    """x: (B, d); pos: (B,); pools: (k_pools, v_pools), the layer-stacked
    ``(L, nblk, page, KVH, D)`` pools; layer: int32 scalar, THIS layer's
    index into them.

    Returns (x', (k_pools', v_pools'), aux): only layer ``layer``'s pages
    of the stacks change (this token's K/V).
    """
    B, d = x.shape
    k_pools, v_pools = pools
    h = rms_norm(x, p["ln1"].astype(jnp.float32), cfg.norm_eps)
    q, k, v = _qkv(p["attn"], h[:, None, :], cfg)   # (B,1,*)
    q = apply_rope(_heads(q, cfg.num_heads, cfg.head_dim),
                   pos[:, None], cfg.rope_theta)[:, 0]
    k = apply_rope(_heads(k, cfg.num_kv_heads, cfg.head_dim),
                   pos[:, None], cfg.rope_theta)[:, 0]
    v = _heads(v, cfg.num_kv_heads, cfg.head_dim)[:, 0]
    o, k_pools, v_pools = paged_attend_append(
        mesh, q, k, v, k_pools, v_pools, layer, table_ids, offsets,
        share_mask, base, seq_lens_incl, impl=impl, exclusive=exclusive)
    x = x + o.reshape(B, cfg.q_dim) @ p["attn"]["wo"].astype(x.dtype)

    if cross_kv is not None:
        xk, xv = cross_kv                            # (B,Ssrc,KVH,D)
        hx = rms_norm(x, p["ln_x"].astype(jnp.float32), cfg.norm_eps)
        qx = _heads(hx[:, None, :] @ p["xattn"]["wq"].astype(x.dtype),
                    cfg.num_heads, cfg.head_dim)
        S_src = xk.shape[1]
        ox = flash_attention(qx, xk, xv, jnp.zeros((B, 1), jnp.int32),
                             jnp.zeros((B, S_src), jnp.int32),
                             jnp.ones((B, S_src), bool), MaskInfo(causal=False))
        x = x + ox.reshape(B, cfg.q_dim) @ p["xattn"]["wo"].astype(x.dtype)

    h2 = rms_norm(x, p["ln2"].astype(jnp.float32), cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = moe_mod.moe_ffn(p["moe"], h2[:, None, :], cfg, mesh)
        y = y[:, 0]
    else:
        y = swiglu_mlp(h2[:, None, :], p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"], mesh)[:, 0]
        aux = jnp.float32(0)
    return x + y, (k_pools, v_pools), aux


def decoder_stack_decode(stacked, x, pos, k_pools, v_pools, table_ids,
                         offsets, share_mask, base, seq_lens_incl,
                         cfg: ModelConfig, mesh, cross_kvs=None,
                         impl: str = "ref", exclusive: bool = False):
    """Scan decode over layers.  k_pools/v_pools: (L, nblk, page, KVH, D).

    The pools ride in the scan CARRY: each layer scatters its token into
    the carried stacks at ``(layer, block, offset)`` and attends over its
    own pages read out by layer index, so with the pools donated the
    carry aliases them and the step writes only the new tokens' slots.
    Donation does not make the ``xs``/``ys`` form in place: there the
    compiler writes each layer's slab back into a second pool-sized
    ``ys`` buffer and copies that whole buffer into the outputs."""

    def body(carry, inp):
        h, kp, vp = carry
        if cross_kvs is not None:
            lp, layer, xkv = inp
        else:
            (lp, layer), xkv = inp, None
        h, (kp, vp), _ = decoder_layer_decode(
            lp, h, pos, (kp, vp), layer, table_ids, offsets, share_mask,
            base, seq_lens_incl, cfg, mesh, cross_kv=xkv, impl=impl,
            exclusive=exclusive)
        return (h, kp, vp), None

    xs = (stacked, jnp.arange(k_pools.shape[0], dtype=jnp.int32))
    if cross_kvs is not None:
        xs = xs + (cross_kvs,)
    (x, k_pools, v_pools), _ = jax.lax.scan(body, (x, k_pools, v_pools), xs)
    return x, k_pools, v_pools
