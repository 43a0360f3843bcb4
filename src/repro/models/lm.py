"""LanguageModel facade: one interface over all six architecture families.

* ``init_params``  — Param pytree (values + logical sharding axes)
* ``loss_fn``      — training loss for (tokens, labels, mask) batches
* ``prefill``      — full-sequence forward that seeds the serve state
* ``decode_step``  — one-token step over the paged/recurrent state
* ``make_serve_state`` / state sharding specs — used by serving + dry-run
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RowCloneConfig, ShapeConfig
from repro.models import mamba2 as m2
from repro.models import transformer as tfm
from repro.models.attention import MaskInfo
from repro.models.common import (
    Param, chunked_softmax_xent, embed_init, is_param, rms_norm,
    split_params, zeros_init,
)
from repro.models.paged import identity_layout
from repro.sharding import constrain


def _stack_layers(init_fn, key, n):
    keys = jax.random.split(key, n)
    stacked = jax.vmap(init_fn)(keys)
    return jax.tree_util.tree_map(
        lambda p: Param(p.value, ("layers",) + tuple(p.axes)),
        stacked, is_leaf=is_param)


class LanguageModel:
    def __init__(self, cfg: ModelConfig, rc: Optional[RowCloneConfig] = None):
        self.cfg = cfg
        self.rc = rc or RowCloneConfig()

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init_params(self, key):
        cfg = self.cfg
        kE, kL, kH, kS, kX = jax.random.split(key, 5)
        params: Dict = {
            "embed": embed_init(kE, cfg.padded_vocab, cfg.d_model),
            "final_norm": zeros_init((cfg.d_model,), ("norm",)),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = Param(
                jax.random.normal(kH, (cfg.d_model, cfg.padded_vocab)) * 0.02,
                ("embed", "vocab"))
        fam = cfg.family
        if fam in ("dense", "moe", "vlm"):
            params["layers"] = _stack_layers(
                lambda k: tfm.init_decoder_layer(k, cfg), kL, cfg.num_layers)
        elif fam == "ssm":
            params["layers"] = _stack_layers(
                lambda k: m2.init_mamba2_layer(k, cfg), kL, cfg.num_layers)
        elif fam == "hybrid":
            params["layers"] = _stack_layers(
                lambda k: m2.init_mamba2_layer(k, cfg), kL, cfg.num_layers)
            params["shared"] = tfm.init_decoder_layer(kS, cfg)
        elif fam == "encdec":
            params["layers"] = _stack_layers(
                lambda k: tfm.init_decoder_layer(k, cfg, cross=True),
                kL, cfg.num_layers)
            params["enc_layers"] = _stack_layers(
                lambda k: tfm.init_decoder_layer(k, cfg), kX,
                cfg.encoder_layers)
            params["enc_norm"] = zeros_init((cfg.d_model,), ("norm",))
        else:
            raise ValueError(fam)
        return params

    # ------------------------------------------------------------------
    # shared pieces
    # ------------------------------------------------------------------
    def _embed(self, params, tokens, mesh):
        table = params["embed"]
        x = jnp.take(table, tokens, axis=0).astype(jnp.bfloat16
                     if self.cfg.dtype == "bfloat16" else jnp.float32)
        return constrain(x, mesh, "batch", None, None)

    def _lm_head(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["lm_head"]

    def _logits(self, params, x, mesh):
        w = self._lm_head(params)
        logits = x.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16)
        logits = constrain(logits, mesh, "batch", "act_vocab")
        return logits.astype(jnp.float32)

    # ------------------------------------------------------------------
    # training forward (full sequence)
    # ------------------------------------------------------------------
    def _backbone_train(self, params, batch, mesh, remat, return_kv=False):
        """Returns (hidden, aux, kv, xkv, text_offset)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S_text = tokens.shape
        x = self._embed(params, tokens, mesh)
        prefix = 0
        if cfg.family == "vlm":
            patches = batch["patch_embeds"].astype(x.dtype)
            x = jnp.concatenate([patches, x], axis=1)
            prefix = patches.shape[1]
        B, S, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        info = MaskInfo(causal=True, prefix_len=prefix)

        if cfg.family in ("dense", "moe", "vlm"):
            x, aux, kv, _ = tfm.decoder_stack_train(
                params["layers"], x, pos, cfg, mesh, info, remat=remat,
                return_kv=return_kv)
            return x, aux, kv, None, prefix
        if cfg.family == "ssm":
            x, states = self._mamba_stack_train(params, x, mesh, return_kv)
            return x, jnp.float32(0), states, None, 0
        if cfg.family == "hybrid":
            x, aux, kv, states = self._hybrid_stack_train(
                params, x, pos, mesh, info, remat, return_kv)
            return x, aux, (kv, states), None, 0
        if cfg.family == "encdec":
            enc = batch["src_embeds"].astype(x.dtype)
            B_e, S_src, _ = enc.shape
            pos_e = jnp.broadcast_to(jnp.arange(S_src, dtype=jnp.int32),
                                     (B_e, S_src))
            enc, _, _, _ = tfm.decoder_stack_train(
                params["enc_layers"], enc, pos_e, cfg, mesh,
                MaskInfo(causal=False), remat=remat)
            enc = rms_norm(enc, params["enc_norm"].astype(jnp.float32),
                           cfg.norm_eps)
            x, aux, kv, xkv = tfm.decoder_stack_train(
                params["layers"], x, pos, cfg, mesh, info, enc_out=enc,
                remat=remat, return_kv=return_kv)
            return x, aux, kv, xkv, 0
        raise ValueError(cfg.family)

    def _mamba_stack_train(self, params, x, mesh, return_states):
        cfg = self.cfg

        def body(h, lp):
            h, h_final, conv_tail = m2.mamba2_layer(lp, h, cfg, mesh)
            ys = (h_final, conv_tail) if return_states else None
            return h, ys

        body_ck = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
        x, states = jax.lax.scan(body_ck, x, params["layers"])
        return x, states

    def _hybrid_stack_train(self, params, x, pos, mesh, info, remat,
                            return_kv):
        cfg = self.cfg
        k = cfg.shared_attn_every
        n_seg = cfg.num_layers // k
        seg_params = jax.tree_util.tree_map(
            lambda a: a.reshape((n_seg, k) + a.shape[1:]), params["layers"])
        shared = params["shared"]
        strategy = "heads"

        def segment(carry, seg_lp):
            h, aux = carry

            def inner(hc, lp):
                hc, hf, ct = m2.mamba2_layer(lp, hc, cfg, mesh)
                return hc, (hf, ct) if return_kv else None

            h, states = jax.lax.scan(inner, h, seg_lp)
            h, a, kv, _ = tfm.decoder_layer_train(
                shared, h, pos, cfg, mesh, info, strategy,
                return_kv=return_kv)
            return (h, aux + a), (kv, states) if return_kv else None

        seg_ck = jax.checkpoint(
            segment, policy=tfm.REMAT_POLICIES.get(remat)) \
            if remat != "none" else segment
        (x, aux), ys = jax.lax.scan(seg_ck, (x, jnp.float32(0)), seg_params)
        if return_kv:
            kv, states = ys
            return x, aux, kv, states
        return x, aux, None, None

    # ------------------------------------------------------------------
    # loss
    # ------------------------------------------------------------------
    def loss_fn(self, params, batch, mesh, remat: str = "minimal"):
        cfg = self.cfg
        x, aux, _, _, prefix = self._backbone_train(params, batch, mesh, remat)
        x = rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
        if prefix:
            x = x[:, prefix:, :]
        w = self._lm_head(params)
        loss = chunked_softmax_xent(x, w, batch["labels"], batch["mask"],
                                    mesh)
        total = loss + 1e-2 * aux
        return total, {"loss": loss, "aux": aux}

    # ------------------------------------------------------------------
    # serve state
    # ------------------------------------------------------------------
    def make_serve_state(self, batch: int, seq_len: int, mesh=None,
                         filled: Optional[int] = None, dtype=jnp.bfloat16):
        """Zero-initialized serve state with identity block layout.

        ``filled`` — tokens already present per sequence (decode_* cells set
        seq_len - 1 so the next append lands in the final slot).
        """
        cfg, page = self.cfg, self.rc.page_size
        filled = seq_len - 1 if filled is None else filled
        state: Dict = {"seq_lens": jnp.full((batch,), filled, jnp.int32)}
        dp = 1
        if mesh is not None:
            dp_axes = [a for a in ("pod", "data") if a in mesh.axis_names]
            dp = int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
            if batch % dp:
                dp = 1
        if cfg.family in ("dense", "moe", "vlm", "encdec", "hybrid"):
            L = cfg.num_attn_layers
            table, mask, base = identity_layout(batch, seq_len, page, dp)
            nblk = base.shape[0]
            state["block_table"] = jnp.asarray(table)
            state["share_mask"] = jnp.asarray(mask)
            state["base"] = jnp.asarray(base)
            state["k_pools"] = jnp.zeros(
                (L, nblk, page, cfg.num_kv_heads, cfg.head_dim), dtype)
            state["v_pools"] = jnp.zeros_like(state["k_pools"])
        if cfg.family in ("ssm", "hybrid"):
            L, W = cfg.num_layers, cfg.ssm_conv_width
            C = cfg.ssm_d_inner + 2 * cfg.ssm_state
            shp_conv = (L, batch, W - 1, C)
            shp_ssm = (L, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                       cfg.ssm_state)
            if cfg.family == "hybrid":
                k = cfg.shared_attn_every
                n_seg = L // k
                shp_conv = (n_seg, k) + shp_conv[1:]
                shp_ssm = (n_seg, k) + shp_ssm[1:]
            state["conv_state"] = jnp.zeros(shp_conv, jnp.float32)
            state["ssm_state"] = jnp.zeros(shp_ssm, jnp.float32)
        if cfg.family == "encdec":
            S_src = max(seq_len // cfg.src_frames_ratio, 1)
            state["cross_k"] = jnp.zeros(
                (cfg.num_layers, batch, S_src, cfg.num_kv_heads,
                 cfg.head_dim), dtype)
            state["cross_v"] = jnp.zeros_like(state["cross_k"])
        return state

    def state_logical_axes(self, state):
        """Logical sharding axes for each serve-state leaf."""
        cfg = self.cfg
        ax = {"seq_lens": ("batch",)}
        if "k_pools" in state:
            pool = ("layers", "kv_blocks", None, None, None)
            ax.update(block_table=("batch", None),
                      share_mask=("kv_blocks", None),
                      base=("kv_blocks",), k_pools=pool, v_pools=pool)
        if "conv_state" in state:
            nd = state["conv_state"].ndim
            lead = (None,) * (nd - 3)
            ax["conv_state"] = lead + ("batch", None, "act_ffn")
            ax["ssm_state"] = lead + ("batch", "act_heads", None, None)
        if "cross_k" in state:
            ax["cross_k"] = (None, "batch", None, None, None)
            ax["cross_v"] = (None, "batch", None, None, None)
        return ax

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------
    def prefill(self, params, batch, mesh, remat: str = "minimal",
                margin_tokens: Optional[int] = None):
        """Full forward; returns (last_logits, serve_state).

        ``margin_tokens`` — extra decode capacity beyond the prompt
        (default: one page)."""
        cfg, page = self.cfg, self.rc.page_size
        x, aux, kv, xkv, prefix = self._backbone_train(
            params, batch, mesh, remat, return_kv=True)
        B, S, _ = x.shape
        margin = page if margin_tokens is None else margin_tokens
        nper = (S + margin + page - 1) // page
        xn = rms_norm(x[:, -1, :], params["final_norm"].astype(jnp.float32),
                      cfg.norm_eps)
        logits = self._logits(params, xn, mesh)
        kv_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        state = self.make_serve_state(B, nper * page, mesh, filled=S,
                                      dtype=kv_dtype)
        if cfg.family in ("dense", "moe", "vlm", "encdec"):
            k, v = kv  # (L,B,S,KVH,D)
            state["k_pools"] = _kv_to_pools(k, page, kv_dtype, nper)
            state["v_pools"] = _kv_to_pools(v, page, kv_dtype, nper)
        elif cfg.family == "hybrid":
            (k, v), (hf, ct) = kv[0], kv[1]
            state["k_pools"] = _kv_to_pools(k, page, kv_dtype, nper)
            state["v_pools"] = _kv_to_pools(v, page, kv_dtype, nper)
            state["ssm_state"] = hf
            state["conv_state"] = ct
        elif cfg.family == "ssm":
            hf, ct = kv
            state["ssm_state"] = hf
            state["conv_state"] = ct
        if cfg.family == "encdec" and xkv is not None:
            state["cross_k"], state["cross_v"] = xkv
        return logits, state

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def decode_step(self, params, state, tokens, mesh, impl: str = "ref",
                    exclusive: bool = False):
        """tokens: (B,) int32 — the token just sampled; returns logits for
        the next position and the updated state."""
        cfg, page = self.cfg, self.rc.page_size
        B = tokens.shape[0]
        pos = state["seq_lens"]                       # (B,) position of token
        x = self._embed(params, tokens, mesh)          # (B,d)
        seq_incl = pos + 1

        if cfg.family in ("dense", "moe", "vlm", "encdec"):
            ids = jnp.take_along_axis(
                state["block_table"], (pos // page)[:, None], axis=1)[:, 0]
            cross_kvs = None
            if cfg.family == "encdec":
                cross_kvs = (state["cross_k"], state["cross_v"])
            x, kp, vp = tfm.decoder_stack_decode(
                params["layers"], x, pos, state["k_pools"], state["v_pools"],
                ids, pos % page, state["share_mask"], state["base"],
                seq_incl, cfg, mesh, cross_kvs=cross_kvs, impl=impl,
                exclusive=exclusive)
            state = dict(state, k_pools=kp, v_pools=vp)
        elif cfg.family == "ssm":
            def body(h, inp):
                lp, cs, ss = inp
                h, cs, ss = m2.mamba2_decode_step(lp, h, cs, ss, cfg, mesh)
                return h, (cs, ss)
            x, (cs, ss) = jax.lax.scan(
                body, x, (params["layers"], state["conv_state"],
                          state["ssm_state"]))
            state = dict(state, conv_state=cs, ssm_state=ss)
        elif cfg.family == "hybrid":
            x, state = self._hybrid_decode(params, state, x, pos, seq_incl,
                                           mesh, impl, exclusive)
        else:
            raise ValueError(cfg.family)

        xn = rms_norm(x, params["final_norm"].astype(jnp.float32),
                      cfg.norm_eps)
        logits = self._logits(params, xn, mesh)
        state = dict(state, seq_lens=seq_incl)
        return logits, state

    def _hybrid_decode(self, params, state, x, pos, seq_incl, mesh, impl,
                       exclusive=False):
        cfg, page = self.cfg, self.rc.page_size
        B = x.shape[0]
        k = cfg.shared_attn_every
        n_seg = cfg.num_layers // k
        seg_params = jax.tree_util.tree_map(
            lambda a: a.reshape((n_seg, k) + a.shape[1:]), params["layers"])
        ids = jnp.take_along_axis(
            state["block_table"], (pos // page)[:, None], axis=1)[:, 0]
        shared = params["shared"]

        def segment(h, inp):
            lp, cs, ss, kp, vp = inp

            def inner(hc, s_inp):
                l, c, s = s_inp
                hc, c, s = m2.mamba2_decode_step(l, hc, c, s, cfg, mesh)
                return hc, (c, s)

            h, (cs, ss) = jax.lax.scan(inner, h, (lp, cs, ss))
            h, (kp, vp), _ = tfm.decoder_layer_decode(
                shared, h, pos, (kp[None], vp[None]), 0, ids, pos % page,
                state["share_mask"], state["base"], seq_incl, cfg, mesh,
                impl=impl, exclusive=exclusive)
            return h, (cs, ss, kp[0], vp[0])

        x, (cs, ss, kp, vp) = jax.lax.scan(
            segment, x, (seg_params, state["conv_state"], state["ssm_state"],
                         state["k_pools"], state["v_pools"]))
        return x, dict(state, conv_state=cs, ssm_state=ss, k_pools=kp,
                       v_pools=vp)


def _kv_to_pools(kv, page, dtype, nper):
    """(L, B, S, KVH, D) -> (L, B*nper, page, KVH, D) identity layout with
    per-sequence capacity ``nper`` blocks.  Slots beyond seq_lens are masked
    by the paged-attention validity check, so zero padding is safe."""
    L, B, S, KVH, D = kv.shape
    cap = nper * page
    if S < cap:
        kv = jnp.pad(kv, ((0, 0), (0, 0), (0, cap - S), (0, 0), (0, 0)))
    return kv.reshape(L, B * nper, page, KVH, D).astype(dtype)


def build_model(cfg: ModelConfig, rc: Optional[RowCloneConfig] = None):
    return LanguageModel(cfg, rc)
