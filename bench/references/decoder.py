"""The benchmark's decoder architecture: a grouped-query decoder with a
SwiGLU feed-forward (``family`` dense), or the same decoder with a
capacity-buffer mixture of experts (``family`` moe).  Everything the
benchmark knows of its mathematics, from the configuration file's numbers
alone:

- :func:`program_checks`: the program's config fields the file fixes;
- :func:`weight_layout`: the leaves ``bench/weights.py`` draws from the
  seed, the embedding and head outside one stack of identical layers;
- the cost model ``bench/cost.py`` reads: :func:`matmul_weights_per_token`,
  :func:`attn_flops_per_pair`, :func:`kv_block_bytes`,
  :func:`primary_pools`;
- :func:`logit_gaps`: the plain reference forward pass and the
  lower-precision control that proves the comparison can fail.

The reference is straight ``jax.numpy`` in float32 at
``Precision.HIGHEST``: RMSNorm with a ``1 + w`` scale, rotary embeddings
on the two halves of each head, grouped-query causal attention, a SwiGLU
feed-forward or, for ``family == "moe"``, softmax top-k routing over every
expert plus the shared experts.  It imports nothing of the program; its
weights come from ``bench/weights.py`` and the same seed.

It runs layer by layer over a batch of whole sequences (prompt plus the
tokens the engine served), so only one layer's float32 weights are on the
device at a time.  For each position it reads the reference's best logit
and the logit of the token that was served there: the gap between them is
0 for a token the reference would also have picked.

The control is the same forward computed in int8 (weights quantised per
output channel, activations per token, int32 accumulation): the step
below bfloat16 that would tempt a later change.  Its reading is the gap of
the token the int8 forward puts first.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

BF16 = 2
#: the program pads the vocabulary of its embedding and head to this
VOCAB_MULTIPLE = 256


# -- what the program must run ---------------------------------------------
def program_checks(m: dict) -> dict:
    """The program's ``ModelConfig`` fields and the values the file states
    for them."""
    want = {
        "d_model": m["hidden_size"],
        "num_layers": m["num_hidden_layers"],
        "num_heads": m["num_attention_heads"],
        "num_kv_heads": m["num_key_value_heads"],
        "head_dim": m["head_dim"],
        "vocab_size": m["vocab_size"],
        "rope_theta": m["rope_theta"],
        "norm_eps": m["rms_norm_eps"],
        "tie_embeddings": m["tie_word_embeddings"],
        "dtype": m["torch_dtype"],
        "family": m["family"],
    }
    if m["family"] == "moe":
        want.update(num_experts=m["n_routed_experts"],
                    top_k=m["num_experts_per_tok"],
                    num_shared_experts=m["n_shared_experts"],
                    d_ff=m["moe_intermediate_size"])
    else:
        want["d_ff"] = m["intermediate_size"]
    return want


# -- the weights -----------------------------------------------------------
def padded_vocab(m: dict) -> int:
    v = m["vocab_size"]
    return -(-v // VOCAB_MULTIPLE) * VOCAB_MULTIPLE


def _top_specs(m: dict) -> List[W.Spec]:
    """The leaves outside the layer stack."""
    d, v = m["hidden_size"], padded_vocab(m)
    specs = [("embed", (v, d), 0.02), ("final_norm", (d,), "norm")]
    if not m["tie_word_embeddings"]:
        specs.append(("lm_head", (d, v), 0.02))
    return specs


def _layer_specs(m: dict) -> List[W.Spec]:
    """One decoder layer's leaves."""
    d, h, kvh, hd = (m["hidden_size"], m["num_attention_heads"],
                     m["num_key_value_heads"], m["head_dim"])
    specs = [
        ("ln1", (d,), "norm"),
        ("attn/wq", (d, h * hd), d ** -0.5),
        ("attn/wk", (d, kvh * hd), d ** -0.5),
        ("attn/wv", (d, kvh * hd), d ** -0.5),
        ("attn/wo", (h * hd, d), (h * hd) ** -0.5),
        ("ln2", (d,), "norm"),
    ]
    if m["family"] == "moe":
        e, f = m["n_routed_experts"], m["moe_intermediate_size"]
        fs = m["n_shared_experts"] * f
        specs += [
            ("moe/router", (d, e), 0.02),
            ("moe/w_gate", (e, d, f), d ** -0.5),
            ("moe/w_up", (e, d, f), d ** -0.5),
            ("moe/w_down", (e, f, d), f ** -0.5),
            ("moe/shared/w_gate", (d, fs), d ** -0.5),
            ("moe/shared/w_up", (d, fs), d ** -0.5),
            ("moe/shared/w_down", (fs, d), fs ** -0.5),
        ]
    else:
        f = m["intermediate_size"]
        specs += [
            ("mlp/w_gate", (d, f), d ** -0.5),
            ("mlp/w_up", (d, f), d ** -0.5),
            ("mlp/w_down", (f, d), f ** -0.5),
        ]
    return specs


def weight_layout(m: dict) -> W.Layout:
    """The program's parameter tree: embedding, final norm and head, and
    every layer's leaves stacked under ``layers``."""
    return _top_specs(m), [("layers", m["num_hidden_layers"],
                            _layer_specs(m))]


# -- the cost model --------------------------------------------------------
def matmul_weights_per_token(m: dict) -> int:
    """Weights one token multiplies by, over every layer and the head:
    for a mixture of experts the router, its ``k`` routed experts and the
    shared experts, not the buffer slots a capacity-buffer dispatch also
    computes."""
    d, h, kvh, hd = (m["hidden_size"], m["num_attention_heads"],
                     m["num_key_value_heads"], m["head_dim"])
    attn = d * h * hd + 2 * d * kvh * hd + h * hd * d
    if m["family"] == "moe":
        f = m["moe_intermediate_size"]
        ffn = d * m["n_routed_experts"] \
            + 3 * d * f * (m["num_experts_per_tok"] + m["n_shared_experts"])
    else:
        ffn = 3 * d * m["intermediate_size"]
    v = m["vocab_size"]
    return m["num_hidden_layers"] * (attn + ffn) + d * v


def attn_flops_per_pair(m: dict) -> int:
    """FLOPs of attention per (query, key) pair over every layer: the
    score and the weighted value, 2 FLOPs per multiply-add each."""
    return 4 * m["num_hidden_layers"] * m["num_attention_heads"] * m["head_dim"]


def kv_block_bytes(m: dict, page: int) -> int:
    """Bytes of one KV-pool block (every layer's chunk of ``page``
    tokens) in ONE pool, K or V, in bf16."""
    return (m["num_hidden_layers"] * page * m["num_key_value_heads"]
            * m["head_dim"] * BF16)


def primary_pools(m: dict) -> int:
    """The KV pools a plain drain row acts on: K and V."""
    return 2


# -- the reference and its control -----------------------------------------
HI = jax.lax.Precision.HIGHEST
#: tokens per chunk of the LM head (bounds the float32 logits on device)
HEAD_CHUNK = 256


def _dims(m: dict) -> dict:
    return {
        "d": m["hidden_size"], "h": m["num_attention_heads"],
        "kvh": m["num_key_value_heads"], "hd": m["head_dim"],
        "theta": float(m["rope_theta"]), "eps": float(m["rms_norm_eps"]),
        "moe": m["family"] == "moe",
        "e": m.get("n_routed_experts", 0), "k": m.get("num_experts_per_tok", 0),
        "cf": float(m.get("capacity_factor", 0.0)),
    }


# -- int8 arithmetic of the control -----------------------------------------
def _q8(x, axis):
    """Symmetric int8 quantisation with one scale per slice along ``axis``
    (the reduced axis); returns (int8 values, float32 scales)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def _mm(x, w, quant: bool):
    """x (..., i) @ w (i, o) in float32, or in int8 for the control."""
    if not quant:
        return jnp.matmul(x, w, precision=HI)
    qx, sx = _q8(x, -1)
    qw, sw = _q8(w, 0)
    acc = jnp.matmul(qx, qw, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


# -- the layer ------------------------------------------------------------
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[..., None].astype(jnp.float32) * freqs          # (N,T,half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal grouped-query attention of one sequence: q (T,H,D),
    k/v (T,KVH,D) -> (T,H,D)."""
    T, H, D = q.shape
    kvh = k.shape[1]
    qg = q.reshape(T, kvh, H // kvh, D)
    s = jnp.einsum("tkgd,skd->kgts", qg, k, precision=HI) * D ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HI)
    return o.reshape(T, H, D)


def _swiglu(x, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def _capacity(plen, k, e, cf):
    """Per-expert buffer of a prefill of ``plen`` tokens (rounded up to 8,
    at least 8), as the configuration's ``capacity_factor`` states."""
    c = jnp.ceil(plen.astype(jnp.float32) * k / e * cf).astype(jnp.int32)
    return jnp.maximum(8, (c + 7) // 8 * 8)


def _moe(w, h, plen, dm, quant):
    """Top-k softmax routing over every expert, renormalised gates, the
    prompt's overflow beyond each expert's buffer dropped (choice-major,
    then position order), plus the shared experts.  Also returns each
    position's routing margin: its k-th router logit less the next, over
    the root mean square of its router logits."""
    N, T, d = h.shape
    E, K = dm["e"], dm["k"]
    logits = _mm(h, w["moe/router"], quant)
    probs = jax.nn.softmax(logits, -1)
    gate, idx = jax.lax.top_k(probs, K)                     # (N,T,K)
    edge = jax.lax.top_k(logits, K + 1)[0]
    rms = jnp.sqrt(jnp.mean(logits * logits, -1))
    margin = (edge[..., K - 1] - edge[..., K]) / rms        # (N,T)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    in_prompt = jnp.arange(T)[None, :] < plen[:, None]      # (N,T)
    cap = _capacity(plen, K, E, dm["cf"])[:, None]          # (N,1)
    counts = jnp.zeros((N, 1, E), jnp.int32)
    combine = jnp.zeros((N, T, E), jnp.float32)
    for j in range(K):
        oh = jax.nn.one_hot(idx[..., j], E, dtype=jnp.int32)
        oh = oh * in_prompt[..., None]
        slot = jnp.sum((jnp.cumsum(oh, 1) - oh + counts) * oh, -1)
        keep = (slot < cap) | ~in_prompt
        counts = counts + oh.sum(1, keepdims=True)
        combine = combine + jax.nn.one_hot(idx[..., j], E) \
            * (gate[..., j] * keep)[..., None]
    hf = h.reshape(N * T, d)
    cf = combine.reshape(N * T, E)

    def expert(y, inp):
        wg, wu, wd, c = inp
        return y + c[:, None] * _swiglu(hf, wg, wu, wd, quant), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(hf),
                        (w["moe/w_gate"], w["moe/w_up"], w["moe/w_down"],
                         cf.T))
    y = y.reshape(N, T, d)
    return y + _swiglu(h, w["moe/shared/w_gate"], w["moe/shared/w_up"],
                       w["moe/shared/w_down"], quant), margin


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _layer(w, x, plen, dm, quant):
    dm = dict(dm)
    w = {p: a.astype(jnp.float32) for p, a in w.items()}
    N, T, d = x.shape
    pos = jnp.broadcast_to(jnp.arange(T), (N, T))
    h = _rms(x, w["ln1"], dm["eps"])
    q = _mm(h, w["attn/wq"], quant).reshape(N, T, dm["h"], dm["hd"])
    k = _mm(h, w["attn/wk"], quant).reshape(N, T, dm["kvh"], dm["hd"])
    v = _mm(h, w["attn/wv"], quant).reshape(N, T, dm["kvh"], dm["hd"])
    q, k = _rope(q, pos, dm["theta"]), _rope(k, pos, dm["theta"])
    o = jax.lax.map(lambda a: _attention(*a), (q, k, v))
    x = x + _mm(o.reshape(N, T, -1), w["attn/wo"], quant)
    h = _rms(x, w["ln2"], dm["eps"])
    if dm["moe"]:
        y, margin = _moe(w, h, plen, dm, quant)
    else:
        y = _swiglu(h, w["mlp/w_gate"], w["mlp/w_up"], w["mlp/w_down"], quant)
        margin = jnp.full((N, T), jnp.inf, jnp.float32)
    return x + y, margin


@functools.partial(jax.jit, static_argnames=("quant",))
def _embed(table, tokens, quant):
    t = table.astype(jnp.float32)
    if quant:
        q, s = _q8(t, -1)
        t = q.astype(jnp.float32) * s
    return jnp.take(t, tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("eps", "with_control"))
def _head(top, x, xc, targets, eps, with_control):
    """Per position: the reference's best logit minus the logit of
    ``targets`` (the served token), and minus the logit of the control's
    first choice.  Chunked over tokens."""
    head = top["lm_head"] if "lm_head" in top else top["embed"].T
    head = head.astype(jnp.float32)
    norm = top["final_norm"].astype(jnp.float32)
    N, T, d = x.shape
    M = N * T
    n_chunks = -(-M // HEAD_CHUNK)
    pad = n_chunks * HEAD_CHUNK - M

    def chunks(a):
        a = jnp.pad(a.reshape(M, *a.shape[2:]),
                    [(0, pad)] + [(0, 0)] * (a.ndim - 2))
        return a.reshape(n_chunks, HEAD_CHUNK, *a.shape[1:])

    def one(inp):
        xb, xcb, tb = inp
        lg = _mm(_rms(xb, norm, eps), head, False)
        best = lg.max(-1)
        gap = best - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0]
        if not with_control:
            return gap, jnp.zeros_like(gap)
        pick = jnp.argmax(_mm(_rms(xcb, norm, eps), head, True), -1)
        return gap, best - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]

    gap, gap_c = jax.lax.map(one, (chunks(x), chunks(xc), chunks(targets)))
    return (gap.reshape(-1)[:M].reshape(N, T),
            gap_c.reshape(-1)[:M].reshape(N, T))


def logit_gaps(m: dict, seed: int, prompts: Sequence[np.ndarray],
               served: Sequence[Sequence[int]], control: bool = False
               ) -> List[Dict[str, np.ndarray]]:
    """For each sequence (``prompts[i]`` then ``served[i]``), the gap of
    every served token below the reference's best logit at its position
    (``gap``), the reference's narrowest routing margin over the layers
    there (``margin``; infinite for a dense model) and, with ``control``,
    the gap of the int8 forward's first choice there (``gap_control``)."""
    dm = tuple(sorted(_dims(m).items()))
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(s[:-1], np.int32)])
            for p, s in zip(prompts, served)]
    T = max(len(s) for s in seqs)
    T = -(-T // 128) * 128
    N = len(seqs)
    tokens = np.zeros((N, T), np.int32)
    targets = np.zeros((N, T), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        seq = seqs[i]
        tokens[i, :len(seq)] = seq
        S = len(p)
        targets[i, S - 1:S - 1 + len(s)] = np.asarray(s, np.int32)
    plen = jnp.asarray([len(p) for p in prompts], jnp.int32)
    top = W.top(m, seed)
    x = _embed(top["embed"], jnp.asarray(tokens), False)
    xc = _embed(top["embed"], jnp.asarray(tokens), True) if control else x
    margin = jnp.full(tokens.shape, jnp.inf, jnp.float32)
    for l in range(m["num_hidden_layers"]):
        w = W.layer(m, seed, l)
        x, mg = _layer(w, x, plen, dm, False)
        margin = jnp.minimum(margin, mg)
        if control:
            xc, _ = _layer(w, xc, plen, dm, True)
        del w
    gap, gap_c = _head(top, x, xc, jnp.asarray(targets),
                       float(m["rms_norm_eps"]), control)
    gap, gap_c, margin = np.asarray(gap), np.asarray(gap_c), \
        np.asarray(margin)
    out = []
    for i, (p, s) in enumerate(zip(prompts, served)):
        sl = slice(len(p) - 1, len(p) - 1 + len(s))
        r = {"gap": gap[i, sl], "margin": margin[i, sl]}
        if control:
            r["gap_control"] = gap_c[i, sl]
        out.append(r)
    return out
