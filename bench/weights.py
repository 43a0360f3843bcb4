"""Random weights from ``--seed``, made by the benchmark and handed to the
serving engine, so the plain reference can make the very same numbers
again without taking anything from the program.

Every leaf draws from its own key, ``fold_in(fold_in(root, crc32(path)),
layer)``: the whole tree comes out of ONE jitted call on the device (the
layer-stacked leaves are made one layer at a time inside it, so no fp32
copy of a stacked leaf ever exists), and :func:`layer` makes any one
layer's leaves alone, bit for bit the same.
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

VOCAB_MULTIPLE = 256


def padded_vocab(m: dict) -> int:
    v = m["vocab_size"]
    return -(-v // VOCAB_MULTIPLE) * VOCAB_MULTIPLE


def root_key(seed: int):
    """A key from any non-negative whole number (seeds may pass 2**31)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, seed >> 31)


def top_specs(m: dict) -> List[Tuple[str, Tuple[int, ...], float]]:
    """(path, shape, scale) of the leaves outside the layer stack;
    scale 'norm' marks an RMSNorm scale (stored as its offset from 1)."""
    d, v = m["hidden_size"], padded_vocab(m)
    specs = [("embed", (v, d), 0.02), ("final_norm", (d,), "norm")]
    if not m["tie_word_embeddings"]:
        specs.append(("lm_head", (d, v), 0.02))
    return specs


def layer_specs(m: dict) -> List[Tuple[str, Tuple[int, ...], float]]:
    """(path, per-layer shape, scale) of one decoder layer's leaves."""
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


def _draw(key, shape, scale, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if scale == "norm":
        return (0.1 * x).astype(dtype)
    return (x * scale).astype(dtype)


def _leaf_key(root, path: str):
    return jax.random.fold_in(root, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _nest(flat: Dict[str, jnp.ndarray]) -> Dict:
    out: Dict = {}
    for path, x in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = x
    return out


def init_fn(m: dict, dtype=jnp.bfloat16):
    """``root key -> parameter tree``: the program's layout (layer leaves
    stacked on a leading axis) in ``dtype``, each stacked leaf made one
    layer at a time."""
    L = m["num_hidden_layers"]
    tops, layers = top_specs(m), layer_specs(m)

    def build(root):
        flat = {p: _draw(_leaf_key(root, p), s, sc, dtype)
                for p, s, sc in tops}
        for p, s, sc in layers:
            k = _leaf_key(root, "layers/" + p)
            flat["layers/" + p] = jax.lax.map(
                lambda l, k=k, s=s, sc=sc: _draw(jax.random.fold_in(k, l),
                                                 s, sc, dtype),
                jnp.arange(L))
        return _nest(flat)

    return build


def make_params(m: dict, seed: int, dtype=jnp.bfloat16) -> Dict:
    """The whole parameter tree from ``seed``, in one jitted call."""
    return jax.jit(init_fn(m, dtype))(root_key(seed))


@functools.partial(jax.jit, static_argnames=("specs", "dtype"))
def _layer(root, l, specs, dtype):
    return {p: _draw(jax.random.fold_in(_leaf_key(root, "layers/" + p), l),
                     s, sc, dtype) for p, s, sc in specs}


def layer(m: dict, seed: int, l: int, dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """Layer ``l``'s leaves, flat by path, equal to slice ``l`` of the
    stacked leaves :func:`make_params` makes."""
    return _layer(root_key(seed), jnp.int32(l), tuple(
        (p, s, sc) for p, s, sc in layer_specs(m)), dtype)


@functools.partial(jax.jit, static_argnames=("specs", "dtype"))
def _top(root, specs, dtype):
    return {p: _draw(_leaf_key(root, p), s, sc, dtype) for p, s, sc in specs}


def top(m: dict, seed: int, dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """The embedding, final norm and LM head, flat by path."""
    return _top(root_key(seed), tuple(top_specs(m)), dtype)
