"""Random weights from ``--seed``, made by the benchmark and handed to the
serving engine, so the plain reference can make the very same numbers
again without taking anything from the program.

Which leaves there are, and their shapes and scales, is the
configuration's weight layout (``weight_layout`` of its module in
``bench/references/``): leaves outside any layer stack, and one or more
stacks of identical layers.  Every leaf draws from its own key,
``fold_in(fold_in(root, crc32(path)), layer)``: the whole tree comes out
of ONE jitted call on the device (the layer-stacked leaves are made one
layer at a time inside it, so no fp32 copy of a stacked leaf ever exists),
and :func:`layer` makes any one layer's leaves alone, bit for bit the same.
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from bench import spec

#: (path, shape, scale) of one leaf; scale ``"norm"`` marks an RMSNorm
#: scale (stored as its offset from 1)
Spec = Tuple[str, Tuple[int, ...], Union[float, str]]
#: (leaves outside the stacks, [(stack prefix, layers, one layer's leaves)])
Layout = Tuple[List[Spec], List[Tuple[str, int, List[Spec]]]]


def layout(m: dict) -> Layout:
    """The weight layout of configuration ``m``, from its module."""
    return spec.reference(m).weight_layout(m)


def root_key(seed: int):
    """A key from any non-negative whole number (seeds may pass 2**31)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, seed >> 31)


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
    """``root key -> parameter tree``: the program's layout (each stack's
    leaves stacked on a leading axis) in ``dtype``, each stacked leaf made
    one layer at a time."""
    tops, stacks = layout(m)

    def build(root):
        flat = {p: _draw(_leaf_key(root, p), s, sc, dtype)
                for p, s, sc in tops}
        for prefix, n, specs in stacks:
            for p, s, sc in specs:
                k = _leaf_key(root, f"{prefix}/{p}")
                flat[f"{prefix}/{p}"] = jax.lax.map(
                    lambda l, k=k, s=s, sc=sc: _draw(
                        jax.random.fold_in(k, l), s, sc, dtype),
                    jnp.arange(n))
        return _nest(flat)

    return build


def make_params(m: dict, seed: int, dtype=jnp.bfloat16) -> Dict:
    """The whole parameter tree from ``seed``, in one jitted call."""
    return jax.jit(init_fn(m, dtype))(root_key(seed))


@functools.partial(jax.jit, static_argnames=("prefix", "specs", "dtype"))
def _layer(root, l, prefix, specs, dtype):
    return {p: _draw(jax.random.fold_in(_leaf_key(root, f"{prefix}/{p}"), l),
                     s, sc, dtype) for p, s, sc in specs}


def layer(m: dict, seed: int, l: int, stack: Optional[str] = None,
          dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """Layer ``l`` of the stack named ``stack`` (the layout's only stack
    when None), its leaves flat by path, equal to slice ``l`` of the
    stacked leaves :func:`make_params` makes."""
    stacks = {prefix: specs for prefix, _, specs in layout(m)[1]}
    if stack is None:
        if len(stacks) != 1:
            raise ValueError(f"the layout has stacks {sorted(stacks)}: "
                             "name one")
        (stack,) = stacks
    return _layer(root_key(seed), jnp.int32(l), stack,
                  tuple(stacks[stack]), dtype)


@functools.partial(jax.jit, static_argnames=("specs", "dtype"))
def _top(root, specs, dtype):
    return {p: _draw(_leaf_key(root, p), s, sc, dtype) for p, s, sc in specs}


def top(m: dict, seed: int, dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """The leaves outside the layer stacks, flat by path."""
    return _top(root_key(seed), tuple(layout(m)[0]), dtype)
