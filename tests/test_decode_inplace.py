"""The decode step writes its tokens into the donated KV pools in place.

``decoder_stack_decode`` carries the layer-stacked pools through its layer
scan and scatters each layer's new token into them.  These tests pin that
down on the CPU: the step equals the earlier form, which passed the pools
as scan ``xs``/``ys``, bit for bit; and the compiled serving step holds no
pool-sized temporary (the ``xs``/``ys`` form held a second pool and copied
it whole into the outputs).  ``tests/test_tpu_compile.py`` checks the same
for a described TPU v5e.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import ServingEngine
from repro.models import build_model, split_params
from repro.models import transformer as tfm
from repro.models.common import rms_norm

B, NPER = 4, 4
#: positions of the new token: sequence 1 shares sequence 0's first block
#: (a CoW prefix page); sequence 3 writes the last slot of its last page
POS = np.array([70, 100, 5, NPER * 64 - 1], np.int32)


def _state(cfg, page, kv_dtype):
    L, nblk = cfg.num_attn_layers, B * NPER
    shape = (L, nblk, page, cfg.num_kv_heads, cfg.head_dim)
    kk, kv = jax.random.split(jax.random.key(5))
    table = np.arange(nblk, dtype=np.int32).reshape(B, NPER)
    table[1, 0] = table[0, 0]
    mask = np.zeros((nblk, B), np.int8)
    base = np.zeros((nblk,), np.int32)
    for b in range(B):
        for j in range(POS[b] // page + 1):
            mask[table[b, j], b] = 1
            base[table[b, j]] = j * page
    return {"k_pools": jax.random.normal(kk, shape).astype(kv_dtype),
            "v_pools": jax.random.normal(kv, shape).astype(kv_dtype),
            "block_table": jnp.asarray(table),
            "share_mask": jnp.asarray(mask), "base": jnp.asarray(base),
            "seq_lens": jnp.asarray(POS)}


def _decode_xs_ys(model, params, state, tokens):
    """``decode_step``'s logits and pools with the pools passed through the
    layer scan as ``xs``/``ys``: each layer gets its own slab and returns
    it whole."""
    cfg, page = model.cfg, model.rc.page_size
    pos = state["seq_lens"]
    ids = jnp.take_along_axis(state["block_table"], (pos // page)[:, None],
                              axis=1)[:, 0]

    def body(h, inp):
        lp, kp, vp = inp
        h, (kp, vp), _ = tfm.decoder_layer_decode(
            lp, h, pos, (kp[None], vp[None]), 0, ids, pos % page,
            state["share_mask"], state["base"], pos + 1, cfg, None)
        return h, (kp[0], vp[0])

    x = model._embed(params, tokens, None)
    x, (kp, vp) = jax.lax.scan(body, x, (params["layers"], state["k_pools"],
                                         state["v_pools"]))
    xn = rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
    return model._logits(params, xn, None), kp, vp


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("arch", ["yi-6b", "deepseek-moe-16b"])
def test_carried_pools_equal_the_xs_ys_scan(arch, dtype):
    """Dense and MoE blocks, f32 and bf16: logits and both pools come out
    bitwise equal to the ``xs``/``ys`` scan, and only the new tokens'
    slots of the pools change."""
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype=dtype)
    model = build_model(cfg)
    params, _ = split_params(model.init_params(jax.random.key(0)))
    kv_dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    state = _state(cfg, model.rc.page_size, kv_dtype)
    tokens = jax.random.randint(jax.random.key(3), (B,), 0, cfg.vocab_size)
    old_k, old_v = np.asarray(state["k_pools"]), np.asarray(state["v_pools"])

    want = jax.jit(lambda p, s, t: _decode_xs_ys(model, p, s, t))(
        params, state, tokens)
    logits, st = jax.jit(lambda p, s, t: model.decode_step(p, s, t, None))(
        params, state, tokens)
    got = (logits, st["k_pools"], st["v_pools"])
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    page = model.rc.page_size
    ids = np.asarray(state["block_table"])[np.arange(B), POS // page]
    written = np.zeros(old_k.shape[1:3], bool)
    written[ids, POS % page] = True
    for new, old in ((got[1], old_k), (got[2], old_v)):
        new = np.asarray(new)
        np.testing.assert_array_equal(new[:, ~written], old[:, ~written])
        assert not np.array_equal(new[:, written], old[:, written])
    assert np.array_equal(np.asarray(st["seq_lens"]), POS + 1)


def test_served_decode_step_holds_no_pool_copy():
    """The serving engine's decode program at yi-6b's KV block (4 KV heads
    x 128, 16 sequences x 16 pages of 64), 4 layers in f32: no pool-shaped
    copy, broadcast or dynamic-update-slice, and temporaries under half
    the K+V pools (the ``xs``/``ys`` scan needed 1.4x the pools)."""
    cfg = dataclasses.replace(get_config("yi-6b").reduced(), num_layers=4,
                              num_heads=32, num_kv_heads=4, head_dim=128)
    eng = ServingEngine(cfg, None, max_seqs=16, max_blocks_per_seq=16,
                        max_admit_pages=16, adaptive_ring=False)
    params = jax.eval_shape(
        lambda k: split_params(build_model(cfg).init_params(k))[0],
        jax.random.key(0))
    k, v = eng.engine.pools["k"], eng.engine.pools["v"]
    assert k.shape == (4, 256, 64, 4, 128) and k.dtype == jnp.float32
    table, mask, base = eng.cache.device_tables()
    i32 = jax.ShapeDtypeStruct((16,), jnp.int32)
    compiled = eng._decode_jit.lower(params, k, v, table, mask, base, i32,
                                     i32, None).compile()
    pool_bytes = k.nbytes + v.nbytes
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 2
    pool_shaped = re.compile(
        r"= f32\[4,256,64,4,128\]\{[^}]*\} "
        r"(copy|broadcast|dynamic-update-slice)\(")
    assert not [ln for ln in compiled.as_text().splitlines()
                if pool_shaped.search(ln)]
