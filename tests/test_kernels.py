"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st

from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fpm_copy import fpm_copy_cross_pallas, fpm_copy_pallas
from repro.kernels.paged_attention import paged_attention_slab_pallas
from repro.kernels.ssd_chunk import ssd_intra_chunk_pallas
from repro.kernels.zero_init import zero_init_pallas
from repro.models.mamba2 import _ssd_intra_chunk_jnp


# ---------------------------------------------------------------------------
# FPM copy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("block_shape", [(8, 128), (16, 4, 64), (128,)])
def test_fpm_copy_shapes_dtypes(dtype, block_shape):
    nblk = 16
    key = jax.random.key(0)
    pool = (jax.random.normal(key, (nblk,) + block_shape) * 10).astype(dtype)
    ids = jnp.array([[0, 5], [3, 7], [2, -1], [1, 9]], jnp.int32)
    out = fpm_copy_pallas(pool.copy(), ids, interpret=True)
    ref = kref.fpm_copy(pool, ids[:, 0], ids[:, 1])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_fpm_copy_property(data):
    """Engine contract: destinations are disjoint from sources (CoW targets
    are fresh blocks), sources read the pre-copy pool state."""
    nblk = data.draw(st.integers(8, 32))
    half = nblk // 2
    m = data.draw(st.integers(1, min(half, 8)))
    srcs = data.draw(st.lists(st.integers(0, half - 1), min_size=m,
                              max_size=m))
    dsts = data.draw(st.lists(st.integers(half, nblk - 1), min_size=m,
                              max_size=m, unique=True))
    pool = jnp.arange(nblk * 8, dtype=jnp.float32).reshape(nblk, 8)
    ids = jnp.asarray(np.stack([srcs, dsts], 1).astype(np.int32))
    out = np.asarray(fpm_copy_pallas(pool.copy(), ids, interpret=True))
    ref = np.array(pool)  # writable copy
    for s, d in zip(srcs, dsts):
        ref[d] = np.asarray(pool)[s]
    np.testing.assert_array_equal(out, ref)


def test_fpm_copy_cross():
    src = jax.random.normal(jax.random.key(1), (8, 4, 128))
    dst = jnp.zeros((12, 4, 128))
    ids = jnp.array([[0, 3], [7, 11], [2, -1]], jnp.int32)
    out = fpm_copy_cross_pallas(dst.copy(), src, ids, interpret=True)
    ref = kref.fpm_copy_cross(dst, src, ids[:, 0], ids[:, 1])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# zero init (BuZ)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_zero_init(dtype):
    pool = (jax.random.normal(jax.random.key(2), (10, 8, 128)) + 1).astype(dtype)
    zb = jnp.zeros((1, 8, 128), dtype)
    ids = jnp.array([1, 4, -1, 9], jnp.int32)
    out = zero_init_pallas(pool.copy(), zb, ids, interpret=True)
    ref = kref.zero_init(pool, ids)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert float(jnp.abs(out[1]).max()) == 0
    assert float(jnp.abs(out[0]).max()) > 0


# ---------------------------------------------------------------------------
# paged attention slab
# ---------------------------------------------------------------------------

def _random_paged_case(key, B, H, KVH, D, page, nblk, max_len):
    ks = jax.random.split(key, 8)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k_slab = jax.random.normal(ks[1], (nblk, page, KVH, D), jnp.float32)
    v_slab = jax.random.normal(ks[2], (nblk, page, KVH, D), jnp.float32)
    lens = jax.random.randint(ks[3], (B,), 1, max_len + 1)
    # contiguous identity layout
    nper = nblk // B
    mask = np.zeros((nblk, B), np.int8)
    base = np.zeros(nblk, np.int32)
    for b in range(B):
        for j in range(nper):
            mask[b * nper + j, b] = 1
            base[b * nper + j] = j * page
    return q, k_slab, v_slab, jnp.asarray(mask), jnp.asarray(base), lens


@pytest.mark.parametrize("B,H,KVH,D,page", [
    (4, 8, 2, 64, 16), (2, 4, 4, 128, 8), (8, 16, 1, 128, 16),
])
def test_paged_attention_kernel_vs_ref(B, H, KVH, D, page):
    nblk = B * 4
    q, ks_, vs_, mask, base, lens = _random_paged_case(
        jax.random.key(3), B, H, KVH, D, page, nblk, 4 * page)
    out_p = paged_attention_slab_pallas(q, ks_, vs_, mask, base, lens,
                                        page=page, block_chunk=4,
                                        interpret=True)
    out_r = kref.paged_attention_slab(q, ks_, vs_, mask, base, lens,
                                      page=page, block_chunk=4)
    for a, b in zip(out_p, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_paged_attention_vs_dense_oracle():
    """Slab partials normalized == dense attention over contiguous cache."""
    B, H, KVH, D, page = 3, 6, 2, 32, 8
    nper, nblk = 4, 12
    q, ks_, vs_, mask, base, lens = _random_paged_case(
        jax.random.key(4), B, H, KVH, D, page, nblk, nper * page)
    acc, l, m = kref.paged_attention_slab(q, ks_, vs_, mask, base, lens,
                                          page=page)
    out = np.asarray(acc / np.maximum(np.asarray(l), 1e-30)[..., None])
    k_dense = np.asarray(ks_).reshape(B, nper * page, KVH, D)
    v_dense = np.asarray(vs_).reshape(B, nper * page, KVH, D)
    ref = kref.paged_attention_dense_ref(q, jnp.asarray(k_dense),
                                         jnp.asarray(v_dense), lens)
    np.testing.assert_allclose(out, np.asarray(ref), atol=1e-5)


def test_paged_attention_cow_sharing():
    """A block shared by two sequences contributes to both."""
    B, H, KVH, D, page, nblk = 2, 4, 2, 32, 8, 4
    key = jax.random.key(5)
    q = jax.random.normal(key, (B, H, D))
    ks_ = jax.random.normal(jax.random.key(6), (nblk, page, KVH, D))
    vs_ = jax.random.normal(jax.random.key(7), (nblk, page, KVH, D))
    # block 0 shared at position 0 by both; blocks 1,2 private tails
    mask = jnp.asarray(np.array([[1, 1], [1, 0], [0, 1], [0, 0]], np.int8))
    base = jnp.asarray(np.array([0, page, page, 0], np.int32))
    lens = jnp.asarray(np.array([2 * page, page + 3], np.int32))
    acc, l, m = kref.paged_attention_slab(q, ks_, vs_, mask, base, lens,
                                          page=page)
    out = np.asarray(acc / np.maximum(np.asarray(l), 1e-30)[..., None])
    # dense reference per sequence
    k0 = np.concatenate([np.asarray(ks_[0]), np.asarray(ks_[1])])[None]
    v0 = np.concatenate([np.asarray(vs_[0]), np.asarray(vs_[1])])[None]
    k1 = np.concatenate([np.asarray(ks_[0]), np.asarray(ks_[2])])[None]
    v1 = np.concatenate([np.asarray(vs_[0]), np.asarray(vs_[2])])[None]
    r0 = kref.paged_attention_dense_ref(q[:1], jnp.asarray(k0),
                                        jnp.asarray(v0), lens[:1])
    r1 = kref.paged_attention_dense_ref(q[1:], jnp.asarray(k1),
                                        jnp.asarray(v1), lens[1:])
    np.testing.assert_allclose(out[0], np.asarray(r0)[0], atol=1e-5)
    np.testing.assert_allclose(out[1], np.asarray(r1)[0], atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4), st.integers(0, 1), st.integers(1, 3))
def test_paged_attention_property_lengths(B, kvh_pow, nper):
    """Random valid lengths: normalized output finite, masked slots inert."""
    KVH = 2 ** kvh_pow
    H, D, page = 2 * KVH, 32, 8
    nblk = B * nper
    q, ks_, vs_, mask, base, lens = _random_paged_case(
        jax.random.key(8), B, H, KVH, D, page, nblk, nper * page)
    acc, l, m = kref.paged_attention_slab(q, ks_, vs_, mask, base, lens,
                                          page=page)
    out = np.asarray(acc / np.maximum(np.asarray(l), 1e-30)[..., None])
    assert np.isfinite(out).all()
    # mutating data beyond each sequence's length must not change output
    spoiled = np.asarray(ks_).copy()
    for b in range(B):
        L = int(lens[b])
        blk, off = L // page, L % page
        g = b * nper + blk
        if blk < nper:
            spoiled[g, off:] = 1e9
        for j in range(blk + 1, nper):
            spoiled[b * nper + j] = 1e9
    acc2, l2, _ = kref.paged_attention_slab(
        q, jnp.asarray(spoiled), vs_, mask, base, lens, page=page)
    out2 = np.asarray(acc2 / np.maximum(np.asarray(l2), 1e-30)[..., None])
    np.testing.assert_allclose(out, out2, atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,prefix,causal", [
    (64, 0, True), (128, 16, True), (64, 0, False),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_vs_ref(S, prefix, causal, dtype):
    B, H, KVH, D = 2, 4, 2, 64
    q = jax.random.normal(jax.random.key(9), (B, H, S, D)).astype(dtype)
    k = jax.random.normal(jax.random.key(10), (B, KVH, S, D)).astype(dtype)
    v = jax.random.normal(jax.random.key(11), (B, KVH, S, D)).astype(dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, prefix_len=prefix,
                                 bq=32, bk=32, interpret=True)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    ref = kref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), pos, pos, jnp.ones((B, S), bool),
        causal=causal, prefix_len=prefix).transpose(0, 2, 1, 3)
    atol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_flash_jnp_scan_vs_ref():
    """The in-model scan flash (models/attention.py) vs naive oracle."""
    from repro.models.attention import MaskInfo, flash_attention
    B, S, H, KVH, D = 2, 96, 4, 2, 32
    q = jax.random.normal(jax.random.key(12), (B, S, H, D))
    k = jax.random.normal(jax.random.key(13), (B, S, KVH, D))
    v = jax.random.normal(jax.random.key(14), (B, S, KVH, D))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    valid = jnp.ones((B, S), bool)
    out = flash_attention(q, k, v, pos, pos, valid,
                          MaskInfo(causal=True, prefix_len=8), kv_chunk=32)
    ref = kref.flash_attention_ref(q, k, v, pos, pos, valid, causal=True,
                                   prefix_len=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# ---------------------------------------------------------------------------
# SSD intra-chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Q,P,N", [(32, 16, 8), (64, 32, 16)])
def test_ssd_intra_kernel_vs_ref(Q, P, N):
    B, H = 2, 4
    xb = jax.random.normal(jax.random.key(15), (B, Q, H, P))
    dtb = jax.nn.softplus(jax.random.normal(jax.random.key(16), (B, Q, H)))
    cum = jnp.cumsum(-0.2 * dtb, axis=1)
    Bm = jax.random.normal(jax.random.key(17), (B, Q, N))
    Cm = jax.random.normal(jax.random.key(18), (B, Q, N))
    out = ssd_intra_chunk_pallas(xb, dtb, cum, Bm, Cm, interpret=True)
    ref = _ssd_intra_chunk_jnp(xb, dtb, cum, Bm, Cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ssd_chunked_vs_naive_recurrence():
    """Chunked SSD == token-by-token recurrence (the paper-exact check)."""
    from repro.models.mamba2 import ssd_chunked
    B, S, H, P, N = 2, 64, 4, 16, 8
    x = jax.random.normal(jax.random.key(19), (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(20), (B, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.key(21), (H,)) * 0.3)
    Bm = jax.random.normal(jax.random.key(22), (B, S, N)) * 0.5
    Cm = jax.random.normal(jax.random.key(23), (B, S, N)) * 0.5
    D = jnp.ones((H,))
    y_chunk, h_chunk = ssd_chunked(x, dt, A, Bm, Cm, D, chunk=16)
    y_ref = kref.ssd_ref(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_ref),
                               atol=2e-3, rtol=1e-3)


def test_ssd_final_state_matches_decode_seed():
    """h_final from the chunked path == state after running the naive
    recurrence, so prefill->decode handoff is exact."""
    from repro.models.mamba2 import ssd_chunked
    B, S, H, P, N = 1, 48, 2, 8, 4
    x = jax.random.normal(jax.random.key(24), (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(25), (B, S, H)))
    A = -jnp.exp(jnp.zeros((H,)))
    Bm = jax.random.normal(jax.random.key(26), (B, S, N)) * 0.5
    Cm = jax.random.normal(jax.random.key(27), (B, S, N)) * 0.5
    Dk = jnp.zeros((H,))
    _, h_final = ssd_chunked(x, dt, A, Bm, Cm, Dk, chunk=16)
    # naive state
    h = np.zeros((B, H, P, N), np.float32)
    for t in range(S):
        decay = np.exp(np.asarray(dt[:, t]) * np.asarray(A)[None])
        h = h * decay[..., None, None] + np.einsum(
            "bhp,bn,bh->bhpn", np.asarray(x[:, t], np.float32),
            np.asarray(Bm[:, t], np.float32), np.asarray(dt[:, t]))
    np.testing.assert_allclose(np.asarray(h_final), h, atol=2e-3, rtol=1e-3)


def test_paged_attention_exclusive_mode_matches_allpairs():
    """owner-gather fast path == all-pairs when no block is shared."""
    B, H, KVH, D, page = 4, 8, 2, 64, 16
    nblk = B * 4
    q, ks_, vs_, mask, base, lens = _random_paged_case(
        jax.random.key(30), B, H, KVH, D, page, nblk, 4 * page)
    a1 = kref.paged_attention_slab(q, ks_, vs_, mask, base, lens, page=page,
                                   block_chunk=4, exclusive=False)
    a2 = kref.paged_attention_slab(q, ks_, vs_, mask, base, lens, page=page,
                                   block_chunk=4, exclusive=True)
    o1 = np.asarray(a1[0] / np.maximum(np.asarray(a1[1]), 1e-30)[..., None])
    o2 = np.asarray(a2[0] / np.maximum(np.asarray(a2[1]), 1e-30)[..., None])
    np.testing.assert_allclose(o1, o2, atol=1e-5)


def test_psm_rdma_kernel_traces_on_multidevice_mesh():
    """PSM remote-DMA kernel (TARGET TPU code — RDMA can't execute on CPU):
    abstract evaluation inside shard_map must succeed, proving the kernel
    body, BlockSpecs, and semaphore plumbing are well-formed."""
    import subprocess, sys, os, textwrap
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.kernels.psm_transfer import psm_transfer_pallas
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        def local(pool_slab, ids):
            return psm_transfer_pallas.__wrapped__(pool_slab, ids,
                                                   axis_name="model")
        with mesh:
            out = jax.eval_shape(
                lambda p, i: jax.shard_map(
                    local, mesh=mesh, in_specs=(P("model"), P(None)),
                    out_specs=P("model"), check_vma=False)(p, i),
                jax.ShapeDtypeStruct((32, 16, 128), jnp.float32),
                jax.ShapeDtypeStruct((3, 3), jnp.int32))
        assert out.shape == (32, 16, 128)
        print("OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-2000:]
