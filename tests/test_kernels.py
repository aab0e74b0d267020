"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_decode.ops import fused_decode, rope_at
from repro.kernels.flash_decode.ops import flash_decode
from repro.kernels.fused_mla_decode.ops import fused_mla_decode
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.rwkv6_scan.ops import rwkv6_scan


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("B,D,S,q_loc,kv_loc,hd", [
    (2, 128, 512, 4, 2, 32),
    (4, 256, 1024, 4, 1, 64),     # MQA
    (1, 64, 256, 8, 8, 16),       # MHA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cache_len", [0, 100, -1])
def test_fused_decode_sweep(B, D, S, q_loc, kv_loc, hd, dtype, cache_len):
    cache_len = S - 1 if cache_len < 0 else min(cache_len, S - 1)
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    P_ = (q_loc + 2 * kv_loc) * hd
    x = (jax.random.normal(ks[0], (B, D)) * 0.2).astype(dtype)
    wqkv = (jax.random.normal(ks[1], (D, P_)) * 0.05).astype(dtype)
    bqkv = (jax.random.normal(ks[2], (P_,)) * 0.01).astype(dtype)
    wo = (jax.random.normal(ks[3], (q_loc * hd, D)) * 0.05).astype(dtype)
    kc = (jax.random.normal(ks[4], (S, kv_loc, hd)) * 0.3).astype(dtype)
    vc = (jax.random.normal(ks[5], (S, kv_loc, hd)) * 0.3).astype(dtype)
    cos, sin = rope_at(cache_len, hd)
    args = (x, wqkv, bqkv, wo, kc, vc, cache_len, cos, sin)
    kw = dict(q_heads=q_loc, kv_heads=kv_loc)
    o, kn, vn, m, l = fused_decode(*args, **kw, interpret=True, block_s=128)
    o_r, kn_r, vn_r, m_r, l_r = fused_decode(*args, **kw, use_ref=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_r, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(l), np.asarray(l_r),
                               rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(kn, np.float32),
                               np.asarray(kn_r, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window,cap", [(0, 0.0), (128, 0.0), (0, 30.0)])
def test_fused_decode_window_softcap(window, cap):
    B, D, S, q_loc, kv_loc, hd = 2, 128, 512, 4, 2, 32
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 8)
    P_ = (q_loc + 2 * kv_loc) * hd
    args = ((jax.random.normal(ks[0], (B, D)) * 0.2),
            jax.random.normal(ks[1], (D, P_)) * 0.05, None,
            jax.random.normal(ks[3], (q_loc * hd, D)) * 0.05,
            jax.random.normal(ks[4], (S, kv_loc, hd)) * 0.3,
            jax.random.normal(ks[5], (S, kv_loc, hd)) * 0.3,
            300, *rope_at(300, hd))
    kw = dict(q_heads=q_loc, kv_heads=kv_loc, window=window,
              attn_softcap=cap)
    o, *_ = fused_decode(*args, **kw, interpret=True, block_s=128)
    o_r, *_ = fused_decode(*args, **kw, use_ref=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                               rtol=3e-5, atol=3e-5)


def test_fused_decode_partial_mode_combines():
    """fuse_out=False partials combine across a 2-way split of the KV
    sequence to the same answer as the monolithic kernel — the cross-chip
    ClusterReduce property (paper Alg. 3)."""
    from repro.core.primitives import flash_merge
    B, D, S, q_loc, kv_loc, hd = 2, 128, 512, 4, 2, 32
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 8)
    P_ = (q_loc + 2 * kv_loc) * hd
    x = jax.random.normal(ks[0], (B, D)) * 0.2
    wqkv = jax.random.normal(ks[1], (D, P_)) * 0.05
    wo = jax.random.normal(ks[3], (q_loc * hd, D)) * 0.05
    kc = jax.random.normal(ks[4], (S, kv_loc, hd)) * 0.3
    vc = jax.random.normal(ks[5], (S, kv_loc, hd)) * 0.3
    clen = 400
    cos, sin = rope_at(clen, hd)
    kw = dict(q_heads=q_loc, kv_heads=kv_loc)
    o_full, *_ = fused_decode(x, wqkv, None, wo, kc, vc, clen, cos, sin,
                              **kw, use_ref=True)
    # split: first half of the cache on "chip 0" (plus the new token),
    # second half on "chip 1"
    h = S // 2
    acc0, _, _, m0, l0 = fused_decode(x, wqkv, None, wo, kc[:h], vc[:h],
                                      min(clen, h), cos, sin, **kw,
                                      fuse_out=False, use_ref=True)
    # chip 1 sees the tail; mask new-token by zero-weight trick: include it
    # only on chip 0 ⇒ chip 1 computes cache-only partial via flash_decode
    q = (x @ wqkv)[:, : q_loc * hd].reshape(B, q_loc, hd)
    half = hd // 2
    c, s_ = cos, sin
    q = jnp.concatenate([q[..., :half] * c - q[..., half:] * s_,
                         q[..., half:] * c + q[..., :half] * s_], -1)
    s1 = jnp.einsum("bkqh,skh->bkqs",
                    q.reshape(B, kv_loc, q_loc // kv_loc, hd),
                    kc[h:]) / np.sqrt(hd)
    valid = (jnp.arange(h) + h) < clen
    s1 = jnp.where(valid[None, None, None], s1, -jnp.inf)
    m1 = jnp.max(s1, -1)
    m1s = jnp.where(jnp.isfinite(m1), m1, -1e30)
    p1 = jnp.where(valid[None, None, None], jnp.exp(s1 - m1s[..., None]), 0)
    l1 = p1.sum(-1)
    o1 = jnp.einsum("bkqs,skh->bkqh", p1, vc[h:])
    m, l, o = flash_merge(
        (m0.reshape(B, kv_loc, -1), l0.reshape(B, kv_loc, -1),
         acc0.reshape(B, kv_loc, q_loc // kv_loc, hd)),
        (m1s, l1, o1))
    att = (o / l[..., None]).reshape(B, q_loc * hd)
    o_comb = att @ wo
    np.testing.assert_allclose(np.asarray(o_comb), np.asarray(o_full),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,D,S,q_loc,kv_loc,hd,d_out", [
    (2, 128, 512, 4, 2, 32, 64),
    (1, 64, 256, 8, 4, 16, 64),
])
@pytest.mark.parametrize("cache_len", [0, 100, -1])
def test_fused_decode_partial_o_vs_oracle(B, D, S, q_loc, kv_loc, hd,
                                          d_out, cache_len):
    """``fuse_out="partial_o"``: the in-kernel per-head Output-Projection
    of the unnormalized accumulator matches the jnp oracle, and
    normalizing + summing heads reproduces the monolithic fused output
    through the flat wo."""
    cache_len = S - 1 if cache_len < 0 else min(cache_len, S - 1)
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 6)
    P_ = (q_loc + 2 * kv_loc) * hd
    x = jax.random.normal(ks[0], (B, D)) * 0.2
    wqkv = jax.random.normal(ks[1], (D, P_)) * 0.05
    wo3 = jax.random.normal(ks[2], (q_loc, hd, d_out)) * 0.05
    kc = jax.random.normal(ks[3], (S, kv_loc, hd)) * 0.3
    vc = jax.random.normal(ks[4], (S, kv_loc, hd)) * 0.3
    cos, sin = rope_at(cache_len, hd)
    kw = dict(q_heads=q_loc, kv_heads=kv_loc, fuse_out="partial_o")
    o, kn, vn, m, l = fused_decode(x, wqkv, None, wo3, kc, vc, cache_len,
                                   cos, sin, **kw, interpret=True,
                                   block_s=64)
    o_r, _, _, m_r, l_r = fused_decode(x, wqkv, None, wo3, kc, vc,
                                       cache_len, cos, sin, **kw,
                                       use_ref=True)
    assert o.shape == (B, q_loc, d_out)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_r),
                               rtol=1e-5, atol=1e-5)
    # normalize per head + sum over heads == fuse_out=True through the
    # flat [q_loc*hd, d_out] wo (the serve-layout identity)
    o_flat, *_ = fused_decode(x, wqkv, None, wo3.reshape(q_loc * hd, d_out),
                              kc, vc, cache_len, cos, sin,
                              q_heads=q_loc, kv_heads=kv_loc, use_ref=True)
    comb = (np.asarray(o) / np.asarray(l)[..., None]).sum(1)
    np.testing.assert_allclose(comb, np.asarray(o_flat),
                               rtol=1e-4, atol=1e-4)


def test_fused_decode_partial_o_cluster_combine():
    """partial_o partials from a 2-way KV-sequence split flash-merge to
    the monolithic answer — the single-ClusterReduce property of the
    prepacked serve layout (projection inside the kernel, combine after)."""
    from repro.core.primitives import flash_merge
    B, D, S, q_loc, kv_loc, hd, d_out = 2, 128, 512, 4, 2, 32, 96
    key = jax.random.PRNGKey(12)
    ks = jax.random.split(key, 6)
    P_ = (q_loc + 2 * kv_loc) * hd
    x = jax.random.normal(ks[0], (B, D)) * 0.2
    wqkv = jax.random.normal(ks[1], (D, P_)) * 0.05
    wo3 = jax.random.normal(ks[2], (q_loc, hd, d_out)) * 0.05
    kc = jax.random.normal(ks[3], (S, kv_loc, hd)) * 0.3
    vc = jax.random.normal(ks[4], (S, kv_loc, hd)) * 0.3
    clen = 400
    cos, sin = rope_at(clen, hd)
    kw = dict(q_heads=q_loc, kv_heads=kv_loc, fuse_out="partial_o")
    h = S // 2
    # "chip 0": first half of the cache, owns the new token
    o0, _, _, m0, l0 = fused_decode(
        x, wqkv, None, wo3, kc[:h], vc[:h], min(clen, h), cos, sin, **kw,
        interpret=True, block_s=64, include_new=jnp.int32(1))
    # "chip 1": second half (positions offset by h), new token excluded
    o1, _, _, m1, l1 = fused_decode(
        x, wqkv, None, wo3, kc[h:], vc[h:], clen, cos, sin, **kw,
        interpret=True, block_s=64, include_new=jnp.int32(0),
        pos=jnp.arange(h, S, dtype=jnp.int32), pos_base=jnp.int32(h))
    m, l, o = flash_merge((np.asarray(m0), np.asarray(l0), np.asarray(o0)),
                          (np.asarray(m1), np.asarray(l1), np.asarray(o1)))
    comb = (np.asarray(o) / np.asarray(l)[..., None]).sum(1)
    o_full, *_ = fused_decode(x, wqkv, None,
                              wo3.reshape(q_loc * hd, d_out), kc, vc, clen,
                              cos, sin, q_heads=q_loc, kv_heads=kv_loc,
                              use_ref=True)
    np.testing.assert_allclose(comb, np.asarray(o_full),
                               rtol=1e-4, atol=1e-4)


def test_fused_mla_partial_o_fold():
    """MLA partial_o through the prepacked W_UV·W_O fold equals the
    monolithic fuse_out=True result with the unfolded weights."""
    B, D, S, q_loc = 2, 128, 512, 4
    l_rank, rope_d, nope, v_dim, d_out = 32, 8, 16, 16, 96
    key = jax.random.PRNGKey(13)
    ks = jax.random.split(key, 8)
    x = jax.random.normal(ks[0], (B, D)) * 0.2
    wq = jax.random.normal(ks[1], (D, q_loc * (nope + rope_d))) * 0.05
    wdkv = jax.random.normal(ks[2], (D, l_rank + rope_d)) * 0.05
    wuk = jax.random.normal(ks[3], (q_loc, nope, l_rank)) * 0.05
    wuv = jax.random.normal(ks[4], (q_loc, l_rank, v_dim)) * 0.05
    wo = jax.random.normal(ks[5], (q_loc * v_dim, d_out)) * 0.05
    cc = jax.random.normal(ks[6], (S, l_rank + rope_d)) * 0.3
    clen = 300
    cos, sin = rope_at(clen, rope_d)
    wproj = jnp.einsum("qlv,qvd->qld", wuv, wo.reshape(q_loc, v_dim, d_out))
    kw = dict(q_heads=q_loc, nope=nope, rope_d=rope_d, l_rank=l_rank)
    o, cn, m, l = fused_mla_decode(
        x, wq, wdkv, wuk, wproj, jnp.zeros((1, 1)), cc, clen, cos, sin,
        **kw, v_dim=d_out, fuse_out="partial_o", interpret=True, block_s=64)
    o_r, cn_r, m_r, l_r = fused_mla_decode(
        x, wq, wdkv, wuk, wproj, jnp.zeros((1, 1)), cc, clen, cos, sin,
        **kw, v_dim=d_out, fuse_out="partial_o", use_ref=True)
    assert o.shape == (B, q_loc, d_out)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                               rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(cn), np.asarray(cn_r),
                               rtol=1e-5, atol=1e-5)
    o_full, *_ = fused_mla_decode(x, wq, wdkv, wuk, wuv, wo, cc, clen,
                                  cos, sin, **kw, v_dim=v_dim,
                                  fuse_out=True, use_ref=True)
    comb = (np.asarray(o) / np.asarray(l)[..., None]).sum(1)
    np.testing.assert_allclose(comb, np.asarray(o_full),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("S,q_loc,kv_loc,hd,clen", [
    (512, 4, 2, 32, 77), (256, 8, 1, 64, 256), (1024, 2, 2, 16, 1000)])
def test_flash_decode_sweep(S, q_loc, kv_loc, hd, clen):
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, q_loc, hd)) * 0.3
    kc = jax.random.normal(ks[1], (S, kv_loc, hd)) * 0.3
    vc = jax.random.normal(ks[2], (S, kv_loc, hd)) * 0.3
    o = flash_decode(q, kc, vc, min(clen, S), block_s=128, interpret=True)
    o_r = flash_decode(q, kc, vc, min(clen, S), use_ref=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("l_rank,rope_d,nope,v_dim", [
    (64, 16, 32, 32), (32, 8, 16, 16)])
@pytest.mark.parametrize("fuse_out", [True, False])
def test_fused_mla_sweep(l_rank, rope_d, nope, v_dim, fuse_out):
    B, D, S, q_loc = 2, 128, 512, 4
    key = jax.random.PRNGKey(4)
    ks = jax.random.split(key, 8)
    x = jax.random.normal(ks[0], (B, D)) * 0.2
    wq = jax.random.normal(ks[1], (D, q_loc * (nope + rope_d))) * 0.05
    wdkv = jax.random.normal(ks[2], (D, l_rank + rope_d)) * 0.05
    wuk = jax.random.normal(ks[3], (q_loc, nope, l_rank)) * 0.05
    wuv = jax.random.normal(ks[4], (q_loc, l_rank, v_dim)) * 0.05
    wo = jax.random.normal(ks[5], (q_loc * v_dim, D)) * 0.05
    cc = jax.random.normal(ks[6], (S, l_rank + rope_d)) * 0.3
    clen = 300
    cos, sin = rope_at(clen, rope_d)
    kw = dict(q_heads=q_loc, nope=nope, rope_d=rope_d, l_rank=l_rank,
              v_dim=v_dim, fuse_out=fuse_out)
    o, cn, m, l = fused_mla_decode(x, wq, wdkv, wuk, wuv, wo, cc, clen, cos,
                                   sin, block_s=128, interpret=True, **kw)
    o_r, cn_r, m_r, l_r = fused_mla_decode(x, wq, wdkv, wuk, wuv, wo, cc,
                                           clen, cos, sin, use_ref=True, **kw)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                               rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(cn), np.asarray(cn_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l_r),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,S,C", [(2, 256, 128), (1, 64, 512), (4, 128, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_sweep(B, S, C, dtype):
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 3)
    la = (-jnp.abs(jax.random.normal(ks[0], (B, S, C))) * 0.1).astype(dtype)
    b = (jax.random.normal(ks[1], (B, S, C)) * 0.2).astype(dtype)
    h0 = jax.random.normal(ks[2], (B, C)) * 0.3
    o, hf = rglru_scan(la, b, h0, block_t=64, block_c=64, interpret=True)
    o_r, hf_r = rglru_scan(la, b, h0, use_ref=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_r, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hf_r),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("B,S,H,hd", [(2, 64, 4, 16), (1, 128, 2, 32)])
def test_rwkv6_scan_sweep(B, S, H, hd):
    key = jax.random.PRNGKey(6)
    ks = jax.random.split(key, 6)
    r = jax.random.normal(ks[0], (B, S, H, hd)) * 0.3
    k = jax.random.normal(ks[1], (B, S, H, hd)) * 0.3
    v = jax.random.normal(ks[2], (B, S, H, hd)) * 0.3
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, hd))) * 0.5 + 0.4
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, hd, hd)) * 0.1
    o, sf = rwkv6_scan(r, k, v, w, u, s0, block_t=16, block_h=2,
                       interpret=True)
    o_r, sf_r = rwkv6_scan(r, k, v, w, u, s0, use_ref=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sf_r),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fuse_out", ["partial_o", True, False])
@pytest.mark.parametrize("B,dtype", [(1, jnp.float32), (2, jnp.bfloat16)])
def test_fused_decode_streamed_weight_tiles(fuse_out, B, dtype):
    """A tile budget of one head forces several projection and output
    grid steps (the published-width layout, at a small size); the result
    still matches the oracle."""
    from repro.kernels.fused_decode.fused_decode import fused_decode_attention
    from repro.kernels.fused_decode.ref import fused_decode_attention_ref
    D, S, q_loc, kv_loc, hd, clen = 128, 256, 4, 2, 128, 150
    ks = jax.random.split(jax.random.PRNGKey(21), 7)
    P_ = (q_loc + 2 * kv_loc) * hd
    x = (jax.random.normal(ks[0], (B, D)) * 0.2).astype(dtype)
    wqkv = (jax.random.normal(ks[1], (D, P_)) * 0.05).astype(dtype)
    bqkv = (jax.random.normal(ks[2], (P_,)) * 0.01).astype(dtype)
    wo = jax.random.normal(ks[3], (q_loc, hd, D)) * 0.05
    wo = (wo if fuse_out == "partial_o" else wo.reshape(q_loc * hd, D)
          ).astype(dtype)
    kc = (jax.random.normal(ks[4], (S, kv_loc, hd)) * 0.3).astype(dtype)
    vc = (jax.random.normal(ks[5], (S, kv_loc, hd)) * 0.3).astype(dtype)
    ln = jax.random.normal(ks[6], (D,)) * 0.1
    cos, sin = rope_at(clen, hd)
    args = (x, wqkv, bqkv, wo, kc, vc, clen, cos, sin)
    kw = dict(q_heads=q_loc, kv_heads=kv_loc, fuse_out=fuse_out,
              norm_scale=ln)
    tile = D * hd * jnp.dtype(dtype).itemsize          # one head per tile
    got = fused_decode_attention(*args, **kw, block_s=64, interpret=True,
                                 weight_tile_bytes=tile)
    want = fused_decode_attention_ref(*args, **kw)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), **_tol(dtype))


@pytest.mark.parametrize("fuse_out", ["partial_o", True, False])
def test_fused_mla_streamed_weight_tiles(fuse_out):
    """MLA with a one-head tile budget: several projection (wq + wuk) and
    output (wuv / wo) grid steps still match the oracle."""
    from repro.kernels.fused_mla_decode.fused_mla_decode import (
        fused_mla_decode_attention)
    from repro.kernels.fused_mla_decode.ref import (
        fused_mla_decode_attention_ref)
    B, D, S, q_loc = 2, 128, 256, 4
    l_rank, rope_d, nope, v_dim = 128, 64, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(22), 8)
    x = jax.random.normal(ks[0], (B, D)) * 0.2
    wq = jax.random.normal(ks[1], (D, q_loc * (nope + rope_d))) * 0.05
    wdkv = jax.random.normal(ks[2], (D, l_rank + rope_d)) * 0.05
    wuk = jax.random.normal(ks[3], (q_loc, nope, l_rank)) * 0.05
    wo = jax.random.normal(ks[5], (q_loc * v_dim, D)) * 0.05
    wuv = jax.random.normal(ks[4], (q_loc, l_rank, v_dim)) * 0.05
    if fuse_out == "partial_o":
        wuv = jnp.einsum("qlv,qvd->qld", wuv, wo.reshape(q_loc, v_dim, D))
    cc = jax.random.normal(ks[6], (S, l_rank + rope_d)) * 0.3
    clen = 200
    cos, sin = rope_at(clen, rope_d)
    kw = dict(q_heads=q_loc, nope=nope, rope_d=rope_d, l_rank=l_rank,
              v_dim=D if fuse_out == "partial_o" else v_dim,
              fuse_out=fuse_out, norm_scale=jax.random.normal(ks[7], (D,)))
    args = (x, wq, wdkv, wuk, wuv, wo, cc, clen, cos, sin)
    got = fused_mla_decode_attention(*args, **kw, block_s=64, interpret=True,
                                     weight_tile_bytes=2 * D * 4 * 64)
    want = fused_mla_decode_attention_ref(*args, **kw)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-5, atol=5e-5)
