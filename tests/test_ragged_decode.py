"""Ragged decode vs the dense per-sequence oracle.

Every slot of a ragged batch (per-slot ``cache_lens``, staggered
activation, inactive −1 slots) must match a DENSE lockstep run of that
sequence alone through the legacy scalar-``cache_len`` path — for each
kernel (``fused_decode`` / ``fused_mla_decode`` / ``flash_decode``), on
both backends, at cluster sizes {1, 2, 4}, for global caches and
sliding-window ring caches past the wrap threshold (satellite of
ISSUE 3; DESIGN.md §6).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import run_multidevice


# ---------------------------------------------------------------------------
# Single-device (cluster 1) fast checks — run in the tier-1 `fast` job
# ---------------------------------------------------------------------------
def _staggered_inputs(rng, T, B, D):
    """xs_r[t, b] = the input slot b sees at global tick t (slot b joins
    at tick starts[b]); xs_o[b, i] = its dense per-sequence stream."""
    starts = [0, T // 3, 2 * T // 3]
    xs_o = rng.standard_normal((B, T, D)).astype(np.float32) * 0.3
    xs_r = np.zeros((T, B, D), np.float32)
    for b, s0 in enumerate(starts):
        for t in range(s0, T):
            xs_r[t, b] = xs_o[b, t - s0]
    return starts, jnp.asarray(xs_r), jnp.asarray(xs_o)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("window,s_blk", [(0, 16), (6, 8)])
def test_split_token_ragged_matches_per_sequence(backend, window, s_blk):
    from repro.core import dataflow as df
    D, n_heads, kv_heads, hd, B, T = 32, 2, 1, 16, 3, 12
    rng = np.random.default_rng(0)
    w = df.SplitTokenWeights(
        wq=jnp.asarray(rng.standard_normal((D, n_heads, hd)) * 0.05,
                       jnp.float32),
        wk=jnp.asarray(rng.standard_normal((D, kv_heads, hd)) * 0.05,
                       jnp.float32),
        wv=jnp.asarray(rng.standard_normal((D, kv_heads, hd)) * 0.05,
                       jnp.float32),
        wo=jnp.asarray(rng.standard_normal((n_heads * hd, D)) * 0.05,
                       jnp.float32))
    starts, xs_r, xs_o = _staggered_inputs(rng, T, B, D)
    spec = df.ClusterSpec(heads="model", cluster="model", backend=backend,
                          interpret=True, block_s=2)
    mesh = jax.make_mesh((1,), ("model",))
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    def step(x, cache, cl):
        return df.split_token_attention(spec, x, w, cache, cl,
                                        window=window)

    f = jax.jit(shard_map(step, mesh=mesh, in_specs=(P(), P(), P()),
                          out_specs=(P(), P()), check_vma=False))

    def fresh(b_n, ragged):
        return df.KVBlock(
            k=jnp.zeros((s_blk, b_n * kv_heads, hd), jnp.bfloat16),
            v=jnp.zeros((s_blk, b_n * kv_heads, hd), jnp.bfloat16),
            pos=jnp.full((s_blk, b_n) if ragged else (s_blk,), -1,
                         jnp.int32))

    # ragged run with staggered activation (inactive slots at −1)
    cache = fresh(B, ragged=True)
    cl = jnp.full((B,), -1, jnp.int32)
    outs = []
    for t in range(T):
        act = jnp.asarray([t >= s0 for s0 in starts])
        cl = jnp.where(act & (cl < 0), 0, cl)
        o, cache = f(xs_r[t], cache, cl)
        outs.append(np.asarray(o, np.float32))
        cl = jnp.where(cl >= 0, cl + 1, cl)
    assert int(max(np.asarray(cl))) == T            # longest slot: full T

    # dense per-sequence oracle: scalar cache_len, 1-D pos (legacy path)
    for b, s0 in enumerate(starts):
        cache_b = fresh(1, ragged=False)
        for i in range(T - s0):
            o_b, cache_b = f(xs_o[b, i:i + 1], cache_b, jnp.int32(i))
            np.testing.assert_allclose(
                outs[s0 + i][b], np.asarray(o_b[0], np.float32),
                rtol=2e-2, atol=2e-2,
                err_msg=f"slot {b} step {i} ({backend}, window={window})")


@pytest.mark.parametrize("window", [0, 32])
def test_flash_decode_ragged_vmap_matches_ref(window):
    """Per-slot cache_lens (incl. 0 and full) through a vmapped
    ``flash_decode`` vs the per-sequence reference."""
    from repro.kernels.flash_decode.ops import flash_decode
    B, S, q_loc, kv_loc, hd = 4, 64, 4, 2, 16
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((B, q_loc, hd)) * 0.3, jnp.float32)
    kc = jnp.asarray(rng.standard_normal((S, B, kv_loc, hd)) * 0.3,
                     jnp.float32)
    vc = jnp.asarray(rng.standard_normal((S, B, kv_loc, hd)) * 0.3,
                     jnp.float32)
    lens = jnp.asarray([0, 17, 40, S], jnp.int32)    # 0 and full included

    def one(qb, kb, vb, cl, use_ref):
        return flash_decode(qb[None], kb, vb, cl, window=window,
                            block_s=16, interpret=True, use_ref=use_ref)[0]

    o_rag = jax.vmap(lambda *a: one(*a, False),
                     in_axes=(0, 1, 1, 0))(q, kc, vc, lens)
    for b in range(B):
        if int(lens[b]) == 0:      # empty slot: kernel emits zeros (the
            assert not np.any(np.asarray(o_rag[b]))   # ref softmax NaNs)
            continue
        o_ref = one(q[b], kc[:, b], vc[:, b], lens[b], True)
        np.testing.assert_allclose(np.asarray(o_rag[b]), np.asarray(o_ref),
                                   rtol=3e-5, atol=3e-5, err_msg=f"slot {b}")


# ---------------------------------------------------------------------------
# Cluster {1, 2, 4} sweeps — 8 emulated devices in a subprocess
# ---------------------------------------------------------------------------
@pytest.mark.multidevice
def test_split_token_ragged_cluster_sweep():
    """GQA ragged decode (bias + softcap, global + RING cache past the
    wrap threshold) vs the dense per-sequence lockstep oracle, at
    cluster sizes 1, 2, 4, backends xla + pallas."""
    run_multidevice("""
    from repro.core import dataflow as df
    from repro.core import primitives as prim
    mesh = jax.make_mesh((8,), ("c",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    D, n_heads, kv_heads, hd, B, H = 64, 4, 2, 32, 3, 2
    T, CAP = 12, 20.0
    rng = np.random.default_rng(0)
    WQ = jnp.asarray(rng.standard_normal((D, n_heads, hd)) * 0.05,
                     jnp.float32)
    WK = jnp.asarray(rng.standard_normal((D, kv_heads, hd)) * 0.05,
                     jnp.float32)
    WV = jnp.asarray(rng.standard_normal((D, kv_heads, hd)) * 0.05,
                     jnp.float32)
    BQ = jnp.asarray(rng.standard_normal((n_heads, hd)) * 0.02, jnp.float32)
    BK = jnp.asarray(rng.standard_normal((kv_heads, hd)) * 0.02, jnp.float32)
    BV = jnp.asarray(rng.standard_normal((kv_heads, hd)) * 0.02, jnp.float32)
    WO = jnp.asarray(rng.standard_normal((n_heads * hd, D)) * 0.05,
                     jnp.float32)
    starts = [0, 4, 8]
    XS_O = rng.standard_normal((B, T, D)).astype(np.float32) * 0.3
    XS_R = np.zeros((T, B, D), np.float32)
    for b, s0 in enumerate(starts):
        XS_R[s0:, b] = XS_O[b, :T - s0]
    XS_R, XS_O = jnp.asarray(XS_R), jnp.asarray(XS_O)
    q_loc, kv_loc = n_heads // H, kv_heads // H

    for N in (1, 2, 4):
        heads = prim.SubAxis("c", H, minor_size=N)
        clus = prim.SubAxis("c", N, minor_size=1)
        hd_n = hd // N

        def body(xs_r, xs_o, WQ, WK, WV, BQ, BK, BV, WO):
            h = prim.axis_index(heads)
            c = prim.axis_index(clus)
            dsl = jax.lax.dynamic_slice_in_dim
            sl_h = lambda a: dsl(a, h * (a.shape[-2] // H),
                                 a.shape[-2] // H, axis=-2)
            sl_c = lambda a: dsl(a, c * hd_n, hd_n, axis=-1)
            w = df.SplitTokenWeights(
                wq=sl_c(sl_h(WQ)), wk=sl_c(sl_h(WK)), wv=sl_c(sl_h(WV)),
                wo=dsl(dsl(WO, h * q_loc * hd, q_loc * hd, axis=0),
                       c * (D // N), D // N, axis=1),
                bq=sl_c(sl_h(BQ)), bk=sl_c(sl_h(BK)), bv=sl_c(sl_h(BV)))
            specs = {
                "xla": df.ClusterSpec(heads=heads, cluster=clus,
                                      backend="xla", block_s=2),
                "pallas": df.ClusterSpec(heads=heads, cluster=clus,
                                         backend="pallas", interpret=True,
                                         block_s=2)}
            rag_all, orc_all = [], []
            # T > window + shard: the ring wraps during the sweep; slot 0
            # reaches the FULL global cache (T == s_cap) by the last step
            for window, s_cap in ((0, 12), (8, 8)):
                s_blk = s_cap // N
                # ragged staggered runs, both backends
                for bk in ("xla", "pallas"):
                    cache = df.KVBlock(
                        k=jnp.zeros((s_blk, B * kv_loc, hd), jnp.bfloat16),
                        v=jnp.zeros((s_blk, B * kv_loc, hd), jnp.bfloat16),
                        pos=jnp.full((s_blk, B), -1, jnp.int32))
                    cl = jnp.full((B,), -1, jnp.int32)
                    o_r = []
                    for t in range(T):
                        act = jnp.asarray([t >= s0 for s0 in starts])
                        cl = jnp.where(act & (cl < 0), 0, cl)
                        o, cache = df.split_token_attention(
                            specs[bk], xs_r[t], w, cache, cl,
                            window=window, attn_softcap=CAP)
                        o_r.append(prim.cluster_gather_tiled(o, clus,
                                                             axis=1))
                        cl = jnp.where(cl >= 0, cl + 1, cl)
                    rag_all.append(jnp.stack(o_r))
                # dense per-sequence lockstep oracle, ONCE (scalar-path
                # xla — backend-independent ground truth)
                o_o = []
                for b in range(B):
                    cache_b = df.KVBlock(
                        k=jnp.zeros((s_blk, kv_loc, hd), jnp.bfloat16),
                        v=jnp.zeros((s_blk, kv_loc, hd), jnp.bfloat16),
                        pos=jnp.full((s_blk,), -1, jnp.int32))
                    per = []
                    for i in range(T):
                        ob, cache_b = df.split_token_attention(
                            specs["xla"], xs_o[b, i:i + 1], w, cache_b,
                            jnp.int32(i), window=window,
                            attn_softcap=CAP)
                        per.append(prim.cluster_gather_tiled(
                            ob, clus, axis=1)[0])
                    o_o.append(jnp.stack(per))
                orc_all.append(jnp.stack(o_o))
            # rag_all: 4 × [T, B, D] (2 cache kinds × 2 backends);
            # orc_all: 2 × [B, T, D] (per cache kind)
            return jnp.stack(rag_all)[None], jnp.stack(orc_all)[None]

        rag, orc = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(),) * 9,
            out_specs=(P("c"), P("c")), check_vma=False))(
            XS_R, XS_O, WQ, WK, WV, BQ, BK, BV, WO)
        rag = np.asarray(rag, np.float32)   # [8, 4, T, B, D]
        orc = np.asarray(orc, np.float32)   # [8, 2, B, T, D]
        for ci in range(4):                 # (kind, backend) pairs
            for b, s0 in enumerate(starts):
                got = rag[:, ci, s0:, b]
                want = orc[:, ci // 2, b, :T - s0]
                err = np.abs(got - want).max()
                assert err <= 2e-2, (N, ci, b, err)
        print("RAGGED GQA OK N =", N)
    """, timeout=1800)


@pytest.mark.multidevice
def test_mla_ragged_cluster_sweep():
    """MLA ragged decode vs the dense per-sequence oracle at cluster
    sizes 1, 2, 4, backends xla + pallas."""
    run_multidevice("""
    from repro.core import dataflow as df
    from repro.core import primitives as prim
    mesh = jax.make_mesh((8,), ("c",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    D, q_heads, nope, rope, l_rank, v_dim = 64, 4, 16, 8, 32, 16
    B, H, T = 3, 2, 10
    q_loc = q_heads // H
    nr = nope + rope
    rng = np.random.default_rng(2)
    WQ = jnp.asarray(rng.standard_normal((D, q_heads, nr)) * 0.05,
                     jnp.float32)
    WDKV = jnp.asarray(rng.standard_normal((D, l_rank + rope)) * 0.05,
                       jnp.float32)
    WUK = jnp.asarray(rng.standard_normal((q_heads, nope, l_rank)) * 0.05,
                      jnp.float32)
    WUV = jnp.asarray(rng.standard_normal((q_heads, l_rank, v_dim)) * 0.05,
                      jnp.float32)
    WO = jnp.asarray(rng.standard_normal((q_heads * v_dim, D)) * 0.05,
                     jnp.float32)
    starts = [0, 3, 7]
    XS_O = rng.standard_normal((B, T, D)).astype(np.float32) * 0.3
    XS_R = np.zeros((T, B, D), np.float32)
    for b, s0 in enumerate(starts):
        XS_R[s0:, b] = XS_O[b, :T - s0]
    XS_R, XS_O = jnp.asarray(XS_R), jnp.asarray(XS_O)

    for N in (1, 2, 4):
        heads = prim.SubAxis("c", H, minor_size=N)
        clus = prim.SubAxis("c", N, minor_size=1)
        s_blk = 16 // N

        def body(xs_r, xs_o, WQ, WDKV, WUK, WUV, WO):
            h = prim.axis_index(heads)
            c = prim.axis_index(clus)
            dsl = jax.lax.dynamic_slice_in_dim
            wq_h = dsl(WQ, h * q_loc, q_loc, axis=1)
            wuk_h = dsl(WUK, h * q_loc, q_loc, axis=0)
            wuv_h = dsl(WUV, h * q_loc, q_loc, axis=0)
            wo_h = dsl(WO, h * q_loc * v_dim, q_loc * v_dim, axis=0)
            w = df.MLAWeights(
                wq=dsl(wq_h, c * (nr // N), nr // N, axis=2),
                wdkv=dsl(WDKV, c * ((l_rank + rope) // N),
                         (l_rank + rope) // N, axis=1),
                wuk=dsl(wuk_h, c * (l_rank // N), l_rank // N, axis=2),
                wuv=dsl(wuv_h, c * (l_rank // N), l_rank // N, axis=1),
                wo=dsl(wo_h, c * (D // N), D // N, axis=1))
            specs = {
                "xla": df.ClusterSpec(heads=heads, cluster=clus,
                                      backend="xla", block_s=2),
                "pallas": df.ClusterSpec(heads=heads, cluster=clus,
                                         backend="pallas", interpret=True,
                                         block_s=2)}
            outs = []
            for bk in ("xla", "pallas"):
                cache = df.KVBlock(
                    k=jnp.zeros((s_blk, B, l_rank + rope), jnp.bfloat16),
                    v=jnp.zeros((s_blk, B, 1), jnp.bfloat16),
                    pos=jnp.full((s_blk, B), -1, jnp.int32))
                cl = jnp.full((B,), -1, jnp.int32)
                o_r = []
                for t in range(T):
                    act = jnp.asarray([t >= s0 for s0 in starts])
                    cl = jnp.where(act & (cl < 0), 0, cl)
                    o, cache = df.mla_attention(
                        specs[bk], xs_r[t], w, cache, cl,
                        nope_dim=nope, rope_dim=rope)
                    o_r.append(prim.cluster_gather_tiled(o, clus, axis=1))
                    cl = jnp.where(cl >= 0, cl + 1, cl)
                o_o = []
                for b in range(B):
                    cache_b = df.KVBlock(
                        k=jnp.zeros((s_blk, 1, l_rank + rope),
                                    jnp.bfloat16),
                        v=jnp.zeros((s_blk, 1, 1), jnp.bfloat16),
                        pos=jnp.full((s_blk,), -1, jnp.int32))
                    per = []
                    for i in range(T):
                        ob, cache_b = df.mla_attention(
                            specs[bk], xs_o[b, i:i + 1], w, cache_b,
                            jnp.int32(i), nope_dim=nope, rope_dim=rope)
                        per.append(prim.cluster_gather_tiled(
                            ob, clus, axis=1)[0])
                    o_o.append(jnp.stack(per))
                outs.append((jnp.stack(o_r), jnp.stack(o_o)))
            return (jnp.stack([a for a, _ in outs])[None],
                    jnp.stack([o for _, o in outs])[None])

        rag, orc = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(),) * 7,
            out_specs=(P("c"), P("c")), check_vma=False))(
            XS_R, XS_O, WQ, WDKV, WUK, WUV, WO)
        rag = np.asarray(rag, np.float32)   # [8, 2, T, B, D]
        orc = np.asarray(orc, np.float32)   # [8, 2, B, T, D]
        for ci in range(2):
            for b, s0 in enumerate(starts):
                err = np.abs(rag[:, ci, s0:, b]
                             - orc[:, ci, b, :T - s0]).max()
                assert err <= 2e-2, (N, ci, b, err)
        print("RAGGED MLA OK N =", N)
    """, timeout=1800)


@pytest.mark.multidevice
def test_flash_decode_ragged_cluster_shards():
    """flash_decode over cluster-sharded caches: each rank runs the
    vmapped ragged kernel on its sequence shard with rank-local per-slot
    live spans and must match the per-sequence reference on that shard,
    at cluster sizes 1, 2, 4."""
    run_multidevice("""
    from repro.kernels.flash_decode.ops import flash_decode
    mesh = jax.make_mesh((8,), ("c",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    B, S, q_loc, kv_loc, hd = 3, 32, 2, 1, 16
    rng = np.random.default_rng(3)
    Q = jnp.asarray(rng.standard_normal((B, q_loc, hd)) * 0.3, jnp.float32)
    KC = jnp.asarray(rng.standard_normal((S, B, kv_loc, hd)) * 0.3,
                     jnp.float32)
    VC = jnp.asarray(rng.standard_normal((S, B, kv_loc, hd)) * 0.3,
                     jnp.float32)
    LENS = jnp.asarray([0, 13, S], jnp.int32)

    for N in (1, 2, 4):
        s_blk = S // N

        def body(q, kc, vc, lens):
            rank = jax.lax.axis_index("c") % N
            shard_k = jax.lax.dynamic_slice_in_dim(kc, rank * s_blk,
                                                   s_blk, axis=0)
            shard_v = jax.lax.dynamic_slice_in_dim(vc, rank * s_blk,
                                                   s_blk, axis=0)
            eff = jnp.clip(lens - rank * s_blk, 0, s_blk)

            def one(qb, kb, vb, cl, use_ref):
                return flash_decode(qb[None], kb, vb, cl, block_s=8,
                                    interpret=True, use_ref=use_ref)[0]

            o_rag = jax.vmap(lambda *a: one(*a, False),
                             in_axes=(0, 1, 1, 0))(q, shard_k, shard_v,
                                                   eff)
            o_ref = jnp.stack([one(q[b], shard_k[:, b], shard_v[:, b],
                                   eff[b], True) for b in range(B)])
            return o_rag[None], o_ref[None]

        o_rag, o_ref = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(),) * 4,
            out_specs=(P("c"), P("c")), check_vma=False))(Q, KC, VC, LENS)
        o_rag, o_ref = np.asarray(o_rag), np.asarray(o_ref)
        assert np.isfinite(o_rag).all(), N   # empty shards emit 0, not NaN
        # the ref softmax NaNs on empty rank-local spans where the kernel
        # correctly emits zeros — normalize before comparing
        err = np.abs(o_rag - np.nan_to_num(o_ref)).max()
        assert err <= 3e-5, (N, err)
        print("RAGGED FLASH OK N =", N, err)
    """, timeout=1200)
