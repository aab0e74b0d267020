"""Backend dispatch parity: ``backend="pallas"`` (interpret mode on CPU)
must match ``backend="xla"`` decode outputs — dataflow-level to ≤1e-2
(bf16 caches), and engine-level greedy tokens exactly — for a GQA config
(bias + softcap + sliding-window ring cache) and an MLA config."""
import pytest

from helpers import run_multidevice

pytestmark = pytest.mark.multidevice


def test_split_token_backend_parity_gqa_window():
    # heads 2 × cluster 4 over an 8-device axis; 6 sequential decode steps
    # through a FULL cache and a sliding-window RING cache, both backends.
    run_multidevice("""
    from repro.core import dataflow as df
    from repro.core import primitives as prim
    mesh = jax.make_mesh((8,), ("c",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    heads = prim.SubAxis("c", 2, minor_size=4)
    clus = prim.SubAxis("c", 4, minor_size=1)
    D, n_heads, kv_heads, hd, B, N, H = 64, 4, 2, 32, 2, 4, 2
    q_loc, kv_loc, hd_n = n_heads // H, kv_heads // H, hd // N
    # T > window + s_blk: the ring wraps AND cache_len passes the point
    # where a local-offset window cull would (wrongly) kill every block
    T, CAP = 14, 20.0
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 9)
    WQ = jax.random.normal(ks[0], (D, n_heads, hd)) * 0.05
    WK = jax.random.normal(ks[1], (D, kv_heads, hd)) * 0.05
    WV = jax.random.normal(ks[2], (D, kv_heads, hd)) * 0.05
    BQ = jax.random.normal(ks[3], (n_heads, hd)) * 0.02
    BK = jax.random.normal(ks[4], (kv_heads, hd)) * 0.02
    BV = jax.random.normal(ks[5], (kv_heads, hd)) * 0.02
    WO = jax.random.normal(ks[6], (n_heads * hd, D)) * 0.05
    XS = jax.random.normal(ks[7], (T, B, D)) * 0.3

    def body(xs, WQ, WK, WV, BQ, BK, BV, WO):
        h = prim.axis_index(heads)
        c = prim.axis_index(clus)
        sl_h = lambda a: jax.lax.dynamic_slice_in_dim(
            a, h * (a.shape[-2] // H), a.shape[-2] // H, axis=-2)
        sl_c = lambda a: jax.lax.dynamic_slice_in_dim(
            a, c * hd_n, hd_n, axis=-1)
        w = df.SplitTokenWeights(
            wq=sl_c(sl_h(WQ)), wk=sl_c(sl_h(WK)), wv=sl_c(sl_h(WV)),
            wo=jax.lax.dynamic_slice_in_dim(
                jax.lax.dynamic_slice_in_dim(
                    WO, h * q_loc * hd, q_loc * hd, axis=0),
                c * (D // N), D // N, axis=1),
            bq=sl_c(sl_h(BQ)), bk=sl_c(sl_h(BK)), bv=sl_c(sl_h(BV)))
        outs = []
        for window, s_blk in ((0, 4), (8, 2)):   # full cache + ring cache
            spec_x = df.ClusterSpec(heads=heads, cluster=clus,
                                    backend="xla", block_s=2)
            spec_p = df.ClusterSpec(heads=heads, cluster=clus,
                                    backend="pallas", interpret=True,
                                    block_s=2)
            caches = [df.KVBlock(
                k=jnp.zeros((s_blk, B * kv_loc, hd), jnp.bfloat16),
                v=jnp.zeros((s_blk, B * kv_loc, hd), jnp.bfloat16),
                pos=jnp.full((s_blk,), -1, jnp.int32)) for _ in range(2)]
            for t in range(T):
                o_x, caches[0] = df.split_token_attention(
                    spec_x, xs[t], w, caches[0], jnp.int32(t),
                    window=window, attn_softcap=CAP)
                o_p, caches[1] = df.split_token_attention(
                    spec_p, xs[t], w, caches[1], jnp.int32(t),
                    window=window, attn_softcap=CAP)
                outs.append(jnp.stack([o_x, o_p]))
        return jnp.stack(outs)[None]          # [1, 2T, 2, B, D/N]

    out = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(), P(), P()),
        out_specs=P("c"), check_vma=False))(
        XS, WQ, WK, WV, BQ, BK, BV, WO)
    out = np.asarray(out, np.float32)         # [8, 2T, 2, B, D/N]
    err = np.abs(out[:, :, 0] - out[:, :, 1]).max()
    assert err <= 1e-2, err
    print("SPLIT-TOKEN PARITY OK", err)
    """)


def test_mla_backend_parity():
    run_multidevice("""
    from repro.core import dataflow as df
    from repro.core import primitives as prim
    mesh = jax.make_mesh((8,), ("c",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    heads = prim.SubAxis("c", 2, minor_size=4)
    clus = prim.SubAxis("c", 4, minor_size=1)
    D, q_heads, nope, rope, l_rank, v_dim = 64, 4, 16, 8, 32, 16
    B, N, H, T = 2, 4, 2, 6
    q_loc = q_heads // H
    nr = nope + rope
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 7)
    WQ = jax.random.normal(ks[0], (D, q_heads, nr)) * 0.05
    WDKV = jax.random.normal(ks[1], (D, l_rank + rope)) * 0.05
    WUK = jax.random.normal(ks[2], (q_heads, nope, l_rank)) * 0.05
    WUV = jax.random.normal(ks[3], (q_heads, l_rank, v_dim)) * 0.05
    WO = jax.random.normal(ks[4], (q_heads * v_dim, D)) * 0.05
    XS = jax.random.normal(ks[5], (T, B, D)) * 0.3
    s_blk = 2                                  # 4 ranks × 2 slots = 8 ≥ T

    def body(xs, WQ, WDKV, WUK, WUV, WO):
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
        spec_x = df.ClusterSpec(heads=heads, cluster=clus,
                                backend="xla", block_s=2)
        spec_p = df.ClusterSpec(heads=heads, cluster=clus,
                                backend="pallas", interpret=True, block_s=2)
        caches = [df.KVBlock(
            k=jnp.zeros((s_blk, B, l_rank + rope), jnp.bfloat16),
            v=jnp.zeros((s_blk, B, 1), jnp.bfloat16),
            pos=jnp.full((s_blk,), -1, jnp.int32)) for _ in range(2)]
        outs = []
        for t in range(T):
            o_x, caches[0] = df.mla_attention(
                spec_x, xs[t], w, caches[0], jnp.int32(t),
                nope_dim=nope, rope_dim=rope)
            o_p, caches[1] = df.mla_attention(
                spec_p, xs[t], w, caches[1], jnp.int32(t),
                nope_dim=nope, rope_dim=rope)
            outs.append(jnp.stack([o_x, o_p]))
        return jnp.stack(outs)[None]

    out = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(),) * 6,
        out_specs=P("c"), check_vma=False))(XS, WQ, WDKV, WUK, WUV, WO)
    out = np.asarray(out, np.float32)
    err = np.abs(out[:, :, 0] - out[:, :, 1]).max()
    assert err <= 1e-2, err
    print("MLA PARITY OK", err)
    """)


def test_prepacked_vs_adapter_parity_gqa():
    """Prepacked serve layout (fully fused partial_o Pallas path) vs the
    train-layout XLA adapter path: identical outputs over sequential
    decode steps through a FULL cache and a sliding-window RING cache,
    with GQA bias + softcap, at cluster sizes 1, 2 and 4."""
    run_multidevice("""
    from repro.core import dataflow as df
    from repro.core import primitives as prim
    mesh = jax.make_mesh((8,), ("c",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    D, n_heads, kv_heads, hd, B = 64, 4, 2, 32, 2
    H = 2                                        # head-groups
    T, CAP = 14, 20.0
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 9)
    WQ = jax.random.normal(ks[0], (D, n_heads, hd)) * 0.05
    WK = jax.random.normal(ks[1], (D, kv_heads, hd)) * 0.05
    WV = jax.random.normal(ks[2], (D, kv_heads, hd)) * 0.05
    BQ = jax.random.normal(ks[3], (n_heads, hd)) * 0.02
    BK = jax.random.normal(ks[4], (kv_heads, hd)) * 0.02
    BV = jax.random.normal(ks[5], (kv_heads, hd)) * 0.02
    WO = jax.random.normal(ks[6], (n_heads * hd, D)) * 0.05
    XS = jax.random.normal(ks[7], (T, B, D)) * 0.3
    q_loc, kv_loc = n_heads // H, kv_heads // H

    for N in (1, 2, 4):
        heads = prim.SubAxis("c", H, minor_size=N)
        clus = prim.SubAxis("c", N, minor_size=1)
        hd_n = hd // N

        def body(xs, WQ, WK, WV, BQ, BK, BV, WO):
            h = prim.axis_index(heads)
            dsl = jax.lax.dynamic_slice_in_dim
            c = prim.axis_index(clus)
            sl_h = lambda a: dsl(a, h * (a.shape[-2] // H),
                                 a.shape[-2] // H, axis=-2)
            sl_c = lambda a: dsl(a, c * hd_n, hd_n, axis=-1)
            # train-layout adapter weights (per-step slicing, XLA path)
            w_x = df.SplitTokenWeights(
                wq=sl_c(sl_h(WQ)), wk=sl_c(sl_h(WK)), wv=sl_c(sl_h(WV)),
                wo=dsl(dsl(WO, h * q_loc * hd, q_loc * hd, axis=0),
                       c * (D // N), D // N, axis=1),
                bq=sl_c(sl_h(BQ)), bk=sl_c(sl_h(BK)), bv=sl_c(sl_h(BV)))
            # serve-layout prepack: gathered wqkv + fused bias + per-head
            # full-width wo rows (what serving/prepack.py materializes)
            flat = lambda a: sl_h(a).reshape(D, -1)
            wqkv = jnp.concatenate([flat(WQ), flat(WK), flat(WV)], axis=1)
            bflat = lambda a: sl_h(a[None])[0].reshape(-1)
            bqkv = jnp.concatenate([bflat(BQ), bflat(BK), bflat(BV)])
            wo3 = dsl(WO, h * q_loc * hd, q_loc * hd,
                      axis=0).reshape(q_loc, hd, D)
            w_p = df.PackedSplitTokenWeights(wqkv=wqkv, wo=wo3, bqkv=bqkv)

            spec_x = df.ClusterSpec(heads=heads, cluster=clus,
                                    backend="xla", block_s=2)
            spec_p = df.ClusterSpec(heads=heads, cluster=clus,
                                    backend="pallas", interpret=True,
                                    block_s=2)
            outs = []
            for window, s_cap in ((0, 16), (8, 8)):  # full + ring cache
                s_blk = s_cap // N
                caches = [df.KVBlock(
                    k=jnp.zeros((s_blk, B * kv_loc, hd), jnp.bfloat16),
                    v=jnp.zeros((s_blk, B * kv_loc, hd), jnp.bfloat16),
                    pos=jnp.full((s_blk,), -1, jnp.int32))
                    for _ in range(2)]
                for t in range(T):
                    o_x, caches[0] = df.split_token_attention(
                        spec_x, xs[t], w_x, caches[0], jnp.int32(t),
                        window=window, attn_softcap=CAP)
                    o_p, caches[1] = df.split_token_attention(
                        spec_p, xs[t], w_p, caches[1], jnp.int32(t),
                        window=window, attn_softcap=CAP)
                    # adapter output is cluster-tiled; packed is full [B, D]
                    o_xf = prim.cluster_gather_tiled(o_x, clus, axis=1)
                    outs.append(jnp.stack([o_xf, o_p]))
            return jnp.stack(outs)[None]          # [1, 2T, 2, B, D]

        out = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P(),) * 8,
            out_specs=P("c"), check_vma=False))(
            XS, WQ, WK, WV, BQ, BK, BV, WO)
        out = np.asarray(out, np.float32)
        err = np.abs(out[:, :, 0] - out[:, :, 1]).max()
        assert err <= 1e-2, (N, err)
        print("PREPACK PARITY OK N =", N, "err", err)
    """, timeout=1500)


def test_engine_backend_parity_tokens():
    """Full engine: greedy tokens agree between backends (GQA with
    sliding window + softcap, and MLA), pallas in interpret mode with
    the serve-layout prepack auto-enabled.  The GQA model has five
    layers: two scanned groups of its two-layer pattern, whose fused FFN
    kernel reads the ``[L, …]`` weight stacks by layer index (one
    ``weights_by_index`` per scanned layer position), and one unstacked
    tail layer that passes its own weights."""
    run_multidevice("""
    from repro.configs import get_config, reduced
    from repro.core import tracecount
    from repro.launch.mesh import make_test_mesh
    from repro.launch.serve import build_engine, generate
    for arch, n_layers in (("gemma2-27b", 5), ("deepseek-v2-lite", 0)):
        cfg = reduced(get_config(arch), n_layers=n_layers)
        mesh = make_test_mesh()
        outs = {}
        for backend in ("xla", "pallas"):
            params, pf, dec, state, lay, scfg = build_engine(
                cfg, mesh, max_seq=48, batch_global=4, backend=backend,
                interpret=(backend == "pallas"))
            # prepack rides the auto default: on exactly for pallas
            assert scfg.prepack == (backend == "pallas"), scfg
            if backend == "pallas" and arch == "gemma2-27b":
                period = len(cfg.block_pattern)
                n_tail = cfg.n_layers % period
                assert n_tail == 1 and cfg.n_layers // period == 2, cfg
                with tracecount.counting() as c:
                    jax.eval_shape(dec, params["serve"], state,
                                   jnp.zeros((4,), jnp.int32))
                c = dict(c)
                # two kernels per traced layer position (scanned and
                # tail) and the head; only the scanned FFNs by index
                assert c.get("pallas_kernel") == 2 * (period + n_tail) + 1, c
                assert c.get("weights_by_index") == period, c
            key = jax.random.PRNGKey(0)
            prompts = jax.random.randint(key, (4, 16), 0, cfg.vocab_size)
            toks, _ = generate(cfg, params, pf, dec, state, prompts, 5,
                               None)
            outs[backend] = np.asarray(toks)
        agree = (outs["xla"] == outs["pallas"]).mean()
        assert agree >= 0.95, (arch, agree, outs)
        print("ENGINE PARITY OK", arch, agree)
    """, timeout=1500)


def test_engine_prepack_parity_mla_cluster():
    """MLA engine at forced cluster sizes 2 and 4: prepacked Pallas
    (with the W_UV·W_O fold) matches the XLA adapter path
    token-for-token — and, at cluster 2, the non-prepacked Pallas
    path."""
    run_multidevice("""
    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_test_mesh
    from repro.launch.serve import build_engine, generate
    cfg = reduced(get_config("deepseek-v2-lite"))
    mesh = make_test_mesh()

    def run(cluster, **kw):
        params, pf, dec, state, lay, scfg = build_engine(
            cfg, mesh, max_seq=48, batch_global=4, cluster=cluster, **kw)
        key = jax.random.PRNGKey(0)
        prompts = jax.random.randint(key, (4, 16), 0, cfg.vocab_size)
        toks, _ = generate(cfg, params, pf, dec, state, prompts, 5, None)
        return np.asarray(toks), scfg

    for n in (2, 4):
        t_x, _ = run(n, backend="xla")
        t_p, scfg = run(n, backend="pallas", interpret=True)
        assert scfg.prepack, scfg
        agree = (t_x == t_p).mean()
        assert agree >= 0.95, (n, agree)
        print("MLA PREPACK ENGINE PARITY OK N =", n, agree)
    t_np, scfg = run(2, backend="pallas", interpret=True, prepack="off")
    assert not scfg.prepack, scfg
    t_p, _ = run(2, backend="pallas", interpret=True)
    agree = (t_np == t_p).mean()
    assert agree >= 0.95, agree
    print("MLA PREPACK-VS-ADAPTER OK", agree)
    """, timeout=1500)
