"""Serve-layout weight prepacking (serving/prepack.py, DESIGN.md §2).

* Layout math: the load-time pack equals what the runtime adapters
  gathered/sliced per step.
* Trace-time op counts: the prepacked Pallas dataflow performs ZERO
  per-step weight-segment gathers and ZERO weight ``dynamic_slice``s,
  and issues exactly ONE Pallas kernel + ONE fused ClusterReduce per
  attention layer; the engine-level decode step shows zero weight
  movement end-to-end.
* Derived state: checkpoints round-trip training-layout weights
  untouched ({"train","serve"} pairs are stripped to "train"), and the
  serve layout re-derives bit-identically after restore.
* Autotune: ``ServePlan.prepack`` resolution + schema self-heal for
  pre-prepack table entries.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import run_multidevice


# ---------------------------------------------------------------------------
# Layout math (single process — the pack is pure reshape/slice)
# ---------------------------------------------------------------------------
def test_gather_seg_matches_runtime_gather():
    from repro.serving.prepack import _gather_seg
    hs, n, D, q, hd_n = 2, 4, 6, 3, 4
    ms = hs * n
    wq = jax.random.normal(jax.random.PRNGKey(0), (ms, D, q, hd_n))
    g = _gather_seg(wq, hs, n, 3)
    assert g.shape == (ms, D, q, hd_n * n)
    for h in range(hs):
        for c in range(n):
            r = h * n + c
            # runtime: cluster_gather_tiled concats segment of rank
            # (h, c') at offset c' — every rank of the group agrees
            want = np.concatenate(
                [np.asarray(wq[h * n + cc]) for cc in range(n)], axis=-1)
            np.testing.assert_array_equal(np.asarray(g[r]), want)


def test_col_tile_matches_runtime_slice():
    from repro.serving.prepack import _col_tile
    hs, n, R, D = 2, 2, 8, 12
    ms = hs * n
    wo = jax.random.normal(jax.random.PRNGKey(1), (ms, R, D))
    t = _col_tile(wo, hs, n, 2)
    assert t.shape == (ms, R, D // n)
    for h in range(hs):
        for c in range(n):
            r = h * n + c
            want = np.asarray(wo[r])[:, c * (D // n):(c + 1) * (D // n)]
            np.testing.assert_array_equal(np.asarray(t[r]), want)


def _small_gqa_setup(cluster=2):
    from repro.configs import get_config, reduced
    from repro.models.transformer import Layout, init_device_major
    cfg = reduced(get_config("qwen2-72b"))           # GQA with qkv bias
    ms = 4
    lay = Layout(ms, heads_sub=ms // cluster)
    params = init_device_major(cfg, lay, jax.random.PRNGKey(0))
    return cfg, lay, params


def test_prepack_tree_gqa_shapes_and_passthrough():
    from repro.core.dataflow import (PackedFFNWeights,
                                     PackedSplitTokenWeights,
                                     SplitTokenWeights)
    from repro.serving.prepack import prepack_for_serving
    cfg, lay, params = _small_gqa_setup(cluster=2)
    ms, n = lay.model_size, lay.cluster
    hd = cfg.resolved_head_dim
    q_loc = cfg.n_heads // lay.heads_sub
    kv_loc = max(1, cfg.n_kv_heads // lay.heads_sub)

    packed = prepack_for_serving(cfg, lay, params, backend="pallas")
    a = packed["blocks"][0]["attn"]
    assert isinstance(a, PackedSplitTokenWeights)
    G = params["blocks"][0]["ln1"].shape[1]          # stacked group dim
    assert a.wqkv.shape == (ms, G, cfg.d_model, (q_loc + 2 * kv_loc) * hd)
    assert a.wo.shape == (ms, G, q_loc, hd, cfg.d_model)
    assert a.bqkv.shape == (ms, G, (q_loc + 2 * kv_loc) * hd)
    # the pre-attention norm scale rides the pack (fused in-kernel norm)
    assert a.ln1.shape == (ms, G, cfg.d_model)
    # non-attention leaves ride through untouched (same objects)
    assert packed["embed"] is params["embed"]
    # dense FFN: the bundle is PURE aliasing — every weight field IS the
    # training tree's buffer (full-width down rows are already the serve
    # layout), only the fused norm scales are bound alongside
    pf = packed["blocks"][0]["ffn"]
    tf = params["blocks"][0]["ffn"]
    assert isinstance(pf, PackedFFNWeights)
    assert pf.w_in is tf.w_in and pf.w_out is tf.w_out
    assert pf.w_gate is tf.w_gate
    assert pf.ln2 is params["blocks"][0]["ln2"]

    # xla serve layout: plain dataflow weights with the wo tile pre-sliced
    packed_x = prepack_for_serving(cfg, lay, params, backend="xla")
    ax = packed_x["blocks"][0]["attn"]
    assert isinstance(ax, SplitTokenWeights)
    assert ax.wo.shape == (ms, G, q_loc * hd, cfg.d_model // n)
    assert ax.wq is params["blocks"][0]["attn"].wq
    # the xla path keeps the unfused FFN
    assert packed_x["blocks"][0]["ffn"] is params["blocks"][0]["ffn"]


def test_prepack_mla_fold_matches_manual():
    """wproj = W_UV · W_O rows, per head, per rank — checked against a
    manual einsum on every rank."""
    from repro.configs import get_config, reduced
    from repro.core.dataflow import PackedMLAWeights
    from repro.models.transformer import Layout, init_device_major
    from repro.serving.prepack import prepack_for_serving
    cfg = reduced(get_config("deepseek-v2-lite"))
    ms = 4
    lay = Layout(ms, heads_sub=2)                    # cluster 2
    params = init_device_major(cfg, lay, jax.random.PRNGKey(0))
    packed = prepack_for_serving(cfg, lay, params, backend="pallas")
    a_t = params["blocks"][0]["attn"]
    a_p = packed["blocks"][0]["attn"]
    assert isinstance(a_p, PackedMLAWeights)
    v = cfg.mla.v_head_dim
    q_loc = a_t.wuk.shape[2]
    for r in range(ms):
        wuv = np.asarray(a_t.wuv[r, 0], np.float32)  # [q, l, v]
        wo = np.asarray(a_t.wo[r, 0], np.float32)    # [q*v, D]
        want = np.einsum("qlv,qvd->qld", wuv,
                         wo.reshape(q_loc, v, -1))
        got = np.asarray(a_p.wproj[r, 0], np.float32)
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# Trace-time op counts: zero weight movement, one kernel + one ClusterReduce
# ---------------------------------------------------------------------------
@pytest.mark.multidevice
def test_counters_dataflow_packed_vs_adapter():
    """One prepacked attention layer traces one kernel, one fused
    ClusterReduce and no weight gather or ``weight_slice``; the adapter
    path pays both.

    ``weight_slice`` counts the per-layer rank slicing of the train-layout
    adapters, which prepacking removed; it reads 0 on the prepacked path
    whatever the decode scan does with the stacked weights.  The scan's
    own per-layer weight copies are a different thing: the FFN's went
    when the fused FFN kernel began to read the ``[L, …]`` stacks by
    layer index, which ``weights_by_index`` counts
    (tests/test_backend_parity.py)."""
    run_multidevice("""
    from repro.core import dataflow as df
    from repro.core import primitives as prim
    from repro.core import tracecount
    from repro.serving.engine import _split_token_weights
    from repro.models.ctx import ParallelCtx

    mesh = jax.make_mesh((8,), ("c",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    heads = prim.SubAxis("c", 2, minor_size=4)
    clus = prim.SubAxis("c", 4, minor_size=1)
    D, n_heads, kv_heads, hd, B, N, H = 64, 4, 2, 32, 2, 4, 2
    q_loc, kv_loc = n_heads // H, kv_heads // H
    s_blk = 4
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    X = jax.random.normal(ks[0], (B, D)) * 0.3
    WQKV = jax.random.normal(ks[1], (D, (q_loc + 2 * kv_loc) * hd)) * 0.05
    WO3 = jax.random.normal(ks[2], (q_loc, hd, D)) * 0.05
    cache = df.KVBlock(k=jnp.zeros((s_blk, B * kv_loc, hd), jnp.bfloat16),
                       v=jnp.zeros((s_blk, B * kv_loc, hd), jnp.bfloat16),
                       pos=jnp.full((s_blk,), -1, jnp.int32))

    spec_p = df.ClusterSpec(heads=heads, cluster=clus, backend="pallas",
                            interpret=True, block_s=2)

    def body_packed(x, wqkv, wo3, k, v, pos):
        w = df.PackedSplitTokenWeights(wqkv=wqkv, wo=wo3, bqkv=None)
        o, nc = df.split_token_attention(
            spec_p, x, w, df.KVBlock(k, v, pos), jnp.int32(3))
        return o[None]

    args = (X, WQKV, WO3, cache.k, cache.v, cache.pos)
    sm = shard_map(body_packed, mesh=mesh, in_specs=(P(),) * 6,
                   out_specs=P("c"), check_vma=False)
    with tracecount.counting() as c:
        jax.eval_shape(sm, *args)
    c = dict(c)
    # prepacked: ONE kernel + ONE fused ClusterReduce (the heads-axis
    # atomicAdd reduction is the only other collective); ZERO weight
    # gathers, ZERO weight slices, ZERO gathers of any kind.
    assert c.get("pallas_kernel") == 1, c
    assert c.get("cluster_combine") == 1, c
    assert c.get("tree_reduce") == 2, c      # fused combine + heads reduce
    assert c.get("tree_gather", 0) == 0, c
    assert c.get("weight_gather", 0) == 0, c
    assert c.get("weight_slice", 0) == 0, c
    print("PACKED COUNTS OK", c)

    # adapter (train-layout) Pallas path for comparison: pays 3 weight
    # gathers per step and a per-layer weight slice in the adapter.
    WQ = jax.random.normal(ks[3], (D, q_loc, hd // N)) * 0.05
    WO = jax.random.normal(ks[4], (q_loc * hd, D)) * 0.05

    def body_adapter(x, wq, wo, k, v, pos):
        ctx = ParallelCtx(model="c", heads=heads, cluster=clus,
                          model_static=8)
        w = _split_token_weights(
            ctx, type("A", (), dict(wq=wq, wk=wq[:, :kv_loc], wv=wq[:, :kv_loc],
                                    wo=wo, bq=None, bk=None, bv=None))())
        o, nc = df.split_token_attention(
            spec_p, x, w, df.KVBlock(k, v, pos), jnp.int32(3))
        return o[None]

    sm2 = shard_map(body_adapter, mesh=mesh, in_specs=(P(),) * 6,
                    out_specs=P("c"), check_vma=False)
    with tracecount.counting() as c2:
        jax.eval_shape(sm2, X, WQ, WO, cache.k, cache.v, cache.pos)
    c2 = dict(c2)
    assert c2.get("weight_slice", 0) >= 1, c2
    assert c2.get("weight_gather", 0) >= 3, c2
    assert c2.get("tree_gather", 0) >= 3, c2
    print("ADAPTER COUNTS OK", c2)
    """)


@pytest.mark.multidevice
def test_counters_engine_zero_weight_movement():
    """End-to-end decode step (gemma2 GQA ring + softcap, forced
    cluster 2): the prepacked engine traces with zero weight gathers and
    zero weight slices; the PR-1 adapter engine pays both."""
    run_multidevice("""
    from repro.configs import get_config, reduced
    from repro.core import tracecount
    from repro.launch.mesh import make_test_mesh
    from repro.launch.serve import build_engine

    cfg = reduced(get_config("gemma2-27b"))
    mesh = make_test_mesh()
    counts = {}
    for label, pp in (("prepack", "on"), ("adapter", "off")):
        params, pf, dec, state, lay, scfg = build_engine(
            cfg, mesh, max_seq=32, batch_global=4, cluster=2,
            backend="pallas", interpret=True, prepack=pp)
        tok = jnp.zeros((4,), jnp.int32)
        with tracecount.counting() as c:
            jax.eval_shape(dec, params["serve"], state, tok)
        counts[label] = dict(c)
        print(label, counts[label])
    assert counts["prepack"].get("weight_gather", 0) == 0, counts
    assert counts["prepack"].get("weight_slice", 0) == 0, counts
    assert counts["prepack"].get("weight_slice_hoisted", 0) == 0, counts
    assert counts["prepack"].get("pallas_kernel", 0) >= 1, counts
    assert counts["adapter"].get("weight_gather", 0) >= 3, counts
    assert counts["adapter"].get("weight_slice_hoisted", 0) >= 1, counts
    # the hoisted adapter path never slices inside the per-layer body
    assert counts["adapter"].get("weight_slice", 0) == 0, counts
    print("ENGINE COUNTS OK")
    """)


@pytest.mark.multidevice
def test_counters_fullblock_two_launches_zero_ffn_psum():
    """Full-block decode fusion proof (DESIGN.md §7): the fused prepacked
    decode step traces with exactly TWO ``pallas_call`` launches per
    dense-FFN attention layer (fused attention + fused FFN tail) plus
    ONE fused LM-head launch per STEP (the L5 sampling tail —
    kernels/fused_head, counted in detail in tests/test_fused_head.py),
    and exactly ONE activation ``psum_model`` per STEP (the embedding
    lookup — zero per-layer FFN psums, replaced by one fused
    ClusterReduce per layer); the unfused XLA step pays one FFN psum
    per layer on top."""
    run_multidevice("""
    from repro.configs import get_config, reduced
    from repro.core import tracecount
    from repro.launch.mesh import make_test_mesh
    from repro.launch.serve import build_engine

    # llama2: dense gated FFN, 1-position pattern; gemma2: ring + softcap,
    # 2-position pattern — the count scales with distinct layer positions
    for arch in ("llama2-7b", "gemma2-27b"):
        cfg = reduced(get_config(arch))
        period = len(cfg.block_pattern)
        mesh = make_test_mesh()
        counts = {}
        for label, kw in (("fused", dict(backend="pallas", interpret=True)),
                          ("xla", dict(backend="xla"))):
            params, pf, dec, state, lay, scfg = build_engine(
                cfg, mesh, max_seq=32, batch_global=4, **kw)
            tok = jnp.zeros((4,), jnp.int32)
            with tracecount.counting() as c:
                jax.eval_shape(dec, params["serve"], state, tok)
            counts[label] = dict(c)
            print(arch, label, counts[label])
        f = counts["fused"]
        # exactly 2 launches per traced layer position (fused attention +
        # fused FFN tail; the scan re-dispatches the same pair per group)
        # + 1 per-step fused LM-head launch (L5)
        assert f.get("pallas_kernel") == 2 * period + 1, (arch, f)
        assert f.get("ffn_pallas_kernel") == period, (arch, f)
        assert f.get("head_pallas_kernel") == 1, (arch, f)
        # zero per-layer activation psums: the only psum_model in the
        # whole step is the embedding assembly
        assert f.get("psum_model") == 1, (arch, f)
        assert f.get("ffn_cluster_reduce") == period, (arch, f)
        # no weight movement either (PR-2 invariant still holds)
        assert f.get("weight_gather", 0) == 0, (arch, f)
        assert f.get("weight_slice", 0) == 0, (arch, f)
        # the unfused step pays embed + one FFN psum per layer position
        assert counts["xla"].get("psum_model") == 1 + period, (arch, counts)
        assert counts["xla"].get("pallas_kernel", 0) == 0, (arch, counts)
    print("FULL-BLOCK COUNTS OK")
    """, timeout=1200)


# ---------------------------------------------------------------------------
# Derived state: checkpoints keep the training layout only
# ---------------------------------------------------------------------------
def test_checkpoint_round_trips_train_layout(tmp_path):
    from repro.checkpoint.manager import CheckpointManager, strip_derived
    from repro.serving.prepack import prepack_for_serving
    cfg, lay, params = _small_gqa_setup(cluster=2)
    packed = prepack_for_serving(cfg, lay, params, backend="pallas")

    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(7, {"train": params, "serve": packed}, block=True)

    # only the training layout was written; it restores bit-identically
    restored, _ = mgr.restore(like=jax.tree.map(np.asarray, params))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), b)

    # symmetric round-trip: restore also accepts the engine's
    # {"train","serve"} pair and strips it the same way save did
    restored2, _ = mgr.restore(
        like=jax.tree.map(np.asarray, {"train": params, "serve": packed}))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored2)):
        np.testing.assert_array_equal(np.asarray(a), b)

    # and the serve layout is re-derived, bit-identically, from it
    rederived = prepack_for_serving(
        cfg, lay, jax.tree.map(jnp.asarray, restored), backend="pallas")
    for a, b in zip(jax.tree.leaves(packed), jax.tree.leaves(rederived)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # recursive: an engine pair nested inside a larger snapshot strips
    # too; a NON-engine {"train","serve"} dict (e.g. metrics) does not
    eng = {"train": {"blocks": [], "tail": [], "x": 1},
           "serve": {"blocks": [], "tail": [], "x": 2}}
    out = strip_derived({"model": eng,
                         "metrics": {"train": 0.5, "serve": 0.7}})
    assert out == {"model": eng["train"],
                   "metrics": {"train": 0.5, "serve": 0.7}}
    assert strip_derived({"embed": 3}) == {"embed": 3}


# ---------------------------------------------------------------------------
# Autotune plumbing
# ---------------------------------------------------------------------------
def test_serve_plan_prepack_resolution(tmp_path):
    from repro.configs import get_config, reduced
    from repro.core.autotune import load_table, save_table, tune_serving
    cfg = reduced(get_config("llama2-7b"))
    path = str(tmp_path / "tune.json")
    p = tune_serving(cfg, seq_len=512, batch=2, model_axis=4,
                     backend="auto", table_path=path)
    assert p.backend == "pallas" and p.prepack is True
    p_x = tune_serving(cfg, seq_len=512, batch=2, model_axis=4,
                       backend="xla", table_path=path)
    assert p_x.prepack is False
    p_xf = tune_serving(cfg, seq_len=512, batch=2, model_axis=4,
                        backend="xla", prepack="on", table_path=path)
    assert p_xf.prepack is True
    # the table keys on the RESOLVED prepack bool: auto and an explicit
    # "on" that resolve identically share one cell (no duplicate tuning)
    p_on = tune_serving(cfg, seq_len=512, batch=2, model_axis=4,
                        backend="pallas", table_path=path)
    n_cells = len(load_table(path))
    p_on2 = tune_serving(cfg, seq_len=512, batch=2, model_axis=4,
                         backend="pallas", prepack=True, table_path=path)
    assert p_on2 == p_on and len(load_table(path)) == n_cells
    # typo'd knobs raise instead of silently disabling the fast path
    with pytest.raises(ValueError):
        tune_serving(cfg, seq_len=512, batch=2, model_axis=4,
                     backend="pallas", prepack="On", table_path=path)

    # a pre-prepack (PR-1 schema) table entry self-heals by re-tuning
    table = load_table(path)
    key = next(iter(table))
    del table[key]["prepack"]
    save_table(path, table)
    p2 = tune_serving(cfg, seq_len=512, batch=2, model_axis=4,
                      backend="auto", table_path=path)
    assert p2 == p
    assert "prepack" in load_table(path)[key]

    # attention-free archs never prepack under auto
    cfg_rec = reduced(get_config("rwkv6-3b"))
    p_rec = tune_serving(cfg_rec, seq_len=512, batch=2, model_axis=4,
                         backend="auto", table_path=path)
    assert p_rec.backend == "xla" and p_rec.prepack is False


def test_weight_gather_bytes_model():
    from repro.configs import get_config, reduced
    from repro.core.autotune import weight_gather_bytes_per_step
    cfg = reduced(get_config("llama2-7b"))
    kw = dict(model_axis=4, cluster_size=2)
    adapter = weight_gather_bytes_per_step(cfg, backend="pallas",
                                           prepack=False, **kw)
    assert adapter > 0
    assert weight_gather_bytes_per_step(cfg, backend="pallas",
                                        prepack=True, **kw) == 0.0
    assert weight_gather_bytes_per_step(cfg, backend="xla",
                                        prepack=False, **kw) == 0.0
    # cluster 1: the gathers are no-ops — nothing to model
    assert weight_gather_bytes_per_step(
        cfg, model_axis=4, cluster_size=1, backend="pallas",
        prepack=False) == 0.0
