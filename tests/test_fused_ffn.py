"""Fused transformer-block tail (kernels/fused_ffn, DESIGN.md §7).

* Kernel vs pure-jnp oracle across gated/ungated × post-norm ×
  residual-fold × dtype sweeps (interpret mode).
* Fused tail (:func:`repro.serving.engine._fused_ffn_tail`) vs the
  unfused ``rms_norm``/``ffn_apply``/residual composition — single
  device and, via ``run_multidevice``, on an 8-rank model axis at
  cluster sizes {1, 2, 4} (the FFN reduce spans the FULL model axis; the
  sweep proves the fused ClusterReduce is invariant to the heads ×
  cluster factoring the attention side picks).
* Ragged slot masks: the FFN is slot-local — each batch row's output
  equals its own single-row run, and all-zero (free-slot) rows stay
  finite.
* A ``_minihyp``-compatible shrinkable property: fused block ≡ unfused
  layer over random shapes/seeds.
* Full-engine token parity: the fused-FFN Pallas path vs the XLA oracle
  at forced cluster sizes {1, 2, 4} for a GQA arch (llama2) and an MLA
  arch (deepseek), per-step over a forced token stream.
"""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                     # tier-1 container: deterministic shim
    from _minihyp import given, settings, strategies as st

from helpers import run_multidevice


def _mk(rng, shape, dtype, scale=0.3):
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


# ---------------------------------------------------------------------------
# Kernel vs oracle (single device, interpret mode)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("act", ["silu", "gelu_tanh"])
def test_fused_ffn_kernel_vs_ref(dtype, gated, act):
    from repro.kernels.fused_ffn.ops import fused_ffn
    rng = np.random.default_rng(0)
    B, D, F = 3, 32, 24
    x = _mk(rng, (B, D), dtype)
    a = _mk(rng, (B, D), dtype)
    wi = _mk(rng, (D, F), dtype, 0.05)
    wg = _mk(rng, (D, F), dtype, 0.05) if gated else None
    wo = _mk(rng, (F, D), dtype, 0.05)
    ln2 = _mk(rng, (D,), jnp.float32, 0.1)
    p1 = _mk(rng, (D,), jnp.float32, 0.1)
    tol = 3e-5 if dtype == jnp.float32 else 2e-2
    for post1 in (None, p1):
        for add_r in (0.0, 1.0):
            kw = dict(act=act, eps=1e-6, block_f=8)
            o_k, r_k = fused_ffn(x, a, wi, wg, wo, ln2, post1,
                                 jnp.float32(add_r), interpret=True, **kw)
            o_r, r_r = fused_ffn(x, a, wi, wg, wo, ln2, post1,
                                 jnp.float32(add_r), use_ref=True, **kw)
            np.testing.assert_allclose(
                np.asarray(o_k, np.float32), np.asarray(o_r, np.float32),
                rtol=tol, atol=tol, err_msg=f"post={post1 is not None}")
            np.testing.assert_allclose(
                np.asarray(r_k, np.float32), np.asarray(r_r, np.float32),
                rtol=tol, atol=tol)


def test_fused_ffn_block_f_tiling_invariance():
    """The d_ff tile size must not change the result (f32: exactly the
    same accumulation order per output element)."""
    from repro.kernels.fused_ffn.ops import fused_ffn
    rng = np.random.default_rng(1)
    B, D, F = 2, 16, 32
    args = (_mk(rng, (B, D), jnp.float32), _mk(rng, (B, D), jnp.float32),
            _mk(rng, (D, F), jnp.float32, 0.05),
            _mk(rng, (D, F), jnp.float32, 0.05),
            _mk(rng, (F, D), jnp.float32, 0.05),
            _mk(rng, (D,), jnp.float32, 0.1), None, jnp.float32(1.0))
    outs = [fused_ffn(*args, act="silu", block_f=bf, interpret=True)[0]
            for bf in (4, 8, 16, 32)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("layer", ["first", "last"])
@pytest.mark.parametrize("post_norm", [False, True])
def test_fused_ffn_stacked_weights_by_layer_index(gated, layer, post_norm):
    """``[L, …]`` weight stacks read in place at ``layer`` give exactly
    the outputs of the same kernel on that layer's 2-D slices (the decode
    scan's path vs one layer's weights), with and without the Gemma-2
    post-attention norm, and the call counts one ``weights_by_index``."""
    from repro.core import tracecount
    from repro.kernels.fused_ffn.fused_ffn import fused_ffn_block
    rng = np.random.default_rng(4)
    L, B, D, F = 3, 2, 16, 32
    i = 0 if layer == "first" else L - 1
    x = _mk(rng, (B, D), jnp.bfloat16)
    a = _mk(rng, (B, D), jnp.bfloat16)
    wi = _mk(rng, (L, D, F), jnp.bfloat16, 0.05)
    wg = _mk(rng, (L, D, F), jnp.bfloat16, 0.05) if gated else None
    wo = _mk(rng, (L, F, D), jnp.bfloat16, 0.05)
    ln2 = _mk(rng, (D,), jnp.float32, 0.1)
    post1 = _mk(rng, (D,), jnp.float32, 0.1) if post_norm else None
    kw = dict(act="silu", block_f=8, interpret=True)
    with tracecount.counting() as c:
        got = fused_ffn_block(x, a, wi, wg, wo, ln2, post1, jnp.float32(1.0),
                              layer=jnp.int32(i), **kw)
        n_stacked = c["weights_by_index"]
        want = fused_ffn_block(x, a, wi[i], None if wg is None else wg[i],
                               wo[i], ln2, post1, jnp.float32(1.0), **kw)
        assert c["weights_by_index"] == n_stacked == 1, dict(c)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


# ---------------------------------------------------------------------------
# Fused tail vs the unfused layer composition (single device)
# ---------------------------------------------------------------------------
def _unfused_tail(ctx, cfg, x, a, fp, ln2, post1, post2):
    from repro.models.layers import ffn_apply, rms_norm
    eps = cfg.norm_eps
    av = rms_norm(a, post1, eps) if post1 is not None else a
    x1 = x + av
    h = rms_norm(x1, ln2, eps)
    f = ffn_apply(ctx, fp, h, cfg.ffn_act)
    if post2 is not None:
        f = rms_norm(f, post2, eps)
    return x1 + f


@pytest.mark.parametrize("gated,post", [(True, False), (True, True),
                                        (False, False)])
def test_fused_tail_matches_unfused_layer_single_device(gated, post):
    from repro.configs import get_config, reduced
    from repro.core import dataflow as df
    from repro.models.ctx import single_device_ctx
    from repro.models.layers import FFNParams
    from repro.serving.engine import ServeConfig, _fused_ffn_tail
    cfg = reduced(get_config("llama2-7b"))
    ctx = single_device_ctx()
    scfg = ServeConfig(max_seq=16, batch_local=3, backend="pallas",
                       interpret=True, block_f=8)
    rng = np.random.default_rng(2)
    B, D, F = 3, cfg.d_model, 48
    x = _mk(rng, (B, D), jnp.float32)
    a = _mk(rng, (B, D), jnp.float32)
    fp = FFNParams(w_in=_mk(rng, (D, F), jnp.float32, 0.05),
                   w_out=_mk(rng, (F, D), jnp.float32, 0.05),
                   w_gate=_mk(rng, (D, F), jnp.float32, 0.05)
                   if gated else None)
    ln2 = _mk(rng, (D,), jnp.float32, 0.1)
    p1 = _mk(rng, (D,), jnp.float32, 0.1) if post else None
    p2 = _mk(rng, (D,), jnp.float32, 0.1) if post else None
    blk = {"ffn": df.PackedFFNWeights(w_in=fp.w_in, w_out=fp.w_out,
                                      ln2=ln2, w_gate=fp.w_gate,
                                      post_ln1=p1), "ln2": ln2}
    if post:
        blk["post_ln1"] = p1
        blk["post_ln2"] = p2
    got = _fused_ffn_tail(ctx, cfg, scfg, blk, x, a, blk["ffn"])
    want = _unfused_tail(ctx, cfg, x, a, fp, ln2, p1, p2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fused_ffn_ragged_slot_independence():
    """Slot-local: row b of a batched call equals its own single-row run,
    and an all-zero (free scheduler slot) row stays finite."""
    from repro.kernels.fused_ffn.ops import fused_ffn
    rng = np.random.default_rng(3)
    B, D, F = 4, 16, 12
    x = _mk(rng, (B, D), jnp.float32).at[2].set(0.0)   # free slot: zeroed
    a = _mk(rng, (B, D), jnp.float32).at[2].set(0.0)   # residual stream
    wi = _mk(rng, (D, F), jnp.float32, 0.05)
    wg = _mk(rng, (D, F), jnp.float32, 0.05)
    wo = _mk(rng, (F, D), jnp.float32, 0.05)
    ln2 = _mk(rng, (D,), jnp.float32, 0.1)
    kw = dict(act="silu", block_f=4, interpret=True)
    o_b, r_b = fused_ffn(x, a, wi, wg, wo, ln2, None, jnp.float32(1.0), **kw)
    assert np.isfinite(np.asarray(o_b)).all()
    for b in range(B):
        o_1, _ = fused_ffn(x[b:b + 1], a[b:b + 1], wi, wg, wo, ln2, None,
                           jnp.float32(1.0), **kw)
        np.testing.assert_allclose(np.asarray(o_b[b]), np.asarray(o_1[0]),
                                   rtol=1e-5, atol=1e-5, err_msg=f"slot {b}")


# ---------------------------------------------------------------------------
# Shrinkable property: fused block ≡ unfused layer
# ---------------------------------------------------------------------------
@given(st.integers(0, 2 ** 31), st.integers(1, 4), st.booleans())
@settings(max_examples=10, deadline=None)
def test_fused_block_equals_unfused_layer_property(seed, B, gated):
    """Property (hypothesis or the _minihyp shim): for random seeds,
    batch sizes and gating, the fused block tail equals the unfused
    rms→FFN→residual composition — THE invariant that makes the
    two-launch layer a drop-in replacement."""
    from repro.configs import get_config, reduced
    from repro.core import dataflow as df
    from repro.models.ctx import single_device_ctx
    from repro.models.layers import FFNParams
    from repro.serving.engine import ServeConfig, _fused_ffn_tail
    cfg = reduced(get_config("llama2-7b"))
    ctx = single_device_ctx()
    scfg = ServeConfig(max_seq=16, batch_local=B, backend="pallas",
                       interpret=True, block_f=16)
    rng = np.random.default_rng(seed)
    D, F = cfg.d_model, 32
    x = _mk(rng, (B, D), jnp.float32)
    a = _mk(rng, (B, D), jnp.float32)
    fp = FFNParams(w_in=_mk(rng, (D, F), jnp.float32, 0.05),
                   w_out=_mk(rng, (F, D), jnp.float32, 0.05),
                   w_gate=_mk(rng, (D, F), jnp.float32, 0.05)
                   if gated else None)
    ln2 = _mk(rng, (D,), jnp.float32, 0.1)
    w = df.PackedFFNWeights(w_in=fp.w_in, w_out=fp.w_out, ln2=ln2,
                            w_gate=fp.w_gate)
    got = _fused_ffn_tail(ctx, cfg, scfg, {"ffn": w, "ln2": ln2}, x, a, w)
    want = _unfused_tail(ctx, cfg, x, a, fp, ln2, None, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# Cluster sweeps — 8 emulated devices in a subprocess
# ---------------------------------------------------------------------------
@pytest.mark.multidevice
def test_fused_ffn_tail_cluster_sweep():
    """Fused tail vs the ffn_apply oracle on a sharded 8-rank model axis
    at cluster sizes {1, 2, 4} (heads × cluster factorings), gated +
    ungated, pre- and post-norm, with a ragged batch that includes a
    zeroed free slot.  The fused ClusterReduce spans the FULL model axis
    regardless of the attention factoring — the sweep proves the
    replacement for psum_model is factoring-invariant."""
    run_multidevice("""
    from repro.configs import get_config, reduced
    from repro.core import dataflow as df
    from repro.models.ctx import make_train_ctx
    from repro.models.layers import FFNParams, ffn_apply, rms_norm
    from repro.serving.engine import ServeConfig, _fused_ffn_tail
    mesh = jax.make_mesh((8,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    cfg = reduced(get_config("llama2-7b"))
    rng = np.random.default_rng(0)
    B, D, F = 3, cfg.d_model, 48
    X = jnp.asarray(rng.standard_normal((B, D)) * 0.3, jnp.float32)
    A = jnp.asarray(rng.standard_normal((B, D)) * 0.3, jnp.float32)
    X = X.at[1].set(0.0)          # free-slot row: zeroed residual stream
    A = A.at[1].set(0.0)
    WI = jnp.asarray(rng.standard_normal((D, F)) * 0.05, jnp.float32)
    WG = jnp.asarray(rng.standard_normal((D, F)) * 0.05, jnp.float32)
    WOUT = jnp.asarray(rng.standard_normal((F, D)) * 0.05, jnp.float32)
    LN2 = jnp.asarray(rng.standard_normal((D,)) * 0.1, jnp.float32)
    P1 = jnp.asarray(rng.standard_normal((D,)) * 0.1, jnp.float32)
    P2 = jnp.asarray(rng.standard_normal((D,)) * 0.1, jnp.float32)

    for N in (1, 2, 4):
        for gated in (True, False):
            for post in (False, True):
                scfg = ServeConfig(max_seq=16, batch_local=B,
                                   backend="pallas", interpret=True,
                                   block_f=4)

                def body(x, a, wi, wg, wout, ln2, p1, p2):
                    ctx = make_train_ctx("model", heads_sub=8 // N,
                                         model_size=8)
                    r = jax.lax.axis_index("model")
                    floc = F // 8
                    dsl = jax.lax.dynamic_slice_in_dim
                    wi_l = dsl(wi, r * floc, floc, axis=1)
                    wg_l = dsl(wg, r * floc, floc, axis=1) if gated \\
                        else None
                    wo_l = dsl(wout, r * floc, floc, axis=0)
                    w = df.PackedFFNWeights(
                        w_in=wi_l, w_out=wo_l, ln2=ln2, w_gate=wg_l,
                        post_ln1=p1 if post else None)
                    blk = {"ffn": w, "ln2": ln2}
                    if post:
                        blk["post_ln1"] = p1
                        blk["post_ln2"] = p2
                    fused = _fused_ffn_tail(ctx, cfg, scfg, blk, x, a, w)
                    av = rms_norm(a, p1, cfg.norm_eps) if post else a
                    x1 = x + av
                    h = rms_norm(x1, ln2, cfg.norm_eps)
                    f = ffn_apply(ctx, FFNParams(w_in=wi_l, w_out=wo_l,
                                                 w_gate=wg_l),
                                  h, cfg.ffn_act)
                    if post:
                        f = rms_norm(f, p2, cfg.norm_eps)
                    return fused[None], (x1 + f)[None]

                got, want = jax.jit(shard_map(
                    body, mesh=mesh, in_specs=(P(),) * 8,
                    out_specs=(P("model"), P("model")),
                    check_vma=False))(X, A, WI, WG, WOUT, LN2, P1, P2)
                got = np.asarray(got, np.float32)
                assert np.isfinite(got).all(), (N, gated, post)
                err = np.abs(got - np.asarray(want, np.float32)).max()
                assert err <= 1e-4, (N, gated, post, err)
        print("FUSED FFN TAIL OK N =", N)
    """, timeout=1800)


@pytest.mark.multidevice
def test_engine_fullblock_parity_cluster_sweep():
    """Full-engine token parity of the two-launch fused layer vs the XLA
    oracle at forced cluster sizes {1, 2, 4}, GQA (llama2, fused FFN) +
    MLA (deepseek, fused in-kernel norm): the first sampled token (pure
    prefill) must agree exactly, and per-step greedy tokens over a
    FORCED token stream (no cascade) must agree on ≥90% of (step, slot)
    cells — bf16 near-ties flip the argmax at this reduced scale on the
    pre-existing paths too."""
    run_multidevice("""
    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_test_mesh
    from repro.launch.serve import build_engine
    for arch in ("llama2-7b", "deepseek-v2-lite"):
        cfg = reduced(get_config(arch))
        mesh = make_test_mesh()
        for n in (1, 2, 4):
            res = {}
            for backend in ("xla", "pallas"):
                params, pf, dec, state, lay, scfg = build_engine(
                    cfg, mesh, max_seq=48, batch_global=4, cluster=n,
                    backend=backend, interpret=(backend == "pallas"))
                assert scfg.prepack == (backend == "pallas"), scfg
                key = jax.random.PRNGKey(0)
                prompts = jax.random.randint(key, (4, 12), 0,
                                             cfg.vocab_size)
                nxt, st = pf(params["train"], state, prompts, None)
                toks = jax.random.randint(jax.random.PRNGKey(3), (8, 4),
                                          0, cfg.vocab_size)
                outs = [np.asarray(nxt)]
                for t in range(8):
                    o, st = dec(params["serve"], st, toks[t])
                    outs.append(np.asarray(o))
                res[backend] = np.stack(outs)
            # prefill goes through the training layout on both builds
            np.testing.assert_array_equal(res["xla"][0], res["pallas"][0])
            agree = (res["xla"] == res["pallas"]).mean()
            assert agree >= 0.9, (arch, n, agree)
            print("ENGINE FULL-BLOCK PARITY OK", arch, "N =", n, agree)
    """, timeout=1800)
