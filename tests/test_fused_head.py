"""Fused LM-head/sampling tail (kernels/fused_head, DESIGN.md §7 L5).

* Kernel vs pure-jnp oracle across dtype × softcap × block_v sweeps.
  The contract (DESIGN.md §8 pt 0): candidate logits agree within the
  f32 reassociation bound of a D-term dot product, and indices are
  equal wherever the oracle's logits are not near-tied within that
  bound (:func:`_assert_head_contract`).
* block_v tiling invariance and lowest-index tie-breaking (within a
  tile, across tiles, and across vocab shards).
* ``greedy_sample`` cross-shard tie-breaking: equal-max logits on
  different ranks pick the LOWEST global index on EVERY rank —
  regression-locks the semantics the fused head reduce reproduces
  (pre-fix, first-argument-wins ties made ranks disagree).
* Fused tail (``engine._fused_head_tail``) ≡ the unfused
  ``rms_norm``/``lm_head_logits``/``softcap``/``greedy_sample``
  composition — single device and, via ``run_multidevice``, on an
  8-rank model axis at cluster sizes {1, 2, 4}, token-EXACT, including
  zeroed free-slot rows.
* Full-engine token exactness: the prepacked Pallas engine with the
  fused head vs the SAME engine with ``fuse_head=False`` (identical
  fused layers, loose XLA tail) — token-for-token over a forced stream
  at cluster {1, 2, 4}, including a retired (free) scheduler slot.
* Trace-time proof: ONE ``head_pallas_kernel`` + ONE
  ``head_cluster_reduce`` + ZERO ``lm_head_logits`` per fused step —
  the ``[B, V]`` logits never materialize; the full dense step is
  embed psum + 2 launches/layer + 1 head launch + 1 head reduce.
* Modeled byte columns + ``ServePlan.block_v`` schema self-heal.
"""
import jax
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
@pytest.mark.parametrize("cap", [0.0, 30.0])
@pytest.mark.parametrize("bv", [8, 16, 64])
def test_fused_head_kernel_vs_ref_exact(dtype, cap, bv):
    from repro.kernels.fused_head.ops import fused_head
    rng = np.random.default_rng(0)
    B, D, V = 3, 32, 64
    x = _mk(rng, (B, D), dtype)
    tab = _mk(rng, (V, D), dtype, 0.05)
    ln = _mk(rng, (D,), jnp.float32, 0.1)
    mk_, ik = fused_head(x, tab, ln, logit_softcap=cap, block_v=bv,
                         interpret=True)
    mr, ir = fused_head(x, tab, ln, logit_softcap=cap, use_ref=True)
    np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
    np.testing.assert_array_equal(np.asarray(mk_), np.asarray(mr))


def _assert_head_contract(x, tab, ln, cap, got, eps=1e-6):
    """The fused-head contract (DESIGN.md §8 pt 0) for ``got = (values,
    indices)`` of one top-k call against exact (float64) logits.

    Values: within ``2·(D+1)·2⁻²⁴·Σ_d|h_d·e_d|`` of the exact logit —
    twice the worst-case rounding of an f32 sum of D products in ANY
    order (the kernel's dot and XLA's may reduce D in different orders,
    and the order moves with block_v), softcap being 1-Lipschitz.
    Indices: equal to the exact ranking wherever the exact logits around
    that rank are more than twice that bound apart (away from
    near-ties)."""
    from repro.models.layers import rms_norm
    h = np.asarray(rms_norm(x, ln, eps), np.float64)           # [B, D]
    e = np.asarray(tab, np.float64)                            # [V, D]
    exact = h @ e.T
    if cap:
        exact = np.tanh(exact / cap) * cap
    D = h.shape[1]
    tol = 2 * (D + 1) * 2.0 ** -24 * (np.abs(h) @ np.abs(e).T).max(
        axis=1, keepdims=True)                                 # [B, 1]
    vals, idx = np.asarray(got[0], np.float64), np.asarray(got[1])
    k = vals.shape[1]
    order = np.argsort(-exact, axis=1, kind="stable")
    ranked = np.take_along_axis(exact, order, axis=1)
    np.testing.assert_array_less(np.abs(vals - ranked[:, :k]),
                                 np.broadcast_to(tol, vals.shape))
    gap_lo = np.abs(ranked[:, :k] - ranked[:, 1:k + 1])
    gap_hi = np.concatenate([np.full((len(h), 1), np.inf),
                             gap_lo[:, :-1]], axis=1)
    clear = np.minimum(gap_lo, gap_hi) > 2 * tol
    np.testing.assert_array_equal(idx[clear], order[:, :k][clear])


def test_fused_head_block_v_tiling_invariance():
    """The vocab tile size must not change the result beyond the f32
    reassociation bound: every block_v meets the head contract, and the
    strict cross-tile merge preserves argmax-first semantics."""
    from repro.kernels.fused_head.ops import fused_head
    rng = np.random.default_rng(1)
    B, D, V = 2, 16, 64
    for dtype in (jnp.float32, jnp.bfloat16):
        for cap in (0.0, 30.0):
            x = _mk(rng, (B, D), dtype)
            tab = _mk(rng, (V, D), dtype, 0.05)
            ln = _mk(rng, (D,), jnp.float32, 0.1)
            for bv in (4, 8, 16, 32, 64):
                _assert_head_contract(
                    x, tab, ln, cap,
                    fused_head(x, tab, ln, logit_softcap=cap, block_v=bv,
                               k=4, interpret=True))


def test_fused_head_tie_breaks_to_lowest_index_across_tiles():
    """Equal maxima planted in DIFFERENT vocab tiles (and inside one
    tile) must pick the lowest index — ``jnp.argmax`` semantics, the
    contract the cross-shard merge extends globally."""
    from repro.kernels.fused_head.ops import fused_head  # noqa: F811
    x = jnp.zeros((1, 8), jnp.float32).at[0, 0].set(1.0)
    ln = jnp.zeros((8,), jnp.float32)
    tab = jnp.zeros((32, 8), jnp.float32).at[5, 0].set(7.0).at[21, 0].set(7.0)
    for bv in (4, 8, 16, 32):
        _, ik = fused_head(x, tab, ln, block_v=bv, interpret=True)
        assert int(ik[0, 0]) == 5, (bv, ik)
    # within-tile tie too
    tab2 = jnp.zeros((32, 8), jnp.float32).at[9, 0].set(7.0).at[11, 0].set(7.0)
    _, ik2 = fused_head(x, tab2, ln, block_v=16, interpret=True)
    assert int(ik2[0, 0]) == 9


@pytest.mark.slow
@given(st.integers(0, 2 ** 31), st.integers(1, 4), st.booleans(),
       st.booleans())
@settings(max_examples=25, deadline=None)
def test_fused_head_property_exact(seed, B, capped, bf16):
    """Property (hypothesis full profile nightly / "ci" profile or the
    _minihyp shim in tier-1): for random seeds, batch sizes, softcap and
    dtype, kernel and oracle both meet the head contract — THE invariant
    that makes the fused tail a drop-in for lm_head_logits +
    greedy_sample."""
    from repro.kernels.fused_head.ops import fused_head
    rng = np.random.default_rng(seed)
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    D, V = 16, 32
    x = _mk(rng, (B, D), dtype)
    tab = _mk(rng, (V, D), dtype, 0.05)
    ln = _mk(rng, (D,), jnp.float32, 0.1)
    cap = 30.0 if capped else 0.0
    for use_ref in (False, True):
        _assert_head_contract(
            x, tab, ln, cap,
            fused_head(x, tab, ln, logit_softcap=cap, block_v=8, k=4,
                       interpret=True, use_ref=use_ref))


# ---------------------------------------------------------------------------
# Fused tail ≡ unfused composition (single device)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cap", [0.0, 30.0])
def test_fused_tail_matches_unfused_single_device(cap):
    from repro.configs import get_config, reduced
    from repro.core import dataflow as df
    from repro.models.ctx import single_device_ctx
    from repro.models.layers import lm_head_logits, rms_norm, softcap
    from repro.serving.engine import (ServeConfig, _fused_head_tail,
                                      greedy_sample_pair)
    cfg = reduced(get_config("gemma2-27b" if cap else "llama2-7b"))
    ctx = single_device_ctx()
    scfg = ServeConfig(max_seq=16, batch_local=3, backend="pallas",
                       interpret=True, block_v=16)
    rng = np.random.default_rng(2)
    B, D, V = 3, cfg.d_model, 64
    x = _mk(rng, (B, D), jnp.bfloat16).at[1].set(0.0)   # free-slot row
    tab = _mk(rng, (V, D), jnp.bfloat16, 0.05)
    ln = _mk(rng, (D,), jnp.float32, 0.1)
    w = df.PackedHeadWeights(table=tab, ln=ln)
    # the tail now returns the k-wide (values, indices) candidate lists,
    # sorted value-descending; candidate 0 IS the greedy (max, argmax)
    # pair, so both halves must match the PR-5 composition bit-for-bit
    # (the value feeds the check_finite per-slot sentinel)
    cand_v, cand_i = _fused_head_tail(ctx, cfg, scfg, w, x)
    logits = lm_head_logits(ctx, tab, rms_norm(x, ln, cfg.norm_eps))
    if cap:
        logits = softcap(logits, cap)
    want_tok, want_val = greedy_sample_pair(ctx, logits)
    np.testing.assert_array_equal(np.asarray(cand_i[:, 0]),
                                  np.asarray(want_tok))
    np.testing.assert_allclose(np.asarray(cand_v[:, 0]),
                               np.asarray(want_val), rtol=1e-6)
    # candidates are strictly value-sorted and index-deduplicated
    cv, ci = np.asarray(cand_v), np.asarray(cand_i)
    assert (cv[:, :-1] >= cv[:, 1:]).all()
    for b in range(cv.shape[0]):
        assert len(set(ci[b].tolist())) == ci.shape[1]


# ---------------------------------------------------------------------------
# Modeled byte columns + plan plumbing
# ---------------------------------------------------------------------------
def test_head_bytes_model():
    from repro.configs import get_config, reduced
    from repro.core.autotune import (head_hbm_logits_bytes_per_step,
                                     head_ici_bytes_per_step)
    cfg = reduced(get_config("llama2-7b"))
    kw = dict(model_axis=8, batch=2)
    # unfused tails pay the [B, V_loc] logits write; the fused head
    # (prepacked pallas) deletes it
    unfused = head_hbm_logits_bytes_per_step(cfg, backend="xla",
                                             prepack=False, **kw)
    assert unfused == 2 * (cfg.vocab_size // 8) * 4
    assert head_hbm_logits_bytes_per_step(cfg, backend="pallas",
                                          prepack=False, **kw) == unfused
    assert head_hbm_logits_bytes_per_step(cfg, backend="pallas",
                                          prepack=True, **kw) == 0.0
    # the (value, index) pair reduce is identical on both tails, zero on
    # a single-shard axis
    ici_f = head_ici_bytes_per_step(cfg, backend="pallas", prepack=True, **kw)
    ici_u = head_ici_bytes_per_step(cfg, backend="xla", prepack=False, **kw)
    assert ici_f == ici_u > 0
    assert head_ici_bytes_per_step(cfg, model_axis=1, batch=2,
                                   backend="xla", prepack=False) == 0.0


def test_serve_plan_block_v_selfheal(tmp_path):
    """A pre-fused-head (PR-4 schema) table entry lacks ``block_v`` and
    must self-heal by re-tuning through the TypeError path."""
    from repro.configs import get_config, reduced
    from repro.core.autotune import load_table, save_table, tune_serving
    cfg = reduced(get_config("llama2-7b"))
    path = str(tmp_path / "tune.json")
    p = tune_serving(cfg, seq_len=512, batch=2, model_axis=4,
                     backend="auto", table_path=path)
    assert p.block_v > 0
    table = load_table(path)
    key = next(iter(table))
    del table[key]["block_v"]
    save_table(path, table)
    p2 = tune_serving(cfg, seq_len=512, batch=2, model_axis=4,
                      backend="auto", table_path=path)
    assert p2 == p
    assert "block_v" in load_table(path)[key]


def test_bundle_head_pure_aliasing():
    """The head bundle duplicates ZERO bytes: ``table`` IS the training
    tree's embed (tied) / lm_head buffer and ``ln`` IS ``final_norm`` —
    for both the standalone pass and the full prepack; ``head_view``
    returns exactly what decode samples with."""
    from repro.configs import get_config, reduced
    from repro.core.dataflow import PackedHeadWeights
    from repro.models.transformer import Layout, init_device_major
    from repro.serving.prepack import (bundle_head, head_view,
                                       prepack_for_serving)
    for arch, src in (("llama2-7b", "lm_head"), ("gemma2-27b", "embed")):
        cfg = reduced(get_config(arch))
        lay = Layout(4, heads_sub=2)
        params = init_device_major(cfg, lay, jax.random.PRNGKey(0))
        packed = prepack_for_serving(cfg, lay, params, backend="pallas")
        h = packed["head"]
        assert isinstance(h, PackedHeadWeights)
        assert h.table is params[src]
        assert h.ln is params["final_norm"]
        # xla serve layout keeps the loose tail (no bundle)
        assert "head" not in prepack_for_serving(cfg, lay, params,
                                                 backend="xla")
        # the standalone pass and the view helper agree (same buffers)
        b2 = bundle_head(cfg, params)["head"]
        assert b2.table is h.table and b2.ln is h.ln
        hv_pair = head_view(cfg, {"train": params, "serve": packed})
        assert hv_pair.table is h.table and hv_pair.ln is h.ln
        # unpacked trees yield the equivalent train view
        hv = head_view(cfg, params)
        assert hv.table is params[src] and hv.ln is params["final_norm"]


# ---------------------------------------------------------------------------
# greedy_sample cross-shard tie-breaking — 8 emulated devices
# ---------------------------------------------------------------------------
@pytest.mark.multidevice
def test_greedy_sample_tiebreak_across_vocab_shards():
    """Equal-max logits on different vocab shards must pick the LOWEST
    global index, and EVERY rank must return the same token (the merge
    is commutative, so per-rank tree association orders agree) — the
    semantics the fused head reduce reproduces."""
    run_multidevice("""
    from repro.models.ctx import make_train_ctx
    from repro.serving.engine import greedy_sample
    mesh = jax.make_mesh((8,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    V_LOC, B = 4, 2
    # scenarios: (shard, local_idx) pairs planted with the SAME max
    scenarios = [
        [(3, 2), (6, 1)],              # expect 3*4+2 = 14
        [(0, 3), (7, 0)],              # expect 3
        [(2, 1), (2, 3), (5, 0)],      # within-shard + cross-shard: 9
        [(1, 0), (0, 0)],              # adjacent shards: 0
    ]
    for plant in scenarios:
        want = min(s * V_LOC + i for s, i in plant)
        base = np.full((8, B, V_LOC), -2.0, np.float32)
        for s, i in plant:
            base[s, :, i] = 5.0
        logits = jnp.asarray(base)

        def body(lg):
            ctx = make_train_ctx("model", heads_sub=8, model_size=8)
            r = jax.lax.axis_index("model")
            tok = greedy_sample(ctx, lg[r])
            return tok[None]

        toks = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(),),
                                 out_specs=P("model"),
                                 check_vma=False))(logits)
        toks = np.asarray(toks)                      # [8, B] per rank
        assert (toks == want).all(), (plant, want, toks)
        print("TIEBREAK OK", plant, "->", want)
    """)


# ---------------------------------------------------------------------------
# Fused tail ≡ unfused composition — cluster sweep, 8 emulated devices
# ---------------------------------------------------------------------------
@pytest.mark.multidevice
def test_fused_head_tail_cluster_sweep_token_exact():
    """Fused head tail vs the unfused lm_head_logits + greedy_sample
    composition on a sharded 8-rank model axis at cluster sizes
    {1, 2, 4} (heads × cluster factorings — the head reduce spans the
    FULL model axis and must be factoring-invariant), dtypes f32 + bf16,
    softcap on/off, with a zeroed free-slot row.  Token-EXACT, and the
    per-rank results all agree."""
    run_multidevice("""
    from repro.configs import get_config, reduced
    from repro.core import dataflow as df
    from repro.models.ctx import make_train_ctx
    from repro.models.layers import lm_head_logits, rms_norm, softcap
    from repro.serving.engine import (ServeConfig, _fused_head_tail,
                                      greedy_sample)
    mesh = jax.make_mesh((8,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(0)
    B, V = 3, 64
    for arch, cap in (("llama2-7b", 0.0), ("gemma2-27b", 30.0)):
        cfg = reduced(get_config(arch))
        D = cfg.d_model
        for dt in (jnp.float32, jnp.bfloat16):
            X = jnp.asarray(rng.standard_normal((B, D)) * 0.3, dt)
            X = X.at[1].set(0.0)       # free-slot row: zeroed stream
            TAB = jnp.asarray(rng.standard_normal((V, D)) * 0.05, dt)
            LN = jnp.asarray(rng.standard_normal((D,)) * 0.1, jnp.float32)
            for N in (1, 2, 4):
                scfg = ServeConfig(max_seq=16, batch_local=B,
                                   backend="pallas", interpret=True,
                                   block_v=4)

                def body(x, tab, ln):
                    ctx = make_train_ctx("model", heads_sub=8 // N,
                                         model_size=8)
                    r = jax.lax.axis_index("model")
                    v_loc = V // 8
                    tab_l = jax.lax.dynamic_slice_in_dim(
                        tab, r * v_loc, v_loc, axis=0)
                    w = df.PackedHeadWeights(table=tab_l, ln=ln)
                    cv, ci = _fused_head_tail(ctx, cfg, scfg, w, x)
                    lg = lm_head_logits(ctx, tab_l,
                                        rms_norm(x, ln, cfg.norm_eps))
                    if cap:
                        lg = softcap(lg, cap)
                    # candidate 0 of the k-wide merge IS the greedy token
                    return ci[:, 0][None], greedy_sample(ctx, lg)[None]

                got, want = jax.jit(shard_map(
                    body, mesh=mesh, in_specs=(P(),) * 3,
                    out_specs=(P("model"), P("model")),
                    check_vma=False))(X, TAB, LN)
                got = np.asarray(got)            # [8, B] per-rank tokens
                want = np.asarray(want)
                assert (got == want).all(), (arch, dt, N, got, want)
                assert (got == got[0]).all(), (arch, dt, N, got)
            print("FUSED HEAD TAIL OK", arch, dt.__name__)
    """, timeout=1800)


# ---------------------------------------------------------------------------
# Full-engine token exactness + trace-count proof — 8 emulated devices
# ---------------------------------------------------------------------------
@pytest.mark.multidevice
def test_engine_fused_head_token_exact_cluster_sweep():
    """The prepacked Pallas engine with the fused head vs the SAME
    engine built with ``fuse_head=False`` (identical fused layers, loose
    XLA head tail): token-for-token EXACT over prefill + a forced decode
    stream at cluster {1, 2, 4}, including a retired (free) slot whose
    meaningless token must also agree.  Plus the trace-count proof: the
    fused step is embed-psum + 2 launches/layer + 1 head launch + 1 head
    reduce, with ZERO [B, V] logits materializations."""
    run_multidevice("""
    from repro.configs import get_config, reduced
    from repro.core import tracecount
    from repro.launch.mesh import make_test_mesh
    from repro.launch.serve import EngineOptions, build_engine_full
    for arch in ("llama2-7b", "gemma2-27b"):
        cfg = reduced(get_config(arch))
        period = len(cfg.block_pattern)
        mesh = make_test_mesh()
        for n in (1, 2, 4):
            res = {}
            for label, fh in (("fused", True), ("nohead", False)):
                h = build_engine_full(
                    cfg, mesh, max_seq=32, batch_global=4,
                    options=EngineOptions(cluster=n, backend="pallas",
                                          interpret=True, fuse_head=fh))
                tok0 = jnp.zeros((4,), jnp.int32)
                with tracecount.counting() as c:
                    jax.eval_shape(h.decode_fn, h.params["serve"],
                                   h.state, tok0)
                c = dict(c)
                if fh:
                    assert c.get("head_pallas_kernel") == 1, (arch, n, c)
                    assert c.get("head_cluster_reduce") == 1, (arch, n, c)
                    assert c.get("lm_head_logits", 0) == 0, (arch, n, c)
                    assert c.get("pallas_kernel") == 2 * period + 1, \\
                        (arch, n, c)
                    assert c.get("psum_model") == 1, (arch, n, c)
                else:
                    assert c.get("head_pallas_kernel", 0) == 0, c
                    assert c.get("lm_head_logits") == 1, c
                key = jax.random.PRNGKey(0)
                prompts = jax.random.randint(key, (4, 12), 0,
                                             cfg.vocab_size)
                nxt, st = h.prefill_fn(h.params["train"], h.state,
                                       prompts, None)
                # retire slot 2: its cache_len freezes at -1 and its
                # (ignored) sampled token must still match exactly
                st = h.retire_fn(st, jnp.asarray([0, 0, 1, 0], jnp.int32))
                toks = jax.random.randint(jax.random.PRNGKey(3), (6, 4),
                                          0, cfg.vocab_size)
                outs = [np.asarray(nxt)]
                for t in range(6):
                    o, st = h.decode_fn(h.params["serve"], st, toks[t])
                    outs.append(np.asarray(o))
                res[label] = np.stack(outs)
            np.testing.assert_array_equal(res["fused"], res["nohead"])
            print("ENGINE FUSED HEAD OK", arch, "N =", n)
    """, timeout=1800)
