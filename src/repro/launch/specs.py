"""Abstract specs + step builders for every (arch × shape × mesh) cell.

Everything here works on ``jax.ShapeDtypeStruct``s — the dry-run lowers
and compiles with zero allocation (the same pattern real launches use,
then materialize with ``out_shardings``).

Spec conventions (device-major storage, DESIGN.md §5):
* params:        [model, *local]                P("model", …)
* opt/EF state:  [dp, model, *local]            P(dp_axes, "model", …)
* decode state:  [dp, model, *local]            P(dp_axes, "model", …)
* batch:         [B_global, …]                  P(dp_axes, …)  (replicated
                 when B_global < dp — the long_500k single-stream case)
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.autotune import tune_cluster, tune_serving
from repro.models.ctx import ParallelCtx, make_train_ctx
from repro.models.transformer import (Layout, fsdp_axes,
                                      fsdp_param_specs, fsdp_shard_abstract,
                                      grad_sync_tree, init_device_major,
                                      layout_for, param_specs)
from repro.launch.mesh import dp_axes_of, dp_size_of
from repro.serving.engine import ServeConfig, decode_step, init_decode_state
from repro.serving.prefill import prefill
from repro.training.train_step import (TrainConfig, init_train_state,
                                       make_train_step)

PyTree = Any


# ---------------------------------------------------------------------------
# Layout selection
# ---------------------------------------------------------------------------
def _cluster_ok(cfg: ModelConfig, ms: int, n: int) -> bool:
    """Divisibility constraints for a serve cluster of size n."""
    hs = ms // n
    if hs < 1 or cfg.n_heads % hs:
        return False
    hd = cfg.resolved_head_dim
    if hd % n or cfg.d_model % n:
        return False
    if cfg.mla is not None:
        m = cfg.mla
        if ((m.kv_lora_rank + m.rope_head_dim) % n
                or m.kv_lora_rank % n
                or (m.nope_head_dim + m.rope_head_dim) % n):
            return False
    if cfg.sliding_window % n:
        return False
    return True


def serving_layout(cfg: ModelConfig, shape: ShapeConfig, ms: int) -> Layout:
    """Cluster size from the paper's tuning model (§4.1), constrained to
    divisible configurations.  Attention-free archs fall back to the
    training factoring (the technique is inapplicable — DESIGN.md §4)."""
    if cfg.is_attention_free:
        return layout_for(cfg, ms)
    best = tune_cluster(cfg, seq_len=shape.seq_len,
                        batch=max(1, shape.global_batch), model_axis=ms)
    n = best.cluster_size
    while n > 1 and not _cluster_ok(cfg, ms, n):
        n //= 2
    if not _cluster_ok(cfg, ms, n):
        return layout_for(cfg, ms)
    return Layout(ms, heads_sub=ms // n)


def train_layout(cfg: ModelConfig, ms: int) -> Layout:
    return layout_for(cfg, ms)


def ctx_for(mesh, lay: Layout, **kw) -> ParallelCtx:
    return make_train_ctx("model", heads_sub=lay.heads_sub,
                          model_size=lay.model_size,
                          data=dp_axes_of(mesh), **kw)


# ---------------------------------------------------------------------------
# Abstract trees
# ---------------------------------------------------------------------------
def abstract_params(cfg: ModelConfig, lay: Layout) -> PyTree:
    return jax.eval_shape(
        lambda: init_device_major(cfg, lay, jax.random.PRNGKey(0)))


def _local_view(shape: Tuple[int, ...]) -> Tuple[int, ...]:
    return (1,) + tuple(shape[1:])


def abstract_opt_state(cfg: ModelConfig, tcfg: TrainConfig, params_abs,
                       dp: int, ms: int, fsdp_ax=None
                       ) -> Tuple[PyTree, Optional[PyTree]]:
    """(opt_state_abs, ef_abs) with [dp, model] leading device dims."""
    local = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(_local_view(l.shape), l.dtype),
        params_abs)

    def init(p):
        rank = jnp.zeros((), jnp.int32)
        return init_train_state(cfg, tcfg, p, dp, rank, fsdp_ax=fsdp_ax)

    opt_abs, ef_abs = jax.eval_shape(init, local)

    def lift(l):
        return jax.ShapeDtypeStruct((dp, ms) + tuple(l.shape), l.dtype)

    opt_abs = jax.tree.map(lift, opt_abs)
    ef_abs = jax.tree.map(lift, ef_abs) if ef_abs is not None else None
    return opt_abs, ef_abs


def state_spec_tree(tree: PyTree, dp_axes) -> PyTree:
    """P(dp_axes, "model", None, …) for [dp, model, *local] leaves."""
    return jax.tree.map(
        lambda l: P(dp_axes, "model", *([None] * (l.ndim - 2))), tree)


def abstract_decode_state(cfg: ModelConfig, scfg: ServeConfig,
                          ctx: ParallelCtx, dp: int) -> PyTree:
    local = jax.eval_shape(lambda: init_decode_state(cfg, scfg, ctx))
    ms = ctx.model_size

    def lift(l):
        return jax.ShapeDtypeStruct((dp, ms) + tuple(l.shape), l.dtype)

    return jax.tree.map(lift, local)


# ---------------------------------------------------------------------------
# Batch specs
# ---------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh
                ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """(ShapeDtypeStructs, PartitionSpecs) for the step's data inputs."""
    dp_axes = dp_axes_of(mesh)
    dp = dp_size_of(mesh)
    B = shape.global_batch
    bspec = P(dp_axes) if B % dp == 0 and B >= dp else P()
    i32 = jnp.int32
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    out: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    if shape.mode == "train":
        S = shape.seq_len
        out["tokens"] = sds((B, S), i32)
        out["targets"] = sds((B, S), i32)
        specs["tokens"] = P(*bspec, None)
        specs["targets"] = P(*bspec, None)
        if cfg.frontend is not None:
            fr = cfg.frontend
            out["frontend_embeds"] = sds((B, fr.num_positions,
                                          fr.feature_dim), f32)
            specs["frontend_embeds"] = P(*bspec, None, None)
            if cfg.encoder is None:            # vlm: mask patch positions
                out["valid"] = sds((B, S), f32)
                specs["valid"] = P(*bspec, None)
    elif shape.mode == "prefill":
        out["tokens"] = sds((B, shape.seq_len), i32)
        specs["tokens"] = P(*bspec, None)
        if cfg.frontend is not None:
            fr = cfg.frontend
            out["frontend_embeds"] = sds((B, fr.num_positions,
                                          fr.feature_dim), f32)
            specs["frontend_embeds"] = P(*bspec, None, None)
    else:                                       # decode
        out["tokens"] = sds((B,), i32)
        specs["tokens"] = bspec
    return out, specs


# ---------------------------------------------------------------------------
# Step builders (shard_map-wrapped, jit-ready)
# ---------------------------------------------------------------------------
def _unwrap2(tree):
    return jax.tree.map(lambda l: l[0, 0], tree)


def _wrap2(tree):
    return jax.tree.map(lambda l: l[None, None], tree)


def build_train_step(cfg: ModelConfig, mesh, tcfg: TrainConfig,
                     shape: ShapeConfig, lay: Optional[Layout] = None):
    """Returns (fn, abstract_args, lay) — fn(params, opt, ef, batch)."""
    ms = mesh.shape["model"]
    lay = lay or train_layout(cfg, ms)
    dp_axes = dp_axes_of(mesh)
    dp = dp_size_of(mesh)
    ctx = ctx_for(mesh, lay)
    params_abs = abstract_params(cfg, lay)      # GLOBAL (unsliced) shapes
    sync = grad_sync_tree(cfg, lay, params_abs)
    ax_tree = None
    if tcfg.fsdp and dp > 1:
        ax_tree = fsdp_axes(params_abs, dp)
        # the in_specs add the dp slicing; global args stay full-shaped
        p_specs = fsdp_param_specs(cfg, params_abs, ax_tree, dp_axes)
        params_for_opt = fsdp_shard_abstract(params_abs, ax_tree, dp)
    else:
        p_specs = param_specs(cfg, params_abs)
        params_for_opt = params_abs
    step = make_train_step(ctx, cfg, tcfg, dp_axes, dp, sync_tree=sync,
                           fsdp_ax=ax_tree)
    batch_abs, b_specs = input_specs(cfg, shape, mesh)

    opt_abs, ef_abs = abstract_opt_state(cfg, tcfg, params_for_opt, dp, ms,
                                         fsdp_ax=ax_tree)
    o_specs = state_spec_tree(opt_abs, dp_axes)
    e_specs = state_spec_tree(ef_abs, dp_axes) if ef_abs is not None else None

    def body(params, opt, ef, batch):
        opt_l = _unwrap2(opt)
        ef_l = _unwrap2(ef) if ef is not None else None
        new_p, new_opt, new_ef, metrics = step(params, opt_l, ef_l, batch)
        metrics = {k: v[None] for k, v in metrics.items()}
        return (new_p, _wrap2(new_opt),
                _wrap2(new_ef) if new_ef is not None else None, metrics)

    m_spec = {k: P(None) for k in ("loss", "grad_norm", "tokens")}
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(p_specs, o_specs, e_specs, b_specs),
        out_specs=(p_specs, o_specs, e_specs, m_spec),
        check_vma=False)
    return fn, (params_abs, opt_abs, ef_abs, batch_abs), lay


def _needs_weight_spread(cfg: ModelConfig, ms: int) -> bool:
    """Weights > ~10 GiB/device under model-axis sharding alone."""
    return cfg.param_count() * 2 / ms > 10 * 2**30


def _dff_override_specs(p_specs, params_abs):
    """Add 'data' to the d_ff dim of MoE expert (+dense residual) leaves."""
    from repro.models.moe import MoEParams as MP

    def fix_moe(spec_tree, abs_tree):
        def ent(l, last):
            e = [None] * l.ndim
            e[0] = "model"
            e[l.ndim - (1 if last else 2)] = "data"
            return P(*e)

        return MP(
            router=spec_tree.router,
            w_in=ent(abs_tree.w_in, last=True),
            w_out=ent(abs_tree.w_out, last=False),
            w_gate=None if abs_tree.w_gate is None
            else ent(abs_tree.w_gate, last=True),
            dense=None if abs_tree.dense is None else type(abs_tree.dense)(
                w_in=ent(abs_tree.dense.w_in, last=True),
                w_out=ent(abs_tree.dense.w_out, last=False),
                w_gate=None if abs_tree.dense.w_gate is None
                else ent(abs_tree.dense.w_gate, last=True)),
        )

    out = dict(p_specs)
    out["blocks"] = []
    for sp, ab in zip(p_specs["blocks"], params_abs["blocks"]):
        blk = dict(sp)
        if isinstance(ab.get("ffn"), MP):
            blk["ffn"] = fix_moe(sp["ffn"], ab["ffn"])
        out["blocks"].append(blk)
    return out


def build_decode_step(cfg: ModelConfig, mesh, shape: ShapeConfig,
                      scfg_extra: Optional[dict] = None,
                      backend: str = "xla", interpret: bool = False,
                      block_s: Optional[int] = None, prepack="auto"):
    ms = mesh.shape["model"]
    lay = serving_layout(cfg, shape, ms)
    dp_axes = dp_axes_of(mesh)
    dp = dp_size_of(mesh)
    ctx = ctx_for(mesh, lay, **(scfg_extra or {}))
    B = shape.global_batch
    b_shard = B % dp == 0 and B >= dp
    b_loc = B // dp if b_shard else B
    dff = (_needs_weight_spread(cfg, ms) and cfg.moe is not None
           and cfg.moe.expert_d_ff % mesh.shape["data"] == 0)
    plan = tune_serving(cfg, seq_len=shape.seq_len, batch=max(1, b_loc),
                        model_axis=ms, backend=backend, prepack=prepack)
    scfg = ServeConfig(max_seq=shape.seq_len, batch_local=b_loc,
                       dff_shard=dff, backend=plan.backend,
                       interpret=interpret,
                       block_s=block_s or plan.block_s,
                       block_f=plan.block_f,
                       prepack=plan.prepack)
    params_abs = abstract_params(cfg, lay)
    if scfg.prepack:
        # the decode step consumes the serve layout (derived once from
        # the training layout at load — serving/prepack.py)
        from repro.serving.prepack import prepack_abstract
        params_abs = prepack_abstract(cfg, lay, params_abs,
                                      backend=scfg.backend)
    p_specs = param_specs(cfg, params_abs)
    if dff:
        p_specs = _dff_override_specs(p_specs, params_abs)
    state_abs = abstract_decode_state(cfg, scfg, ctx, dp)
    s_specs = state_spec_tree(state_abs, dp_axes)
    tok_spec = P(dp_axes) if b_shard else P()

    def body(params, state, tokens):
        st = _unwrap2(state)
        nxt, new_st = decode_step(ctx, cfg, scfg, params, st, tokens)
        return nxt, _wrap2(new_st)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(p_specs, s_specs, tok_spec),
                   out_specs=(tok_spec, s_specs),
                   check_vma=False)
    batch_abs, _ = input_specs(cfg, shape, mesh)
    return fn, (params_abs, state_abs, batch_abs["tokens"]), lay, scfg


def build_prefill_step(cfg: ModelConfig, mesh, shape: ShapeConfig):
    ms = mesh.shape["model"]
    lay = serving_layout(cfg, shape, ms)
    dp_axes = dp_axes_of(mesh)
    dp = dp_size_of(mesh)
    ctx = ctx_for(mesh, lay)
    B = shape.global_batch
    b_shard = B % dp == 0 and B >= dp
    b_loc = B // dp if b_shard else B
    scfg = ServeConfig(max_seq=shape.seq_len, batch_local=b_loc)
    params_abs = abstract_params(cfg, lay)
    # giant models: FSDP-slice the prefill weights over dp, gather per group
    fsdp_info = None
    if _needs_weight_spread(cfg, ms) and dp > 1:
        ax_tree = fsdp_axes(params_abs, dp)
        p_specs = fsdp_param_specs(cfg, params_abs, ax_tree, dp_axes)
        fsdp_info = (ax_tree, dp_axes)
    else:
        p_specs = param_specs(cfg, params_abs)
    state_abs = abstract_decode_state(cfg, scfg, ctx, dp)
    s_specs = state_spec_tree(state_abs, dp_axes)
    batch_abs, b_specs = input_specs(cfg, shape, mesh)
    tok_spec = b_specs["tokens"]
    fe_spec = b_specs.get("frontend_embeds", P())

    def body(params, state, tokens, fe):
        st = _unwrap2(state)
        nxt, new_st = prefill(ctx, cfg, scfg, params, st, tokens, fe,
                              fsdp=fsdp_info)
        return nxt, _wrap2(new_st)

    nxt_spec = P(dp_axes) if b_shard else P()
    fn = shard_map(body, mesh=mesh,
                   in_specs=(p_specs, s_specs, tok_spec, fe_spec),
                   out_specs=(nxt_spec, s_specs),
                   check_vma=False)
    fe_abs = batch_abs.get("frontend_embeds")
    return fn, (params_abs, state_abs, batch_abs["tokens"], fe_abs), lay, scfg
