"""Decode engine: ClusterFusion serving path.

``decode_step`` is the paper's product: per attention layer it runs the
cluster-centric fused dataflow (Alg. 3 SplitToken / Alg. 4 MLA) over the
``heads × cluster`` factoring of the model axis, with all intermediates
inside the shard_map body (one XLA computation per step, collectives =
exactly the ClusterGather/ClusterReduce schedule).  Attention-free blocks
(RG-LRU / RWKV-6) keep O(1) state — the paper's technique is inapplicable
there (DESIGN.md §4) and they use their own fused steps.

Cache layout (SplitToken): per attention layer, per device —
``k/v [S_blk, B_loc·kv_loc, hd]`` with the *sequence* sharded over the
cluster sub-axis (paper's KV-sequence partition) and kv-heads over the
heads sub-axis; ``pos [S_blk, B_loc]`` stores PER-SLOT global positions
(ring semantics for sliding-window layers).  Batch is sharded over the
data axes; decode is RAGGED — ``state["cache_lens"] [B_loc]`` lets every
sequence advance independently, and ``serving/scheduler.py`` runs
continuous batching over the slots (admit into free slots via targeted
prefill inserts, retire on EOS/max-len; DESIGN.md §6).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import (ATTN_GLOBAL, ATTN_LOCAL, RECURRENT, RWKV6,
                                ModelConfig)
from repro.core import dataflow as df
from repro.core import primitives as prim
from repro.core import tracecount
from repro.models import rglru as rglru_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.attention import AttnParams, MLAAttnParams
from repro.models.ctx import ParallelCtx
from repro.models.layers import (EmbedParams, embed_lookup, ffn_apply,
                                 lm_head_logits, rms_norm, softcap)
from repro.models.moe import MoEParams, moe_apply
from repro.models.transformer import unwrap_local
from repro.serving.sampling import (CAND_K, _greedy_pair_merge,
                                    advance_sampling_step,
                                    finalize_candidates, greedy_sample,
                                    greedy_sample_pair, head_candidates,
                                    init_sampling_state, topk_pair_merge)

__all_reexports__ = (_greedy_pair_merge, greedy_sample, greedy_sample_pair)
# ^ the greedy helpers live in serving/sampling.py now (the stochastic
#   finalize shares their merge discipline); re-exported here because
#   PR-5-era call sites import them from the engine.

PyTree = Any


@dataclass(frozen=True)
class ServeConfig:
    max_seq: int                   # cache capacity (global positions)
    batch_local: int               # per-device batch
    fused_combine: bool = False    # beyond-paper single-tree flash merge
    dataflow: str = "split_token"  # split_token | split_head (bench only)
    # giant-MoE weight spreading: expert d_ff additionally sliced over the
    # "data" axis (kimi-1T / arctic-480B decode; DESIGN.md §5)
    dff_shard: bool = False
    # kernel backend for the per-layer local compute stage (DESIGN.md §2):
    # "xla" = block-bucketed XLA dataflow; "pallas" = fused decode kernels
    backend: str = "xla"
    interpret: bool = False        # Pallas interpret mode (CPU only;
                                   # raises on a TPU)
    block_s: int = 256             # KV block granularity (autotunable)
    block_f: int = 512             # d_ff tile of the fused-FFN megakernel
                                   # (autotunable; fitted to F_loc per call)
    block_v: int = 1024            # vocab tile of the fused LM-head/sampling
                                   # kernel (autotunable; fitted to V_loc)
    # serve-layout weight prepack (serving/prepack.py): params arrive
    # already packed per rank — no per-step weight gathers or slices
    prepack: bool = False
    # ragged-decode work accounting: accumulate per-slot attend-step
    # (KV-block) counts into state["work_blocks"] every decode step
    # (core/tracecount.live_attend_blocks) — evidence that retired
    # scheduler slots pay zero attention work.  Off by default (adds a
    # [B]-int32 state leaf + a few integer ops per layer).
    track_work: bool = False
    # per-step integrity sentinel (fleet router health probes,
    # DESIGN.md §9): accumulate per-slot violation counts into
    # state["nonfinite"] — non-finite residual row, non-finite head
    # (value, index) max, or a sampled token outside [0, vocab) on an
    # ACTIVE slot.  Pure where-mask arithmetic + a counter leaf: no
    # jax.debug, no checkify, no host sync — the router reads the leaf
    # on its own schedule.  Off by default so the bench path traces an
    # identical program.
    check_finite: bool = False
    # SDC detection (serving/integrity.py, DESIGN.md §9): per-entry
    # per-slot int32 bit-pattern checksums of the KV caches
    # (state["kv_fp"] / state["kv_fp_tail"]), updated incrementally on
    # append/ring-wrap inside the fused step and recomputed for
    # admitted slots by the prefill insert; the router's probes
    # host-verify them.  Off by default (bench path unchanged).
    kv_fingerprint: bool = False
    # shadow-recompute stash (serving/integrity.py): each step writes
    # the per-slot pre-head residual + winning logit + sampled token
    # (state["head_resid"/"head_val"/"head_tok"]) so a host probe can
    # re-derive the committed token's logit against a pristine head
    # copy.  Off by default.
    shadow_head: bool = False
    # keep each decode step's merged head candidate values (the sorted
    # k = CAND_K logits) in state["cand_v"] — chip_smoke.py compares them
    # across backends.  Off by default.
    stash_candidates: bool = False


@dataclass(frozen=True)
class EngineOptions:
    """Construction-time options for ``build_engine_full`` — the single
    object that replaced its 14 mirrored keyword arguments (the legacy
    kwargs still work through a once-warning deprecation shim).

    Everything here is either resolved into the :class:`ServeConfig`
    the jitted steps close over (``backend`` / ``interpret`` /
    ``block_*`` / ``prepack`` / ``track_work`` / ``check_finite`` /
    ``kv_fingerprint`` / ``shadow_head`` / ``stash_candidates``) or
    consumed by the build
    itself (``fused_combine`` / ``cluster`` / ``autotune_table`` /
    ``fuse_head`` / ``plan_seq_len``).  ``None`` block sizes defer to
    the autotuned plan; ``plan_seq_len`` keys the autotune bucket on
    the expected max LIVE length rather than the allocated capacity
    (DESIGN.md §6)."""
    fused_combine: bool = False
    cluster: Optional[int] = None
    backend: str = "xla"
    interpret: bool = False
    block_s: Optional[int] = None
    block_f: Optional[int] = None
    block_v: Optional[int] = None
    prepack: Any = "auto"
    autotune_table: Optional[str] = None
    track_work: bool = False
    fuse_head: bool = True
    check_finite: bool = False
    kv_fingerprint: bool = False
    shadow_head: bool = False
    stash_candidates: bool = False
    plan_seq_len: Optional[int] = None


# ---------------------------------------------------------------------------
# Cache init (per device)
# ---------------------------------------------------------------------------
def _attn_cache(cfg: ModelConfig, scfg: ServeConfig, ctx: ParallelCtx,
                kind: str, dtype=jnp.bfloat16) -> df.KVBlock:
    n = ctx.cluster_size
    hs = ctx.heads_size
    kv_loc = max(1, cfg.n_kv_heads // hs)
    hd = cfg.resolved_head_dim
    B = scfg.batch_local
    # pos is PER-SLOT ([S_blk, B]): ragged decode gives every sequence
    # its own positions (ring wrap points differ once slots decouple)
    if cfg.mla is not None:
        lr = cfg.mla.kv_lora_rank + cfg.mla.rope_head_dim
        s_blk = scfg.max_seq // n
        return df.KVBlock(k=jnp.zeros((s_blk, B, lr), dtype),
                          v=jnp.zeros((s_blk, B, 1), dtype),
                          pos=jnp.full((s_blk, B), -1, jnp.int32))
    span = cfg.sliding_window if kind == ATTN_LOCAL else scfg.max_seq
    span = min(span, scfg.max_seq)
    s_blk = max(1, span // n)
    return df.KVBlock(k=jnp.zeros((s_blk, B * kv_loc, hd), dtype),
                      v=jnp.zeros((s_blk, B * kv_loc, hd), dtype),
                      pos=jnp.full((s_blk, B), -1, jnp.int32))


def init_decode_state(cfg: ModelConfig, scfg: ServeConfig, ctx: ParallelCtx
                      ) -> Dict[str, Any]:
    """Per-device decode state: stacked caches per pattern position +
    recurrent states + per-slot ``cache_lens [B]`` (+ encoder KV slots
    for enc-dec).  ``cache_lens[b]``: number of cached tokens for slot
    ``b``; −1 marks a FREE slot (continuous-batching scheduler — no KV
    writes, no attention work, position counter frozen).  All-zeros is
    a fresh lockstep batch."""
    kinds = cfg.layer_kinds
    period = len(cfg.block_pattern)
    n_groups = cfg.n_layers // period
    B = scfg.batch_local
    hs = ctx.heads_size
    ms = max(ctx.model_size, 1)

    def stack(fn, n):
        items = [fn() for _ in range(n)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *items)

    state: Dict[str, Any] = {"cache_lens": jnp.zeros((B,), jnp.int32),
                             # per-slot sampling params + emit offset
                             # (greedy defaults), riding the state like
                             # cache_lens does — serving/sampling.py
                             "sampling": init_sampling_state(B)}
    if scfg.track_work:
        state["work_blocks"] = jnp.zeros((B,), jnp.int32)
    if scfg.check_finite:
        state["nonfinite"] = jnp.zeros((B,), jnp.int32)
    per_pos: List[Any] = []
    for p, kind in enumerate(cfg.block_pattern):
        if kind in (ATTN_GLOBAL, ATTN_LOCAL):
            per_pos.append(stack(
                lambda k=kind: _attn_cache(cfg, scfg, ctx, k), n_groups))
        elif kind == RECURRENT:
            ds_loc = (cfg.rglru_d_state or cfg.d_model) // ms
            per_pos.append(stack(
                lambda: rglru_mod.rglru_state_init(B, ds_loc,
                                                   cfg.conv1d_width),
                n_groups))
        elif kind == RWKV6:
            nh_loc = (cfg.d_model // cfg.rwkv_head_dim) // hs
            per_pos.append(stack(
                lambda: rwkv_mod.rwkv6_state_init(B, nh_loc,
                                                  cfg.rwkv_head_dim,
                                                  cfg.d_model), n_groups))
    state["layers"] = per_pos
    n_tail = cfg.n_layers - n_groups * period
    state["tail"] = [
        _attn_cache(cfg, scfg, ctx, kinds[n_groups * period + t])
        if kinds[n_groups * period + t] in (ATTN_GLOBAL, ATTN_LOCAL)
        else (rglru_mod.rglru_state_init(
            B, (cfg.rglru_d_state or cfg.d_model) // ms, cfg.conv1d_width)
            if kinds[n_groups * period + t] == RECURRENT
            else rwkv_mod.rwkv6_state_init(
                B, (cfg.d_model // cfg.rwkv_head_dim) // hs,
                cfg.rwkv_head_dim, cfg.d_model))
        for t in range(n_tail)]
    if scfg.kv_fingerprint:
        # one int32 [B] checksum vector per cache entry (zeros for the
        # attention-free kinds — they ride through untouched); the lists
        # stay parallel to state["layers"] / state["tail"]
        state["kv_fp"] = [jnp.zeros((max(n_groups, 1), B), jnp.int32)
                          for _ in cfg.block_pattern]
        state["kv_fp_tail"] = [jnp.zeros((B,), jnp.int32)
                               for _ in range(n_tail)]
    if scfg.shadow_head:
        state["head_resid"] = jnp.zeros((B, cfg.d_model), jnp.bfloat16)
        state["head_val"] = jnp.zeros((B,), jnp.float32)
        state["head_tok"] = jnp.zeros((B,), jnp.int32)
    if scfg.stash_candidates:
        state["cand_v"] = jnp.zeros((B, CAND_K), jnp.float32)
    if cfg.encoder is not None:
        kv_loc = max(1, cfg.n_kv_heads // hs)
        hd = cfg.resolved_head_dim
        P = cfg.frontend.num_positions
        state["enc_kv"] = {
            "k": jnp.zeros((cfg.n_layers, P, B * kv_loc, hd), jnp.bfloat16),
            "v": jnp.zeros((cfg.n_layers, P, B * kv_loc, hd), jnp.bfloat16),
        }
    return state


# ---------------------------------------------------------------------------
# Weight adapters: train layout (AttnParams) → dataflow weight shards
# ---------------------------------------------------------------------------
def _split_token_weights(ctx: ParallelCtx, p: AttnParams, *,
                         _count: str = "weight_slice"
                         ) -> df.SplitTokenWeights:
    """Train layout already shards heads over `heads` and head_dim over
    `cluster` for wq/wk/wv; wo is [q_loc*hd, D] replicated over cluster —
    the dataflow needs the cluster's D-column slice, taken dynamically.

    Axes are ndim-relative, so the same code serves per-layer leaves and
    stacked ``[n_groups, …]`` scan leaves.  Per-layer use is the legacy
    adapter (direct ``decode_block`` callers — bench baselines);
    ``decode_step`` hoists the slicing out of the layer scan
    (:func:`hoist_serve_weights`), and the prepacked serve layout removes
    it entirely (serving/prepack.py).
    """
    tracecount.bump(_count)
    n = ctx.cluster_size
    c = ctx.cluster_index()
    d_n = p.wo.shape[-1] // n
    wo_seg = lax.dynamic_slice_in_dim(p.wo, c * d_n, d_n,
                                      axis=p.wo.ndim - 1)
    return df.SplitTokenWeights(wq=p.wq, wk=p.wk, wv=p.wv, wo=wo_seg,
                                bq=p.bq, bk=p.bk, bv=p.bv)


def _mla_weights(ctx: ParallelCtx, p: MLAAttnParams, cfg: ModelConfig, *,
                 _count: str = "weight_slice") -> df.MLAWeights:
    tracecount.bump(_count, 3)
    n = ctx.cluster_size
    c = ctx.cluster_index()
    m = cfg.mla
    d_n = p.wo.shape[-1] // n
    l_n = m.kv_lora_rank // n
    return df.MLAWeights(
        wq=p.wq,
        wdkv=p.wdkv,
        wuk=lax.dynamic_slice_in_dim(p.wuk, c * l_n, l_n,
                                     axis=p.wuk.ndim - 1),
        wuv=lax.dynamic_slice_in_dim(p.wuv, c * l_n, l_n,
                                     axis=p.wuv.ndim - 2),
        wo=lax.dynamic_slice_in_dim(p.wo, c * d_n, d_n,
                                    axis=p.wo.ndim - 1),
    )


def _hoist_attn(ctx: ParallelCtx, cfg: ModelConfig, p):
    """One block's rank-slice adapter, run ONCE per decode step outside
    the layer-group scan — the step-invariant ``dynamic_slice`` no
    longer re-executes per layer-group iteration."""
    if isinstance(p, MLAAttnParams):
        return _mla_weights(ctx, p, cfg, _count="weight_slice_hoisted")
    return _split_token_weights(ctx, p, _count="weight_slice_hoisted")


def hoist_serve_weights(ctx: ParallelCtx, cfg: ModelConfig,
                        params: PyTree, scfg: ServeConfig) -> PyTree:
    """Per-step weight adapters, hoisted out of the layer scan.

    Prepacked params (serving/prepack.py) are already in serve layout —
    pass through.  Otherwise every self-attention block's train-layout
    ``attn`` entry is rank-sliced here, once per step, so the scan body
    consumes ready dataflow weights (satellite of DESIGN.md §2's
    prepack: the non-prepacked path stops paying the per-layer-iteration
    ``dynamic_slice`` too)."""
    if scfg.prepack:
        return params
    from repro.serving.prepack import map_blocks

    def adapt(blk, stacked):
        a = blk.get("attn")
        if not isinstance(a, (AttnParams, MLAAttnParams)):
            return blk
        return dict(blk, attn=_hoist_attn(ctx, cfg, a))

    return map_blocks(adapt, params)


# ---------------------------------------------------------------------------
# Per-block decode
# ---------------------------------------------------------------------------
def _spec(ctx: ParallelCtx, scfg: ServeConfig) -> df.ClusterSpec:
    return df.ClusterSpec(heads=ctx.heads or "model",
                          cluster=ctx.cluster or "model",
                          fused_combine=ctx.fused_combine,
                          use_xla=ctx.use_xla_collectives,
                          backend=scfg.backend,
                          interpret=scfg.interpret,
                          block_s=scfg.block_s)


def _fused_ffn_tail(ctx: ParallelCtx, cfg: ModelConfig, scfg: ServeConfig,
                    blk: Dict[str, Any], x: jax.Array, a: jax.Array,
                    w: df.PackedFFNWeights) -> jax.Array:
    """Fused block tail (DESIGN.md §7): post-attention norm + both
    residual adds + pre-FFN norm + gate/up/act/down in ONE Pallas kernel
    per rank, with the per-layer FFN activation ``psum_model`` replaced
    by ONE fused ClusterReduce over the full-width down-projection
    partials (the residual folds into exactly one rank's partial, so the
    reduce completes the layer output directly).

    Post-norm models (``post_ln2``) normalize the SUMMED FFN output, so
    there the second residual add runs after the combine on the
    kernel-emitted ``r``.
    """
    from repro.kernels.fused_ffn.fused_ffn import fused_ffn_block
    eps = cfg.norm_eps
    has_post2 = "post_ln2" in blk
    if has_post2:
        add_r = jnp.float32(0.0)
    else:
        add_r = (ctx.model_index() == 0).astype(jnp.float32)
    bf = df._fit_block_s(w.w_in.shape[-1], scfg.block_f)
    o_part, r = fused_ffn_block(
        x, a, w.w_in, w.w_gate, w.w_out, w.ln2, w.post_ln1, add_r,
        act=cfg.ffn_act, eps=eps, block_f=bf, interpret=scfg.interpret,
        layer=w.layer)
    n = ctx.model_size
    if ctx.model is None:
        f = o_part
    elif n & (n - 1):              # non-pow2 axis: tree schedule invalid
        f = ctx.psum_model(o_part)
    else:
        tracecount.bump("ffn_cluster_reduce")
        f = prim.cluster_reduce(o_part, ctx.model, "sum")
    if has_post2:
        return r + rms_norm(f, blk["post_ln2"], eps)
    return f


def decode_block(ctx: ParallelCtx, cfg: ModelConfig, kind: str,
                 blk: Dict[str, Any], x: jax.Array, cache, cache_len,
                 scfg: ServeConfig, enc_kv=None):
    """x: [B, D] → ([B, D], new cache).  Attention via the paper dataflow."""
    eps = cfg.norm_eps
    if kind == RWKV6:
        p = blk["rwkv"]
        a, _, cache = rwkv_mod.rwkv6_step(
            ctx, p, rms_norm(x, blk["ln1"], eps), cfg.rwkv_head_dim, cache)
        x = x + a
        c, cache = rwkv_mod.rwkv6_channel_step(
            ctx, p, rms_norm(x, blk["ln2"], eps), cache)
        return x + c, cache
    if kind == RECURRENT:
        a, cache = rglru_mod.rglru_block_step(
            ctx, blk["rglru"], rms_norm(x, blk["ln1"], eps), cache)
    elif cfg.mla is not None:
        spec = _spec(ctx, scfg)
        w = blk["attn"]
        if isinstance(w, MLAAttnParams):       # train layout: adapt per layer
            w = _mla_weights(ctx, w, cfg)
        # serve layout with a fused ln1: the RAW residual stream goes in,
        # the kernel normalizes in VMEM (DESIGN.md §7)
        fused_ln1 = isinstance(w, df.PackedMLAWeights) and w.ln1 is not None
        x_in = x if fused_ln1 else rms_norm(x, blk["ln1"], eps)
        o_seg, cache = df.mla_attention(
            spec, x_in, w, cache, cache_len,
            nope_dim=cfg.mla.nope_head_dim, rope_dim=cfg.mla.rope_head_dim,
            rope_theta=cfg.rope_theta, norm_eps=eps)
        # prepacked serve layout emits the full [B, D] output directly
        a = o_seg if isinstance(w, df.PackedMLAWeights) \
            else ctx.gather_cluster(o_seg, axis=1)
    else:
        spec = _spec(ctx, scfg)
        w = blk["attn"]
        if isinstance(w, AttnParams):          # train layout: adapt per layer
            w = _split_token_weights(ctx, w)
        window = cfg.sliding_window if kind == ATTN_LOCAL else 0
        fused_ln1 = (isinstance(w, df.PackedSplitTokenWeights)
                     and w.ln1 is not None)
        x_in = x if fused_ln1 else rms_norm(x, blk["ln1"], eps)
        o_seg, cache = df.split_token_attention(
            spec, x_in, w, cache, cache_len,
            window=window, attn_softcap=cfg.attn_softcap,
            rope_theta=cfg.rope_theta, norm_eps=eps)
        a = o_seg if isinstance(w, df.PackedSplitTokenWeights) \
            else ctx.gather_cluster(o_seg, axis=1)
    # Fused block tail: dense-FFN attention blocks on the prepacked Pallas
    # path run post_ln1 + both residual adds + ln2 + the whole FFN as the
    # layer's SECOND (and last) kernel launch; the activation psum_model
    # is replaced by one fused ClusterReduce (DESIGN.md §7).
    if isinstance(blk.get("ffn"), df.PackedFFNWeights) and enc_kv is None:
        return _fused_ffn_tail(ctx, cfg, scfg, blk, x, a, blk["ffn"]), cache
    if "post_ln1" in blk:
        a = rms_norm(a, blk["post_ln1"], eps)
    x = x + a
    if enc_kv is not None:
        ca = _cross_decode(ctx, blk["cross"], x, enc_kv, cfg)
        x = x + ca
    h = rms_norm(x, blk["ln2"], eps)
    if isinstance(blk["ffn"], MoEParams):
        if scfg.dff_shard:
            from repro.models.moe import moe_apply_dff
            h_all = lax.all_gather(h, "data", axis=0, tiled=True)
            y_all = moe_apply_dff(ctx, blk["ffn"], h_all, cfg.ffn_act,
                                  cfg.moe, dff_axes="data")
            rank = lax.axis_index("data")
            f = lax.dynamic_slice_in_dim(y_all, rank * h.shape[0],
                                         h.shape[0], axis=0)
        else:
            f = moe_apply(ctx, blk["ffn"], h[:, None, :], cfg.ffn_act,
                          cfg.moe)[:, 0]
    else:
        f = ffn_apply(ctx, blk["ffn"], h, cfg.ffn_act)
    if "post_ln2" in blk:
        f = rms_norm(f, blk["post_ln2"], eps)
    return x + f, cache


def _cross_decode(ctx, cross_blk, x, enc_kv, cfg: ModelConfig):
    """Decoder cross-attention against static encoder K/V."""
    p: AttnParams = cross_blk["attn"]
    B, D = x.shape
    n = ctx.cluster_size
    q_loc, hd_seg = p.wq.shape[1], p.wq.shape[2]
    hd = hd_seg * n
    h = rms_norm(x, cross_blk["ln"], cfg.norm_eps)
    q_seg = jnp.einsum("bd,dqh->bqh", h, p.wq)
    q = ctx.gather_cluster(q_seg, axis=2)            # [B, q_loc, hd]
    k, v = enc_kv                                    # [P, B*kv_loc, hd]
    P = k.shape[0]
    kv_loc = k.shape[1] // B
    qpk = q_loc // kv_loc
    qg = q.reshape(B, kv_loc, qpk, hd).astype(jnp.float32)
    kc = k.reshape(P, B, kv_loc, hd).astype(jnp.float32)
    vc = v.reshape(P, B, kv_loc, hd).astype(jnp.float32)
    s = jnp.einsum("bkqh,pbkh->bkqp", qg, kc) / math.sqrt(hd)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkqp,pbkh->bkqh", pr, vc).reshape(B, q_loc * hd)
    y = (o.astype(x.dtype) @ p.wo)
    return ctx.psum_heads(y)


# ---------------------------------------------------------------------------
# Full decode step
# ---------------------------------------------------------------------------
def _finite_violations(cfg: ModelConfig, resid: jax.Array, head_val,
                       nxt: jax.Array, active: jax.Array) -> jax.Array:
    """Per-slot integrity sentinel (``ServeConfig.check_finite``): int32
    [B], 1 where an ACTIVE slot's step output is corrupt — non-finite
    residual row, non-finite head max-logit, or a sampled index outside
    ``[0, vocab)``.  Pure where-mask arithmetic: the guard is a handful
    of elementwise ops folded into the step, never a host assert."""
    tracecount.bump("finite_guard")
    bad = ~jnp.isfinite(resid.astype(jnp.float32)).all(axis=-1)
    bad = bad | ~jnp.isfinite(jnp.asarray(head_val, jnp.float32))
    bad = bad | (nxt < 0) | (nxt >= cfg.vocab_size)
    return (bad & active).astype(jnp.int32)


def _fused_head_tail(ctx: ParallelCtx, cfg: ModelConfig, scfg: ServeConfig,
                     w: df.PackedHeadWeights, x: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
    """Fused LM-head/sampling tail (DESIGN.md §7): final RMSNorm + vocab-
    tiled logits + softcap + streaming top-k partials in ONE Pallas
    kernel per vocab shard, then ONE tree ClusterReduce on the sorted
    ``[B, CAND_K]`` (value, global index) candidate sets — ``[B, V]``
    logits never touch HBM, and the merge is the commutative
    :func:`~repro.kernels.fused_head.topk.topk_pair_merge` (the
    ``_greedy_pair_merge`` discipline at width k), so the candidates
    are bit-exact against the unfused full-logits selection
    (:func:`~repro.serving.sampling.head_candidates`).

    Ragged decode needs no gating: the head is slot-local, so free
    slots flow through (their token is ignored by the scheduler),
    exactly as on the XLA path.

    Returns the merged ``(values [B, K], global_indices [B, K])``; the
    caller finalizes per-slot sampling on them
    (:func:`~repro.serving.sampling.finalize_candidates`).
    """
    from repro.kernels.fused_head.fused_head import fused_head_block
    v_loc = w.table.shape[0]
    # largest divisor of V_loc ≤ block_v, WITHOUT _fit_block_s's
    # fall-back-to-full-size: that fallback trades bucket overhead for
    # skipped work on KV buckets, but here the tile is a VMEM-resident
    # [bv, D] weight block — falling back to V_loc would blow the VMEM
    # budget pick_block_v was sized against on awkward shard sizes
    # (small divisors just mean more grid steps, still correct)
    bv = min(scfg.block_v, v_loc)
    while v_loc % bv:
        bv -= 1
    mx, ix = fused_head_block(
        x, w.table, w.ln, eps=cfg.norm_eps,
        logit_softcap=float(cfg.logit_softcap or 0.0), block_v=bv,
        k=CAND_K, interpret=scfg.interpret)
    idx = ix + ctx.model_index().astype(jnp.int32) * v_loc
    if ctx.model is None:
        return mx, idx
    tracecount.bump("head_cluster_reduce")
    mx, idx = prim.cluster_reduce_pairs((mx, idx), ctx.model,
                                        topk_pair_merge)
    return mx, idx


def _check_not_param_pair(params_dm: PyTree, want: str) -> None:
    """PR-2 footgun guard: ``build_engine`` returns ``params`` as the
    ``{"train", "serve"}`` layout pair — stepping with the whole pair
    silently used to trace the wrong tree.  Fail loudly, naming the
    fix."""
    if isinstance(params_dm, dict) and {"train", "serve"} <= params_dm.keys():
        raise ValueError(
            "got the full {'train', 'serve'} param pair from build_engine; "
            f"pass params[{want!r}] — decode_step consumes the serve "
            "layout, prefill the training layout (see launch/serve.py "
            "generate() and the bench_tpot.py call sites)")


_FFN_STACKED = ("w_in", "w_gate", "w_out")


def _split_weight_stacks(blk: Dict[str, Any]):
    """``(per-layer part, weight stacks)`` of one scanned block.

    A Pallas operand cannot be a strided view, so a stack left in the
    scan's ``xs`` is copied layer by layer into a fresh buffer every
    step before the kernel reads it.  The fused FFN kernel runs once a
    layer, so its weights leave ``xs``: the scan body closes over the
    ``[L, …]`` stacks and hands the kernel ``(stack, layer)``
    (:func:`_with_weight_stacks`), and the kernel reads each weight once,
    in place.  The attention weights stay in ``xs``: the per-slot
    ``vmap`` runs ``fused_decode`` once per slot, so each layer's
    ``wqkv``/``wo`` are read once per slot, and the per-layer copy (which
    XLA stages in VMEM when it fits) is the cheaper source for those
    reads than the stack in HBM."""
    f = blk.get("ffn")
    if not isinstance(f, df.PackedFFNWeights):
        return blk, {}
    stacks = {n: getattr(f, n) for n in _FFN_STACKED}
    return dict(blk, ffn=f._replace(**dict.fromkeys(_FFN_STACKED))), stacks


def _with_weight_stacks(blk: Dict[str, Any], stacks, gi) -> Dict[str, Any]:
    """Layer ``gi`` of a block split by :func:`_split_weight_stacks`: its
    FFN bundle carries the whole stacks and the layer to read."""
    if not stacks:
        return blk
    return dict(blk, ffn=blk["ffn"]._replace(layer=gi, **stacks))


def decode_step(ctx: ParallelCtx, cfg: ModelConfig, scfg: ServeConfig,
                params_dm: PyTree, state: Dict[str, Any],
                tokens: jax.Array) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step: tokens [B_loc] → (next_tokens [B_loc], new state).

    Everything (embedding, L layers of fused attention dataflow, FFN,
    head, sampling) is one computation — the TPU analogue of the paper's
    single-CUDA-graph decode step, with kernel-launch overhead replaced by
    a single XLA dispatch.

    Decode is RAGGED: ``state["cache_lens"]`` is a per-slot [B] vector,
    so every sequence advances independently (per-slot RoPE position,
    append slot, live-span cull — DESIGN.md §6).  Slots at −1 are FREE
    (continuous batching): they write no KV, run zero attend steps, and
    their position counter stays frozen; their sampled token is
    meaningless and ignored by the scheduler.
    """
    _check_not_param_pair(params_dm, "serve")
    params = unwrap_local(params_dm)
    # Step-invariant rank slicing of attention weights happens HERE, once
    # per step, not per layer-group iteration (no-op when the params are
    # prepacked in serve layout — serving/prepack.py).
    params = hoist_serve_weights(ctx, cfg, params, scfg)
    kinds = cfg.layer_kinds
    period = len(cfg.block_pattern)
    n_groups = cfg.n_layers // period
    cache_len = state["cache_lens"]

    def _blk_work(kind: str, cache_i) -> jax.Array:
        """Per-slot attend-step count for one attention layer (runtime
        work counters — core/tracecount.py)."""
        if not scfg.track_work or kind not in (ATTN_GLOBAL, ATTN_LOCAL):
            return jnp.zeros_like(cache_len)
        window = cfg.sliding_window if (kind == ATTN_LOCAL
                                        and cfg.mla is None) else 0
        s_blk = cache_i.k.shape[0]
        return tracecount.live_attend_blocks(
            cache_len, s_blk=s_blk,
            block_s=df._fit_block_s(s_blk, scfg.block_s),
            rank=ctx.cluster_index(), window=window, ring=window > 0)

    # named scopes (embed, layers, kv_read, kv_write, head) reach the
    # compiled ops' metadata, so a profiler trace can tell the XLA glue
    # around the named Pallas kernels apart
    with jax.named_scope("embed"):
        x = embed_lookup(ctx, EmbedParams(params["embed"]), tokens)
        if cfg.tie_embeddings:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)

    if cfg.encoder is not None:
        enc_kv_all = state["enc_kv"]

    # Caches ride in the scan CARRY and are updated with a dynamic-index
    # slice write — XLA performs the update in place (the carry buffer is
    # dead after the write), instead of staging a full per-layer copy
    # through scan ys (§Perf iter 3: ~3× decode HBM-byte reduction).
    n_groups_t = jnp.arange(max(n_groups, 1))
    work0 = jnp.zeros_like(cache_len)
    split = [_split_weight_stacks(b) for b in params["blocks"]]
    scanned = tuple(b for b, _ in split)
    stacks = [st for _, st in split]

    def group_body(carry, inp):
        x, caches, work = carry
        if cfg.encoder is not None:
            blks, gi, ca, ek, ev = inp
        else:
            blks, gi = inp
            ca = ek = ev = None
        new_caches = []
        for p_i in range(period):
            with jax.named_scope("kv_read"):
                cache_i = jax.tree.map(lambda l: l[gi], caches[p_i])
            blk = _with_weight_stacks(blks[p_i], stacks[p_i], gi)
            enc = None
            if ca is not None:
                blk = dict(blk)
                blk["cross"] = ca
                enc = (ek, ev)
            work = work + _blk_work(kinds[p_i], cache_i)
            x, nc = decode_block(ctx, cfg, kinds[p_i], blk, x,
                                 cache_i, cache_len, scfg, enc)
            with jax.named_scope("kv_write"):
                new_caches.append(jax.tree.map(
                    lambda full, upd: lax.dynamic_update_index_in_dim(
                        full, upd.astype(full.dtype), gi, axis=0),
                    caches[p_i], nc))
        return (x, tuple(new_caches), work), None

    xs = ((scanned, n_groups_t, params["cross_attn"],
           enc_kv_all["k"], enc_kv_all["v"]) if cfg.encoder is not None
          else (scanned, n_groups_t))
    with jax.named_scope("layers"):
        (x, new_caches, work), _ = lax.scan(
            group_body, (x, tuple(state["layers"]), work0), xs)

    new_state = dict(state)
    new_state["layers"] = list(new_caches)
    new_tail = []
    for t_i, blk in enumerate(params["tail"]):
        kind_t = kinds[n_groups * period + t_i]
        work = work + _blk_work(kind_t, state["tail"][t_i])
        x, nc = decode_block(ctx, cfg, kind_t, blk,
                             x, state["tail"][t_i], cache_len, scfg)
        new_tail.append(nc)
    new_state["tail"] = new_tail
    if scfg.track_work:
        new_state["work_blocks"] = state["work_blocks"] + work
    # LM-head/sampling tail: the prepacked Pallas path carries the
    # aliasing PackedHeadWeights bundle and runs the fused head kernel
    # (final norm + vocab-tiled logits + softcap + streaming top-k
    # partials, one tree k-merge reduce — no [B, V] logits in HBM);
    # otherwise the loose XLA tail builds the SAME sorted candidate set
    # from full logits (DESIGN.md §7).  Per-slot temperature / top-k /
    # top-p / PRNG finalize on the merged candidates, params riding
    # state["sampling"] (serving/sampling.py; greedy default = bit-
    # identical to the PR-5 (max, argmax) pair).
    with jax.named_scope("head"):
        samp = state["sampling"]
        head = params.get("head")
        if isinstance(head, df.PackedHeadWeights):
            cand_v, cand_i = _fused_head_tail(ctx, cfg, scfg, head, x)
        else:
            xh = rms_norm(x, params["final_norm"], cfg.norm_eps)
            table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
            logits = lm_head_logits(ctx, table, xh)
            if cfg.logit_softcap:
                logits = softcap(logits, cfg.logit_softcap)
            cand_v, cand_i = head_candidates(ctx, logits)
        nxt, head_val = finalize_candidates(cand_v, cand_i, samp)
        new_state["sampling"] = advance_sampling_step(samp, cache_len >= 0)
    if scfg.check_finite:
        new_state["nonfinite"] = state["nonfinite"] + _finite_violations(
            cfg, x, head_val, nxt, cache_len >= 0)
    if scfg.kv_fingerprint:
        # incremental SDC checksums (serving/integrity.py): positions
        # whose per-row ``pos`` moved this step (append / ring wrap)
        # contribute their old→new bit-sum delta — the accumulator
        # tracks exactly what THIS program wrote, so a later host
        # mismatch is corruption, never drift
        from repro.serving.integrity import kv_fp_delta
        tracecount.bump("kv_fp_update")
        new_state["kv_fp"] = [
            kv_fp_delta(old, new, fp) if hasattr(old, "k") else fp
            for old, new, fp in zip(state["layers"], new_state["layers"],
                                    state["kv_fp"])]
        new_state["kv_fp_tail"] = [
            kv_fp_delta(old, new, fp) if hasattr(old, "k") else fp
            for old, new, fp in zip(state["tail"], new_state["tail"],
                                    state["kv_fp_tail"])]
    if scfg.shadow_head:
        # atomic (residual, winning logit, token) triple per slot — the
        # host shadow probe re-derives the logit from the residual with
        # a pristine head copy (serving/integrity.py)
        new_state["head_resid"] = x.astype(jnp.bfloat16)
        new_state["head_val"] = jnp.asarray(head_val, jnp.float32)
        new_state["head_tok"] = nxt.astype(jnp.int32)
    if scfg.stash_candidates:
        new_state["cand_v"] = cand_v.astype(jnp.float32)
    # only ACTIVE slots advance; free slots (−1) stay frozen until the
    # scheduler re-admits them via a prefill insert
    new_state["cache_lens"] = jnp.where(cache_len >= 0, cache_len + 1,
                                        cache_len)
    return nxt, new_state
