"""Cluster-centric fused decode dataflows (paper §3.2, Alg. 3/4/5).

These functions run *inside* ``shard_map`` and implement the paper's
dataflows with the cluster collectives from :mod:`repro.core.primitives`.
The physical ``model`` mesh axis is factored into two logical sub-axes:

* ``heads`` — partitions (grouped) attention heads across head-groups;
  independent work, combined only by the Output-Projection reduction
  (the paper's ``atomicAdd`` across clusters).
* ``cluster`` — the paper's thread-block cluster: N ranks that cooperate
  on ONE head-group via ClusterGather / ClusterReduce.

Dataflows:

* :func:`split_token_attention` — paper Alg. 3 ("SplitToken", the main
  dataflow): head-dim partitioned QKV-Projection → ClusterGather; KV-cache
  *sequence* partitioned FlashDecoding → ClusterReduce of softmax stats and
  partial outputs; output-dim partitioned Output-Projection.
* :func:`split_head_attention` — paper Alg. 5 (App. B.2): head-dim
  partitioned everywhere; reduces the full score vector (traffic ∝ S) —
  implemented for the paper's dataflow-comparison experiments.
* :func:`mla_attention` — paper Alg. 4 (App. B.1): fused weight-absorbed
  DeepSeek MLA decode.

All three keep every intermediate inside the shard_map body — under jit
the whole fused block lowers to one XLA computation with only the
cluster collectives between stages, i.e. the TPU analogue of the paper's
single fused kernel (see DESIGN.md §2).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import primitives as prim
from repro.core import tracecount
from repro.core.primitives import Axis, SubAxis


# ---------------------------------------------------------------------------
# Cluster specification
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ClusterSpec:
    """How the model axis is factored for the cluster-centric dataflow."""

    heads: Axis                  # head-group sub-axis (size H)
    cluster: Axis                # intra-head cluster sub-axis (size N)
    fused_combine: bool = False  # beyond-paper single-tree flash merge;
                                 # applies to the adapter paths only — the
                                 # prepacked partial_o paths ALWAYS use the
                                 # single-tree (m, l, o) merge, which is
                                 # constitutive of their one-ClusterReduce
                                 # contract, not an option
    use_xla: bool = False        # XLA-native collectives (reference path)
    # -- local-stage compute backend (DESIGN.md §2) ------------------------
    backend: str = "xla"         # "xla" | "pallas": QKV-proj + RoPE + flash
                                 # partial as XLA ops vs ONE fused Pallas
                                 # kernel per rank (collectives in between)
    interpret: bool = False      # Pallas interpret mode (CPU tests)
    block_s: int = 256           # KV block granularity for the attention
                                 # inner loop (both backends)

    @property
    def n_cluster(self) -> int:
        return prim._axis_size(self.cluster)

    @property
    def n_heads_axis(self) -> int:
        return prim._axis_size(self.heads)

    # -- collective dispatch (faithful tree vs XLA-native reference) -------
    def reduce(self, x, op="sum"):
        if self.use_xla and not isinstance(self.cluster, SubAxis):
            return prim.cluster_reduce_xla(x, self.cluster, op)
        return prim.cluster_reduce(x, self.cluster, op)

    def gather_tiled(self, x, axis):
        if self.use_xla and not isinstance(self.cluster, SubAxis):
            return lax.all_gather(x, self.cluster, axis=axis, tiled=True)
        return prim.cluster_gather_tiled(x, self.cluster, axis=axis)

    def heads_reduce(self, x):
        if self.use_xla and not isinstance(self.heads, SubAxis):
            return lax.psum(x, self.heads)
        return prim.cluster_reduce(x, self.heads, "sum")

    def flash_combine(self, m, l, o):
        return prim.cluster_flash_combine(m, l, o, self.cluster,
                                          fused=self.fused_combine)


# ---------------------------------------------------------------------------
# KV cache block (per layer, per shard)
# ---------------------------------------------------------------------------
class KVBlock(NamedTuple):
    """One rank's slice of a layer's KV cache.

    ``k``/``v``: [S_blk, kv_heads_local, head_dim] — *sequence*-partitioned
    across the cluster (SplitToken / MLA) or *head-dim*-partitioned
    (SplitHead).  ``pos``: [S_blk] int32 global position of each slot
    (−1 ⇒ empty); storing positions makes full, sliding-window and ring
    caches uniform and keeps masking exact after wrap-around.
    """

    k: jax.Array
    v: jax.Array
    pos: jax.Array


def init_kv_block(s_blk: int, kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16) -> KVBlock:
    return KVBlock(
        k=jnp.zeros((s_blk, kv_heads, head_dim), dtype),
        v=jnp.zeros((s_blk, kv_heads, head_dim), dtype),
        pos=jnp.full((s_blk,), -1, jnp.int32),
    )


def _insert_kv(cache: KVBlock, k_new: jax.Array, v_new: jax.Array,
               slot_owner: jax.Array, local_slot: jax.Array,
               my_rank: jax.Array, position: jax.Array) -> KVBlock:
    """Predicated insert: only the owning cluster rank writes the new KV.

    ``k_new``/``v_new``: [kv_heads_local, head_dim] (batch handled by vmap
    or by the batch=1-per-step decode convention of the caller).
    """
    own = (slot_owner == my_rank)
    idx = jnp.clip(local_slot, 0, cache.k.shape[0] - 1)
    cur_k = lax.dynamic_slice_in_dim(cache.k, idx, 1, axis=0)
    cur_v = lax.dynamic_slice_in_dim(cache.v, idx, 1, axis=0)
    cur_p = lax.dynamic_slice_in_dim(cache.pos, idx, 1, axis=0)
    new_k = jnp.where(own, k_new[None].astype(cache.k.dtype), cur_k)
    new_v = jnp.where(own, v_new[None].astype(cache.v.dtype), cur_v)
    new_p = jnp.where(own, position[None].astype(jnp.int32), cur_p)
    return KVBlock(
        k=lax.dynamic_update_slice_in_dim(cache.k, new_k, idx, axis=0),
        v=lax.dynamic_update_slice_in_dim(cache.v, new_v, idx, axis=0),
        pos=lax.dynamic_update_slice_in_dim(cache.pos, new_p, idx, axis=0),
    )


def _insert_kv_ragged(cache: KVBlock, k_new: jax.Array, v_new: jax.Array,
                      slot_owner: jax.Array, local_slot: jax.Array,
                      my_rank: jax.Array, position: jax.Array) -> KVBlock:
    """Per-slot predicated insert for ragged decode.

    ``k_new``/``v_new``: [B, …] one new entry per batch slot;
    ``slot_owner``/``local_slot``/``position``: [B].  ``cache.k``/``v``
    carry the batch at axis 1 after a ``[S, B, -1]`` view (the GQA
    layout folds kv-heads into that view's trailing dim), ``cache.pos``
    is [S, B].  A slot only writes when (a) this rank owns its append
    slot and (b) the slot is ACTIVE (``position >= 0`` — retired /
    free scheduler slots carry ``cache_len = −1`` and must leave the
    cache untouched, ring wrap would otherwise alias them onto a live
    owner).
    """
    S = cache.k.shape[0]
    B = position.shape[0]
    own = (slot_owner == my_rank) & (position >= 0)
    idx = jnp.clip(local_slot, 0, S - 1)
    b = jnp.arange(B)

    def upd(full, new):
        f3 = full.reshape(S, B, -1)
        n2 = new.reshape(B, -1).astype(full.dtype)
        put = jnp.where(own[:, None], n2, f3[idx, b])
        return f3.at[idx, b].set(put).reshape(full.shape)

    new_p = jnp.where(own, position.astype(jnp.int32), cache.pos[idx, b])
    return KVBlock(k=upd(cache.k, k_new), v=upd(cache.v, v_new),
                   pos=cache.pos.at[idx, b].set(new_p))


def _apply_rope(x: jax.Array, position: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding at ``position`` — a scalar (lockstep decode) or a
    per-slot ``[B]`` vector (ragged decode; x leads with the batch dim).
    x: [..., head_dim]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    pos = jnp.asarray(position, jnp.float32)
    ang = pos[..., None] * freqs                 # [half] or [B, half]
    if pos.ndim:                                 # [B, 1, …, 1, half]
        ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _softcap(x: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(x / cap) * cap if cap > 0 else x


def _fit_block_s(S: int, block_s: int) -> int:
    """Largest divisor of ``S`` that is ≤ ``block_s``.

    Keeps bucketing alive when the tuned block doesn't divide the local
    cache (e.g. s_blk = 320 with block_s = 256 ⇒ 160), instead of
    silently collapsing to one full-cache bucket.  Falls back to ``S``
    only when the best divisor is degenerately small (> 8× shrink —
    near-prime lengths), where per-bucket overhead would exceed the
    skipped work.
    """
    b = min(block_s, S)
    while b > 1 and S % b:
        b -= 1
    return b if b * 8 > min(block_s, S) else S


class _AppendSlot(NamedTuple):
    """Where this decode step's new KV entry lands on the cluster-sharded
    cache, plus the kernel gating derived from it.  With a per-slot
    ``cache_lens [B]`` (ragged decode) ``owner``/``local_slot``/
    ``include_new`` are [B] vectors; ``rank``/``pos_base`` stay scalar."""

    rank: jax.Array          # this rank's cluster index
    owner: jax.Array         # cluster rank owning the append slot
    local_slot: jax.Array    # slot within the owner's shard
    include_new: jax.Array   # 1 iff this rank owns the slot (the new
                             # token is counted exactly once per cluster)
    pos_base: jax.Array      # pos[i] = pos_base + i when the shard is
                             # position-linear; −1 ⇒ masked path


def _append_slot(spec: ClusterSpec, s_blk: int, cache_len,
                 *, window: int = 0) -> _AppendSlot:
    """THE slot/owner/gating formula, shared by every dataflow path.

    Sliding-window layers use a ring of ``n·s_blk`` slots (the slot
    index wraps, so offsets stop being positions ⇒ ``pos_base = −1``
    forces the stored-pos masked path and forbids offset culling);
    linear caches fill in position order (``pos_base = rank·s_blk``
    enables the mask-free fast path and rank-local live-span culling).
    One definition on purpose: this formula is where the ring-wrap and
    owner-gating hardening landed, and a divergent copy is a silent
    cross-backend mismatch.

    Elementwise over ``cache_len``, so a per-slot ``cache_lens [B]``
    vector yields per-slot owners/gates.  INACTIVE slots (scheduler
    convention: ``cache_len = −1``) never own their append slot — the
    ring modulus would otherwise map −1 onto the last live ring slot
    and a real rank would overwrite it.
    """
    n = spec.n_cluster
    rank = prim.axis_index(spec.cluster)
    cache_len = jnp.asarray(cache_len, jnp.int32)
    slot = cache_len % (n * s_blk) if window > 0 else cache_len
    owner, local_slot = slot // s_blk, slot % s_blk
    include_new = ((owner == rank) & (cache_len >= 0)).astype(jnp.int32)
    if window > 0:
        pos_base = jnp.int32(-1)
    else:
        pos_base = (rank * s_blk).astype(jnp.int32)
    return _AppendSlot(rank, owner, local_slot, include_new, pos_base)


def bucketed_flash_attention(qf: jax.Array, kc: jax.Array, vc: jax.Array,
                             valid: jax.Array, *, scale: float,
                             softcap: float = 0.0, block_s: int = 256):
    """Online-softmax attention over **live** KV blocks only.

    The seed dataflow attended over the entire allocated cache every step
    (masked), so decode FLOPs/bytes scaled with ``max_seq``.  Here the
    local sequence axis is cut into ``block_s``-sized buckets and each
    bucket runs under a ``lax.cond`` on its liveness (any valid slot) —
    dead buckets (beyond the live prefix, or wholly outside a sliding
    window) are skipped at runtime, making per-step cost proportional to
    ``cache_len`` (DESIGN.md §3).  Per-bucket partials merge with the
    usual flash rescale, so the result equals the single masked pass.

    ``qf [B,K,Q,hd]``, ``kc/vc [S,B,K,hd]`` (``vc``'s trailing dim may
    differ — MLA latent values), ``valid [S]`` bool — or ``[S, B]`` for
    ragged decode (per-slot live spans; a bucket runs when ANY slot has
    a live entry in it, and each slot sees only its own mask).  Returns
    ``(m, l, o, blocks_run)`` with the ``-1e30``-masked ``m`` convention
    of :func:`repro.core.primitives.cluster_flash_combine`;
    ``blocks_run`` counts executed buckets (proportionality evidence in
    tests; dead code under ``jit`` when unused).
    """
    S = kc.shape[0]
    ab = _fit_block_s(S, block_s)
    nb = S // ab
    B, K, Q = qf.shape[0], qf.shape[1], qf.shape[2]
    hd_v = vc.shape[-1]
    init = (jnp.full((B, K, Q), -1e30, jnp.float32),
            jnp.zeros((B, K, Q), jnp.float32),
            jnp.zeros((B, K, Q, hd_v), jnp.float32),
            jnp.int32(0))

    def bucket_mask(bv):
        if bv.ndim == 2:                         # ragged: [ab, B] per-slot
            return jnp.moveaxis(bv, 0, 1)[:, None, None, :]   # [B,1,1,ab]
        return bv[None, None, None, :]

    def body(i, carry):
        start = i * ab
        bv = lax.dynamic_slice_in_dim(valid, start, ab)

        def live(c):
            m, l, o, cnt = c
            kb = lax.dynamic_slice_in_dim(kc, start, ab, axis=0)
            vb = lax.dynamic_slice_in_dim(vc, start, ab, axis=0)
            s = jnp.einsum("bkqh,sbkh->bkqs", qf, kb,
                           preferred_element_type=jnp.float32) * scale
            s = _softcap(s, softcap)
            s = jnp.where(bucket_mask(bv), s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(bucket_mask(bv), p, 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            o_new = o * corr[..., None] + jnp.einsum(
                "bkqs,sbkh->bkqh", p.astype(vc.dtype), vb,
                preferred_element_type=jnp.float32)
            return m_new, l_new, o_new, cnt + 1

        return lax.cond(jnp.any(bv), live, lambda c: c, carry)

    return lax.fori_loop(0, nb, body, init)


# ---------------------------------------------------------------------------
# Paper Alg. 3 — SplitToken dataflow (the main contribution)
# ---------------------------------------------------------------------------
class SplitTokenWeights(NamedTuple):
    """Per-(heads-rank, cluster-rank) weight shards for Alg. 3.

    ``wq``  [D, q_local, hd/N]  — head-dim segment of the Q projection
    ``wk``  [D, kv_local, hd/N]
    ``wv``  [D, kv_local, hd/N]
    ``bq``/``bk``/``bv`` optional bias segments (Qwen-2), same trailing dims
    ``wo``  [q_local*hd, D/N]   — output-dim segment of the O projection
    """

    wq: jax.Array
    wk: jax.Array
    wv: jax.Array
    wo: jax.Array
    bq: Optional[jax.Array] = None
    bk: Optional[jax.Array] = None
    bv: Optional[jax.Array] = None


class PackedSplitTokenWeights(NamedTuple):
    """Serve-layout (prepacked) per-rank weights for the fully fused
    Pallas SplitToken path (serving/prepack.py, DESIGN.md §2).

    Materialized ONCE at weight-load time — the decode step performs no
    weight-segment ClusterGather and no ``dynamic_slice`` weight slicing.

    ``wqkv`` [D, (q_loc + 2·kv_loc)·hd] — cluster-gathered q/k/v head-dim
              segments concatenated so the kernel runs ONE projection
              matmul (replicated over the cluster sub-axis).
    ``wo``   [q_loc, hd, D] — full-width Output-Projection rows of this
              rank's heads, per-head, consumed by ``fuse_out="partial_o"``.
              Full width keeps every cluster rank's in-kernel partial in
              the SAME output basis, so the flash merge sums them exactly
              and no post-combine cluster gather remains.
    ``bqkv`` [(q_loc + 2·kv_loc)·hd] fused bias, or None.
    ``ln1``  [D] pre-attention RMSNorm scale, fused into the kernel's
             projection phase (the raw residual stream crosses HBM, the
             normed copy exists only in VMEM — DESIGN.md §7); None keeps
             the legacy caller-normalizes contract.
    """

    wqkv: jax.Array
    wo: jax.Array
    bqkv: Optional[jax.Array] = None
    ln1: Optional[jax.Array] = None


class PackedMLAWeights(NamedTuple):
    """Serve-layout (prepacked) per-rank weights for the fully fused
    Pallas MLA path (serving/prepack.py).

    ``wq``    [D, q_loc·(nope+rope)] — cluster-gathered Q projection.
    ``wdkv``  [D, l_rank+rope]       — cluster-gathered latent Down-Proj.
    ``wuk``   [q_loc, nope, l_rank]  — K-up absorption (full latent).
    ``wproj`` [q_loc, l_rank, D]     — fused W_UV·W_O rows: value
              Up-Projection and Output-Projection folded into one
              full-width per-head matrix at load time, extending the
              paper's weight-absorption trick one stage further (and
              keeping all cluster partials in one output basis).
    """

    wq: jax.Array
    wdkv: jax.Array
    wuk: jax.Array
    wproj: jax.Array
    # [D] fused pre-attention RMSNorm scale (None = caller normalizes)
    ln1: Optional[jax.Array] = None


class PackedFFNWeights(NamedTuple):
    """Serve-layout dense-FFN bundle for the fused block-tail megakernel
    (kernels/fused_ffn, DESIGN.md §7).

    The Megatron training layout is ALREADY the serve layout — gate/up
    column tiles ``[D, F_loc]`` and full-width down rows ``[F_loc, D]``
    (one output basis per rank, so down-projection partials sum exactly
    under one fused ClusterReduce — the same invariant as
    :class:`PackedSplitTokenWeights`.wo) — so the pack is pure aliasing:
    every weight field references the training tree's buffer, and only
    the fused norm scales ride along.  Zero extra HBM residency.

    ``w_in``  [D, F_loc] up columns · ``w_gate`` [D, F_loc] or None ·
    ``w_out`` [F_loc, D] full-width down rows · ``ln2`` [D] pre-FFN
    norm scale · ``post_ln1`` [D] post-attention norm scale (Gemma-2
    sandwich) or None · ``layer`` int32 index into ``[L, …]`` stacks of
    the three weights, read in place by the kernel (decode scan), or
    None for one layer's weights.
    """

    w_in: jax.Array
    w_out: jax.Array
    ln2: jax.Array
    w_gate: Optional[jax.Array] = None
    post_ln1: Optional[jax.Array] = None
    layer: Optional[jax.Array] = None


class PackedHeadWeights(NamedTuple):
    """Serve-layout LM-head/sampling-tail bundle for the fused head
    kernel (kernels/fused_head, DESIGN.md §7).

    PURE aliasing, like :class:`PackedFFNWeights`: ``table`` IS the
    training tree's vocab-sharded ``embed`` buffer (``tie_embeddings``)
    or ``lm_head`` buffer, and ``ln`` IS the ``final_norm`` scale — the
    bundle binds them for the fused tail without materializing a byte
    (``serving/prepack.py:bundle_head`` runs outside the jitted
    attention pack).  The kernel streams ``[block_v, D]`` tiles of
    ``table``, normalizes the raw residual stream in VMEM, and emits
    only per-slot ``(max, argmax)`` greedy partials — the ``[B, V]``
    logits never touch HBM.

    ``table`` [V_loc, D] vocab shard · ``ln`` [D] final RMSNorm scale.
    """

    table: jax.Array
    ln: jax.Array


def split_token_attention(
    spec: ClusterSpec,
    x: jax.Array,                 # [B, D] full hidden states (paper: every
                                  # block reads the entire input)
    w: SplitTokenWeights,
    cache: KVBlock,               # sequence-partitioned across the cluster
    cache_len: jax.Array,         # tokens already in the cache (scalar int32)
    *,
    window: int = 0,              # >0 => sliding-window (ring) cache
    attn_softcap: float = 0.0,
    rope_theta: float = 10000.0,
    scale: Optional[float] = None,
    norm_eps: float = 1e-6,       # fused pre-attention RMSNorm eps (packed
                                  # serve layout with ``ln1`` only)
) -> Tuple[jax.Array, KVBlock]:
    """One decode step of fused QKV-Projection → Attention → Output-Projection.

    Returns ``(o_segment [B, D/N], updated cache)``; the output is
    partitioned over the cluster axis along the model dim (the paper's
    atomicAdd tile).  Callers gather with ``spec.gather_tiled`` when the
    next op needs the full hidden vector.

    ``spec.backend`` selects the local-stage compute: ``"xla"`` runs the
    stages as XLA ops (block-bucketed attention over the live prefix);
    ``"pallas"`` fuses QKV-Projection + RoPE + flash partial into one
    Pallas kernel per rank (:mod:`repro.kernels.fused_decode`) with the
    ClusterGather/ClusterReduce collectives kept between kernel
    invocations — the paper's Level-2 fusion on TPU (DESIGN.md §2).

    ``w`` may also be :class:`PackedSplitTokenWeights` (the serve layout
    from serving/prepack.py): the local stage then runs the fully fused
    ``fuse_out="partial_o"`` kernel with NO per-step weight movement —
    one kernel + one fused ClusterReduce per layer — and the return is
    the FULL ``[B, D]`` output (no cluster gather needed).

    **Ragged decode**: ``cache_len`` may be a per-slot ``[B]`` vector
    (with ``cache.pos`` then ``[S_blk, B]``) — every sequence in the
    batch advances independently (RoPE position, append slot, live-span
    masking and the Pallas index-map clamp are all per-slot; inactive
    slots carry ``cache_len = −1`` and do no work).  A scalar
    ``cache_len`` with 1-D ``pos`` keeps the lockstep semantics.
    """
    if isinstance(w, PackedSplitTokenWeights):
        assert spec.backend == "pallas", \
            "prepacked serve-layout weights require backend='pallas'"
        return _split_token_attention_pallas_packed(
            spec, x, w, cache, cache_len, window=window,
            attn_softcap=attn_softcap, rope_theta=rope_theta, scale=scale,
            norm_eps=norm_eps)
    if spec.backend == "pallas":
        return _split_token_attention_pallas(
            spec, x, w, cache, cache_len, window=window,
            attn_softcap=attn_softcap, rope_theta=rope_theta, scale=scale)
    n = spec.n_cluster
    B = x.shape[0]
    q_local, hd_n = w.wq.shape[1], w.wq.shape[2]
    kv_local = w.wk.shape[1]
    hd = hd_n * n
    qpk = q_local // kv_local
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    # (1) Segment results of QKV Projection (paper Alg. 3 line 2).
    q_seg = jnp.einsum("bd,dqh->bqh", x, w.wq)
    k_seg = jnp.einsum("bd,dkh->bkh", x, w.wk)
    v_seg = jnp.einsum("bd,dkh->bkh", x, w.wv)
    if w.bq is not None:
        q_seg = q_seg + w.bq
        k_seg = k_seg + w.bk
        v_seg = v_seg + w.bv

    # (2) ClusterGather the complete q/k/v (line 3).
    q = spec.gather_tiled(q_seg, axis=2)       # [B, q_local, hd]
    k = spec.gather_tiled(k_seg, axis=2)       # [B, kv_local, hd]
    v = spec.gather_tiled(v_seg, axis=2)

    # RoPE needs the complete head vector (rotates across the halves), so it
    # runs post-gather; position = cache_len.
    q = _apply_rope(q, cache_len, rope_theta)
    k = _apply_rope(k, cache_len, rope_theta)

    # (3) Append new KV to the owning rank's cache block.  Sliding-window
    # layers use a ring cache of exactly `window` slots (sharded over the
    # cluster), so the slot index wraps (shared formula: _append_slot).
    s_blk = cache.k.shape[0]
    ragged = jnp.ndim(cache_len) == 1
    ap = _append_slot(spec, s_blk, cache_len, window=window)
    # decode convention: one new token per sequence; B folded into kv head
    # dim via vmap at the serving layer when B > 1 shares a cache.  Here the
    # cache carries B in its kv_heads axis layout: [S, B*kv_local, hd].
    if ragged:
        cache = _insert_kv_ragged(cache, k, v, ap.owner, ap.local_slot,
                                  ap.rank, cache_len)
    else:
        cache = _insert_kv(
            cache,
            k.reshape(B * kv_local, hd), v.reshape(B * kv_local, hd),
            ap.owner, ap.local_slot, ap.rank, cache_len)

    # (4) FlashDecoding partial over the local sequence block (line 4),
    # bucketed so only live blocks execute (cost ∝ cache_len, not S_blk).
    # Scores/outputs accumulate in f32 via preferred_element_type — the
    # bf16 cache is NEVER materialized as an f32 copy (§Perf iter 1: this
    # halves decode HBM bytes vs casting the cache).
    kc = cache.k.reshape(s_blk, B, kv_local, hd)
    vc = cache.v.reshape(s_blk, B, kv_local, hd)
    qf = q.reshape(B, kv_local, qpk, hd).astype(kc.dtype)
    valid = cache.pos >= 0
    valid &= cache.pos <= cache_len
    if window > 0:
        valid &= cache.pos > cache_len - window
    m_safe, l, o, _ = bucketed_flash_attention(
        qf, kc, vc, valid, scale=scale, softcap=attn_softcap,
        block_s=spec.block_s)

    # (5)–(7) ClusterReduce softmax stats, rescale, ClusterReduce outputs.
    _, l_g, o_g = spec.flash_combine(m_safe, l, o)
    att = (o_g / jnp.maximum(l_g[..., None], 1e-30))
    att = att.reshape(B, q_local * hd).astype(x.dtype)

    # (8) Output-Projection tile + cross-cluster (heads) reduction — the
    # paper writes with atomicAdd; on TPU this is the heads-axis tree sum.
    o_seg = att @ w.wo                                        # [B, D/N]
    o_seg = spec.heads_reduce(o_seg)
    return o_seg, cache


def _split_token_attention_pallas(
    spec: ClusterSpec,
    x: jax.Array,
    w: SplitTokenWeights,
    cache: KVBlock,
    cache_len: jax.Array,
    *,
    window: int,
    attn_softcap: float,
    rope_theta: float,
    scale: Optional[float],
) -> Tuple[jax.Array, KVBlock]:
    """SplitToken with the local stage as ONE fused Pallas kernel per rank.

    The paper's Alg. 3 gathers q/k/v *activation* segments across the
    cluster between projection and attention; a Pallas kernel cannot host
    an ICI collective mid-kernel, so the gather is hoisted to the head-dim
    *weight* segments (``q = x·gather(Wq) == gather(x·Wq)``) and the whole
    local stage — QKV projection, RoPE, FlashDecoding partial over this
    rank's KV-sequence shard — runs inside
    :func:`repro.kernels.fused_decode.fused_decode_attention`
    (``fuse_out=False``).  The ClusterReduce flash combine, the
    Output-Projection tile and the heads reduction stay between kernel
    invocations, exactly the Level-2 schedule (DESIGN.md §2).

    Behavior-parity with the XLA path: stored-position masking (ring /
    sliding-window caches), softcap, GQA bias; the new token's own
    attention contribution is counted once — by the rank owning the
    append slot (``include_new``).
    """
    n = spec.n_cluster
    B, D = x.shape
    q_local, hd_n = w.wq.shape[1], w.wq.shape[2]
    kv_local = w.wk.shape[1]
    hd = hd_n * n
    qpk = q_local // kv_local
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    from repro.kernels.fused_decode.fused_decode import fused_decode_attention
    from repro.kernels.fused_decode.ops import rope_at

    # ClusterGather the head-dim weight segments (Alg. 3 line 3, hoisted
    # from activations to weights so the local stage fuses into one kernel).
    # Step-invariant — the prepacked serve layout does this once at load
    # time instead (serving/prepack.py); this adapter path remains for
    # training-layout serving and parity tests.
    tracecount.bump("weight_gather", 3)
    wq = spec.gather_tiled(w.wq, axis=2)                 # [D, q_local, hd]
    wk = spec.gather_tiled(w.wk, axis=2)
    wv = spec.gather_tiled(w.wv, axis=2)
    wqkv = jnp.concatenate([wq.reshape(D, q_local * hd),
                            wk.reshape(D, kv_local * hd),
                            wv.reshape(D, kv_local * hd)], axis=1)
    bqkv = None
    if w.bq is not None:
        tracecount.bump("weight_gather", 3)
        bq = spec.gather_tiled(w.bq, axis=1)             # [q_local, hd]
        bk = spec.gather_tiled(w.bk, axis=1)
        bv = spec.gather_tiled(w.bv, axis=1)
        bqkv = jnp.concatenate([bq.reshape(q_local * hd),
                                bk.reshape(kv_local * hd),
                                bv.reshape(kv_local * hd)])

    cos, sin = rope_at(cache_len, hd, rope_theta)
    s_blk = cache.k.shape[0]
    ragged = jnp.ndim(cache_len) == 1
    ap = _append_slot(spec, s_blk, cache_len, window=window)
    blk = _fit_block_s(s_blk, spec.block_s)
    wo_unused = jnp.zeros((1, 1), x.dtype)   # O-proj runs after the combine

    kc = cache.k.reshape(s_blk, B, kv_local, hd)
    vc = cache.v.reshape(s_blk, B, kv_local, hd)

    def one(xb, kb, vb, cl, cosb, sinb, posb, inc):
        acc, k_new, v_new, m, l = fused_decode_attention(
            xb[None], wqkv, bqkv, wo_unused, kb, vb, cl, cosb, sinb,
            q_heads=q_local, kv_heads=kv_local, scale=scale,
            attn_softcap=attn_softcap, window=window, ring=window > 0,
            block_s=blk, fuse_out=False, interpret=spec.interpret,
            pos=posb, include_new=inc, pos_base=ap.pos_base)
        return acc[0], k_new[0], v_new[0], m[0], l[0]

    # Ragged: the scalar-prefetch operands (cache_len, include_new, RoPE
    # angles, pos column) are vmapped per slot — each batch element's
    # kernel instance gets its OWN index-map clamp and live-span cull.
    kern_axes = (0, 1, 1, 0, 0, 0, 1, 0) if ragged \
        else (0, 1, 1, None, None, None, None, None)
    acc, k_new, v_new, m, l = jax.vmap(one, in_axes=kern_axes)(
        x, kc, vc, cache_len, cos, sin, cache.pos, ap.include_new)

    # Append the kernel-emitted new KV on the owning rank (as in the XLA
    # path; the kernel itself attended the new token via include_new).
    if ragged:
        cache = _insert_kv_ragged(cache, k_new, v_new, ap.owner,
                                  ap.local_slot, ap.rank, cache_len)
    else:
        cache = _insert_kv(cache, k_new.reshape(B * kv_local, hd),
                           v_new.reshape(B * kv_local, hd),
                           ap.owner, ap.local_slot, ap.rank, cache_len)

    # ClusterReduce combine + Output-Projection tile + heads reduction.
    m = m.reshape(B, kv_local, qpk)
    l = l.reshape(B, kv_local, qpk)
    acc = acc.reshape(B, kv_local, qpk, hd)
    _, l_g, o_g = spec.flash_combine(m, l, acc)
    att = (o_g / jnp.maximum(l_g[..., None], 1e-30))
    att = att.reshape(B, q_local * hd).astype(x.dtype)
    o_seg = att @ w.wo                                       # [B, D/N]
    o_seg = spec.heads_reduce(o_seg)
    return o_seg, cache


def _split_token_attention_pallas_packed(
    spec: ClusterSpec,
    x: jax.Array,
    w: PackedSplitTokenWeights,
    cache: KVBlock,
    cache_len: jax.Array,
    *,
    window: int,
    attn_softcap: float,
    rope_theta: float,
    scale: Optional[float],
    norm_eps: float = 1e-6,
) -> Tuple[jax.Array, KVBlock]:
    """SplitToken on prepacked serve-layout weights — the full Alg. 3
    fusion scope (DESIGN.md §2).  Returns ``(o [B, D], cache)`` — the
    output is already FULL-width (no cluster gather follows).

    No per-step weight movement remains: ``wqkv`` was gathered once at
    load time, and the Output-Projection runs INSIDE the kernel
    (``fuse_out="partial_o"``) through the rank's full-width ``wo``
    rows, emitting unnormalized per-head projected [B, q_loc, D]
    partials.  The per-head projection is linear and shared across the
    cluster, so the flash-merge operator stays exact on ``(m, l, o)``
    triples and a single fused ClusterReduce completes the softmax
    combine AND the projection sum; all that follows is a local
    normalize + head sum and the heads-axis reduction (the paper's
    atomicAdd analogue).  Trade-off, documented in DESIGN.md §2: for
    cluster N > 1 the reduce payload grows from ``q_loc·hd`` to
    ``q_loc·D`` per token — bought back by deleting the per-step weight
    gathers (∝ D·heads·hd), the output gather, and one collective.
    """
    B, D = x.shape
    q_local, hd, d_out = w.wo.shape
    kv_local = (w.wqkv.shape[1] // hd - q_local) // 2
    qpk = q_local // kv_local
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    from repro.kernels.fused_decode.fused_decode import fused_decode_attention
    from repro.kernels.fused_decode.ops import rope_at

    cos, sin = rope_at(cache_len, hd, rope_theta)
    s_blk = cache.k.shape[0]
    ragged = jnp.ndim(cache_len) == 1
    ap = _append_slot(spec, s_blk, cache_len, window=window)
    blk = _fit_block_s(s_blk, spec.block_s)

    kc = cache.k.reshape(s_blk, B, kv_local, hd)
    vc = cache.v.reshape(s_blk, B, kv_local, hd)

    def one(xb, kb, vb, cl, cosb, sinb, posb, inc):
        acc, k_new, v_new, m, l = fused_decode_attention(
            xb[None], w.wqkv, w.bqkv, w.wo, kb, vb, cl, cosb, sinb,
            q_heads=q_local, kv_heads=kv_local, scale=scale,
            attn_softcap=attn_softcap, window=window, ring=window > 0,
            block_s=blk, fuse_out="partial_o", interpret=spec.interpret,
            pos=posb, include_new=inc, pos_base=ap.pos_base,
            norm_scale=w.ln1, norm_eps=norm_eps)
        return acc[0], k_new[0], v_new[0], m[0], l[0]

    kern_axes = (0, 1, 1, 0, 0, 0, 1, 0) if ragged \
        else (0, 1, 1, None, None, None, None, None)
    acc, k_new, v_new, m, l = jax.vmap(one, in_axes=kern_axes)(
        x, kc, vc, cache_len, cos, sin, cache.pos, ap.include_new)

    if ragged:
        cache = _insert_kv_ragged(cache, k_new, v_new, ap.owner,
                                  ap.local_slot, ap.rank, cache_len)
    else:
        cache = _insert_kv(cache, k_new.reshape(B * kv_local, hd),
                           v_new.reshape(B * kv_local, hd),
                           ap.owner, ap.local_slot, ap.rank, cache_len)

    # ONE fused ClusterReduce over (m, l, projected partials), then a
    # local normalize + sum over this rank's heads.
    tracecount.bump("cluster_combine")
    m = m.reshape(B, kv_local, qpk)
    l = l.reshape(B, kv_local, qpk)
    p_o = acc.reshape(B, kv_local, qpk, d_out)
    _, l_g, p_g = prim.cluster_flash_combine(m, l, p_o, spec.cluster,
                                             fused=True)
    o_full = (p_g / jnp.maximum(l_g[..., None], 1e-30)).sum(axis=(1, 2))
    o_full = spec.heads_reduce(o_full.astype(x.dtype))       # [B, D]
    return o_full, cache


# ---------------------------------------------------------------------------
# Paper Alg. 5 — SplitHead dataflow (App. B.2, comparison variant)
# ---------------------------------------------------------------------------
class SplitHeadWeights(NamedTuple):
    """``wq/wk/wv`` [D, q|kv_local, hd/N]; ``wo`` [q_local*hd/N, D]."""

    wq: jax.Array
    wk: jax.Array
    wv: jax.Array
    wo: jax.Array


def split_head_attention(
    spec: ClusterSpec,
    x: jax.Array,                 # [B, D]
    w: SplitHeadWeights,
    cache: KVBlock,               # HEAD-DIM-partitioned: [S, B*kv_local, hd/N]
    cache_len: jax.Array,
    *,
    rope_theta: float = 10000.0,
    scale: Optional[float] = None,
) -> Tuple[jax.Array, KVBlock]:
    """Alg. 5: partition the head dim in all three stages; ClusterReduce the
    full score matrix (traffic ∝ S — the paper shows this loses at long S).

    NOTE: RoPE with a split head dim would rotate across ranks; we follow
    the paper (no RoPE in Alg. 5 exposition) but emulate positionality by
    rotating *within* each segment — documented deviation, exercised only in
    the dataflow-comparison benchmark, not in production serving.
    """
    n = spec.n_cluster
    b_rank = prim.axis_index(spec.cluster)
    B = x.shape[0]
    q_local, hd_n = w.wq.shape[1], w.wq.shape[2]
    kv_local = w.wk.shape[1]
    qpk = q_local // kv_local
    scale = scale if scale is not None else 1.0 / math.sqrt(hd_n * n)

    # (2) QKV segments stay in "registers" (no gather — Alg. 5 line 2).
    q_seg = jnp.einsum("bd,dqh->bqh", x, w.wq)
    k_seg = jnp.einsum("bd,dkh->bkh", x, w.wk)
    v_seg = jnp.einsum("bd,dkh->bkh", x, w.wv)
    q_seg = _apply_rope(q_seg, cache_len, rope_theta)
    k_seg = _apply_rope(k_seg, cache_len, rope_theta)

    # (3) Append to local (head-dim-sharded) cache: every rank owns slot.
    s_max = cache.k.shape[0]
    cache = _insert_kv(cache, k_seg.reshape(B * kv_local, hd_n),
                       v_seg.reshape(B * kv_local, hd_n),
                       b_rank, cache_len, b_rank, cache_len)

    kc = cache.k.reshape(s_max, B, kv_local, hd_n).astype(jnp.float32)
    vc = cache.v.reshape(s_max, B, kv_local, hd_n).astype(jnp.float32)
    qf = q_seg.reshape(B, kv_local, qpk, hd_n).astype(jnp.float32)

    # Partial scores over the FULL sequence, then ClusterReduce (Alg. 5 l.3).
    s_part = jnp.einsum("bkqh,sbkh->bkqs", qf, kc) * scale
    s_full = spec.reduce(s_part, "sum")                       # traffic ∝ S
    valid = (cache.pos >= 0) & (cache.pos <= cache_len)
    s_full = jnp.where(valid[None, None, None, :], s_full, -jnp.inf)
    p = jax.nn.softmax(s_full, axis=-1)
    a_seg = jnp.einsum("bkqs,sbkh->bkqh", p, vc)              # [B,kv,q,hd/N]
    a_seg = a_seg.reshape(B, q_local * hd_n).astype(x.dtype)

    # (4)–(6) partial Output-Projection over full D, ClusterReduce + heads.
    o_part = a_seg @ w.wo                                     # [B, D]
    o_full = spec.reduce(o_part, "sum")
    o_full = spec.heads_reduce(o_full)
    return o_full, cache


# ---------------------------------------------------------------------------
# Paper Alg. 4 — fused weight-absorbed MLA dataflow (App. B.1)
# ---------------------------------------------------------------------------
class MLAWeights(NamedTuple):
    """Weight shards for the fused MLA decode (DeepSeek-V2).

    ``wq``    [D, q_local, (nope+rope)/N] — Q-Projection head-dim segment
    ``wdkv``  [D, (l+rope)/N]             — Down-Projection (latent) segment
    ``wuk``   [q_local, nope, l/N]        — K-up, absorbed into Q (out-seg)
    ``wuv``   [q_local, l/N, v]           — V-up, row (l) segment
    ``wo``    [q_local*v, D/N]            — Output-Projection segment
    """

    wq: jax.Array
    wdkv: jax.Array
    wuk: jax.Array
    wuv: jax.Array
    wo: jax.Array


def mla_attention(
    spec: ClusterSpec,
    x: jax.Array,                 # [B, D]
    w: MLAWeights,
    cache: KVBlock,               # latent cache: k=[S_blk, B, l+rope], v unused
    cache_len: jax.Array,
    *,
    nope_dim: int,
    rope_dim: int,
    rope_theta: float = 10000.0,
    norm_eps: float = 1e-6,       # fused pre-attention RMSNorm eps (packed
                                  # serve layout with ``ln1`` only)
) -> Tuple[jax.Array, KVBlock]:
    """Fused MLA decode per paper Alg. 4 (weight-absorbed, Fig. 14 right).

    Schedule (faithful): 3 ClusterGathers (q segments, latent-kv segments,
    up-projected q) + 3 ClusterReduces (flash stats/outputs in latent space,
    value-up partial sums, output tiles via the heads reduction).

    ``spec.backend == "pallas"`` routes the local stage (projections,
    K-up absorption, RoPE, latent flash partial) through the fused MLA
    kernel instead (:func:`_mla_attention_pallas`); the collectives and
    the value-up / Output-Projection tail are shared.

    ``w`` may also be :class:`PackedMLAWeights` (serving/prepack.py):
    the fully fused ``fuse_out="partial_o"`` path with the W_UV·W_O fold
    — one kernel + one fused ClusterReduce per layer, returning the
    FULL ``[B, D]`` output (no cluster gather needed).
    """
    if isinstance(w, PackedMLAWeights):
        assert spec.backend == "pallas", \
            "prepacked serve-layout weights require backend='pallas'"
        return _mla_attention_pallas_packed(
            spec, x, w, cache, cache_len, nope_dim=nope_dim,
            rope_dim=rope_dim, rope_theta=rope_theta, norm_eps=norm_eps)
    if spec.backend == "pallas":
        return _mla_attention_pallas(
            spec, x, w, cache, cache_len, nope_dim=nope_dim,
            rope_dim=rope_dim, rope_theta=rope_theta)
    n = spec.n_cluster
    b_rank = prim.axis_index(spec.cluster)
    B = x.shape[0]
    q_local = w.wq.shape[1]
    l_n = w.wuk.shape[2]
    l_rank = l_n * n
    v_dim = w.wuv.shape[2]
    scale = 1.0 / math.sqrt(nope_dim + rope_dim)

    # (2)–(4): segment Q and latent-KV projections, ClusterGather both.
    q_seg = jnp.einsum("bd,dqh->bqh", x, w.wq)         # [B,q,(nope+rope)/N]
    c_seg = x @ w.wdkv                                  # [B,(l+rope)/N]
    q_full = spec.gather_tiled(q_seg, axis=2)           # [B,q,nope+rope]
    c_full = spec.gather_tiled(c_seg, axis=1)           # [B,l+rope]
    q_nope, q_rope = q_full[..., :nope_dim], q_full[..., nope_dim:]
    c_lat, c_rope = c_full[..., :l_rank], c_full[..., l_rank:]

    # (5)–(6): Up-Projection segments (weight-absorbed q→latent), gather Q.
    q_lat_seg = jnp.einsum("bqn,qnl->bql", q_nope, w.wuk)   # [B,q,l/N]
    q_lat = spec.gather_tiled(q_lat_seg, axis=2)            # [B,q,l]

    q_rope = _apply_rope(q_rope, cache_len, rope_theta)
    c_rope = _apply_rope(c_rope, cache_len, rope_theta)

    # Append latent+rope entry to the owning rank's cache block.
    s_blk = cache.k.shape[0]
    ragged = jnp.ndim(cache_len) == 1
    ap = _append_slot(spec, s_blk, cache_len)
    entry = jnp.concatenate([c_lat, c_rope], axis=-1)       # [B, l+rope]
    ins = _insert_kv_ragged if ragged else _insert_kv
    cache = ins(cache, entry, entry[:, :1],                  # v-side unused
                ap.owner, ap.local_slot, ap.rank, cache_len)

    # (7): FlashDecoding partial in latent space over the local block,
    # bucketed over live blocks only (cost ∝ cache_len — DESIGN.md §3).
    # The score contracts the concatenated (latent ++ rope) dim; values
    # are the latent part, so o comes out in latent space.
    cc = cache.k.reshape(s_blk, B, l_rank + rope_dim).astype(jnp.float32)
    q_cat = jnp.concatenate([q_lat, q_rope], axis=-1).astype(jnp.float32)
    valid = (cache.pos >= 0) & (cache.pos <= cache_len)
    m_safe, l_stat, o, _ = bucketed_flash_attention(
        q_cat[:, None], cc[:, :, None, :], cc[:, :, None, :l_rank], valid,
        scale=scale, block_s=spec.block_s)
    m_safe, l_stat, o = m_safe[:, 0], l_stat[:, 0], o[:, 0]  # [B,q,(l)]

    # (8)–(10): ClusterReduce stats + outputs (online-softmax rescale).
    _, l_g, o_g = spec.flash_combine(m_safe, l_stat, o)
    a_lat = o_g / jnp.maximum(l_g[..., None], 1e-30)        # [B,q,l]

    # (11)–(12): value Up-Projection partial sums over l segments.
    a_seg = lax.dynamic_slice_in_dim(a_lat, b_rank * l_n, l_n, axis=2)
    o_head_part = jnp.einsum("bql,qlv->bqv", a_seg, w.wuv)
    o_head = spec.reduce(o_head_part, "sum")                # [B,q,v]

    # (13): Output-Projection tile + heads reduction (atomicAdd analogue).
    o_seg = o_head.reshape(B, q_local * v_dim).astype(x.dtype) @ w.wo
    o_seg = spec.heads_reduce(o_seg)                        # [B, D/N]
    return o_seg, cache


def _mla_attention_pallas(
    spec: ClusterSpec,
    x: jax.Array,
    w: MLAWeights,
    cache: KVBlock,
    cache_len: jax.Array,
    *,
    nope_dim: int,
    rope_dim: int,
    rope_theta: float,
) -> Tuple[jax.Array, KVBlock]:
    """Alg. 4 with the local stage as one fused Pallas kernel per rank.

    As in :func:`_split_token_attention_pallas`, the three activation
    ClusterGathers of Alg. 4 (q segments, latent-kv segments, absorbed q)
    hoist to their weight segments, so Q-Projection, Down-Projection,
    K-up absorption, RoPE and the latent-space flash partial all run in
    :func:`repro.kernels.fused_mla_decode.fused_mla_decode_attention`
    (``fuse_out=False``).  The ClusterReduce combine, the value
    Up-Projection partial sums and the Output-Projection tile stay
    between kernel invocations (paper Alg. 4 lines 8–13).
    """
    n = spec.n_cluster
    b_rank = prim.axis_index(spec.cluster)
    B, D = x.shape
    q_local = w.wq.shape[1]
    l_n = w.wuk.shape[2]
    l_rank = l_n * n
    v_dim = w.wuv.shape[2]
    from repro.kernels.fused_mla_decode.fused_mla_decode import (
        fused_mla_decode_attention)
    from repro.kernels.fused_decode.ops import rope_at

    # Weight-segment gathers replacing Alg. 4's activation gathers —
    # step-invariant; the prepacked serve layout hoists them to load time.
    tracecount.bump("weight_gather", 3)
    wq = spec.gather_tiled(w.wq, axis=2)      # [D, q_local, nope+rope]
    wdkv = spec.gather_tiled(w.wdkv, axis=1)  # [D, l_rank+rope]
    wuk = spec.gather_tiled(w.wuk, axis=2)    # [q_local, nope, l_rank]
    wq2 = wq.reshape(D, q_local * (nope_dim + rope_dim))

    cos, sin = rope_at(cache_len, rope_dim, rope_theta)
    s_blk = cache.k.shape[0]
    ragged = jnp.ndim(cache_len) == 1
    ap = _append_slot(spec, s_blk, cache_len)       # latent cache is linear
    blk = _fit_block_s(s_blk, spec.block_s)
    wo_unused = jnp.zeros((1, 1), x.dtype)   # value-up + O-proj after combine

    def one(xb, cb, cl, cosb, sinb, posb, inc):
        acc, c_new, m, l = fused_mla_decode_attention(
            xb[None], wq2, wdkv, wuk, w.wuv, wo_unused, cb, cl,
            cosb, sinb, q_heads=q_local, nope=nope_dim, rope_d=rope_dim,
            l_rank=l_rank, v_dim=v_dim, block_s=blk, fuse_out=False,
            interpret=spec.interpret, pos=posb,
            include_new=inc, pos_base=ap.pos_base)
        return acc[0], c_new[0], m[0], l[0]

    kern_axes = (0, 1, 0, 0, 0, 1, 0) if ragged \
        else (0, 1, None, None, None, None, None)
    acc, c_new, m, l = jax.vmap(one, in_axes=kern_axes)(
        x, cache.k, cache_len, cos, sin, cache.pos, ap.include_new)

    # Append the kernel-emitted latent entry on the owning rank.
    ins = _insert_kv_ragged if ragged else _insert_kv
    cache = ins(cache, c_new, c_new[:, :1],              # v-side unused
                ap.owner, ap.local_slot, ap.rank, cache_len)

    # (8)–(13): combine, value Up-Projection partials, O-Projection tile.
    _, l_g, o_g = spec.flash_combine(m, l, acc)
    a_lat = o_g / jnp.maximum(l_g[..., None], 1e-30)     # [B,q,l]
    a_seg = lax.dynamic_slice_in_dim(a_lat, b_rank * l_n, l_n, axis=2)
    o_head_part = jnp.einsum("bql,qlv->bqv", a_seg, w.wuv)
    o_head = spec.reduce(o_head_part, "sum")             # [B,q,v]
    o_seg = o_head.reshape(B, q_local * v_dim).astype(x.dtype) @ w.wo
    o_seg = spec.heads_reduce(o_seg)                     # [B, D/N]
    return o_seg, cache


def _mla_attention_pallas_packed(
    spec: ClusterSpec,
    x: jax.Array,
    w: PackedMLAWeights,
    cache: KVBlock,
    cache_len: jax.Array,
    *,
    nope_dim: int,
    rope_dim: int,
    rope_theta: float,
    norm_eps: float = 1e-6,
) -> Tuple[jax.Array, KVBlock]:
    """Alg. 4 on prepacked serve-layout weights — fully fused.  Returns
    ``(o [B, D], cache)``; no cluster gather follows.

    All of Alg. 4's weight-segment gathers happened at load time, the
    value Up-Projection and Output-Projection are folded into one
    full-width per-head matrix (``wproj = W_UV · W_O``) applied INSIDE
    the kernel on the unnormalized latent accumulator, and Alg. 4's
    value-up partial-sum ClusterReduce (lines 11–12) vanishes.  Per
    layer: one kernel + one fused ClusterReduce + local
    normalize/head-sum + the heads-axis reduction.
    """
    B, D = x.shape
    q_local, _, l_rank = w.wuk.shape
    d_out = w.wproj.shape[-1]
    from repro.kernels.fused_mla_decode.fused_mla_decode import (
        fused_mla_decode_attention)
    from repro.kernels.fused_decode.ops import rope_at

    cos, sin = rope_at(cache_len, rope_dim, rope_theta)
    s_blk = cache.k.shape[0]
    ragged = jnp.ndim(cache_len) == 1
    ap = _append_slot(spec, s_blk, cache_len)       # latent cache is linear
    blk = _fit_block_s(s_blk, spec.block_s)
    wo_unused = jnp.zeros((1, 1), x.dtype)

    def one(xb, cb, cl, cosb, sinb, posb, inc):
        acc, c_new, m, l = fused_mla_decode_attention(
            xb[None], w.wq, w.wdkv, w.wuk, w.wproj, wo_unused, cb,
            cl, cosb, sinb, q_heads=q_local, nope=nope_dim,
            rope_d=rope_dim, l_rank=l_rank, v_dim=d_out, block_s=blk,
            fuse_out="partial_o", interpret=spec.interpret, pos=posb,
            include_new=inc, pos_base=ap.pos_base,
            norm_scale=w.ln1, norm_eps=norm_eps)
        return acc[0], c_new[0], m[0], l[0]

    kern_axes = (0, 1, 0, 0, 0, 1, 0) if ragged \
        else (0, 1, None, None, None, None, None)
    acc, c_new, m, l = jax.vmap(one, in_axes=kern_axes)(
        x, cache.k, cache_len, cos, sin, cache.pos, ap.include_new)

    ins = _insert_kv_ragged if ragged else _insert_kv
    cache = ins(cache, c_new, c_new[:, :1],              # v-side unused
                ap.owner, ap.local_slot, ap.rank, cache_len)

    # ONE fused ClusterReduce over (m, l, projected tiles); normalize per
    # head and sum over this rank's heads.
    tracecount.bump("cluster_combine")
    _, l_g, p_g = prim.cluster_flash_combine(m, l, acc, spec.cluster,
                                             fused=True)
    o_full = (p_g / jnp.maximum(l_g[..., None], 1e-30)).sum(axis=1)
    o_full = spec.heads_reduce(o_full.astype(x.dtype))   # [B, D]
    return o_full, cache


# ---------------------------------------------------------------------------
# DSMEM-traffic totals per dataflow (paper §3.2 + App. B) — bytes
# ---------------------------------------------------------------------------
def traffic_split_token(head_dim: int, model_dim: int, n: int,
                        bytes_per_el: int = 2, batch: int = 1) -> float:
    """Alg. 3 total: Reduce(3h… — paper text) — we follow the corrected
    App. B formula ``Traffic_Reduce(H, N) + Traffic_Gather(3h, N)`` with
    h = head_dim/N segments and H = head_dim (the per-head attention output
    reduced across the cluster)."""
    h_seg = head_dim / n * 3 * bytes_per_el * batch
    red = head_dim * bytes_per_el * batch
    return prim.traffic_gather(h_seg, n) + prim.traffic_reduce(red, n)


def traffic_split_head(seq_len: int, model_dim: int, n: int,
                       bytes_per_el: int = 4, batch: int = 1) -> float:
    """Alg. 5 total: ``Traffic_Reduce(S, N) + Traffic_Reduce(D, N)``."""
    return (prim.traffic_reduce(seq_len * bytes_per_el * batch, n)
            + prim.traffic_reduce(model_dim * bytes_per_el * batch, n))


def traffic_mla(head_dim: int, l_rank: int, total_head_dim: int, n: int,
                bytes_per_el: int = 2, batch: int = 1) -> float:
    """Alg. 4 total: ``Gather(h) + 2·Gather(l) + Reduce(l) + Reduce(H)``."""
    b = bytes_per_el * batch
    return (prim.traffic_gather(head_dim / n * b, n)
            + 2 * prim.traffic_gather(l_rank / n * b, n)
            + prim.traffic_reduce(l_rank * b, n)
            + prim.traffic_reduce(total_head_dim * b, n))
