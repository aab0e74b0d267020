"""Fused QKV-Projection + FlashDecoding-Attention + Output-Projection
decode kernel — the TPU realization of the paper's expanded fusion scope
(DESIGN.md §2, Level 1).

One ``pallas_call`` per decode layer, on a sequential 1-D grid of three
phases:

* *projection* steps ``[0, n_p)`` — each streams one column tile of
  ``wqkv`` (whole heads, at most :data:`WEIGHT_TILE_BYTES`) and computes
  that slice of the new token's q/k/v from the (optionally RMS-normed)
  hidden state; the last one applies RoPE and lays q/k/v out per kv head
  in VMEM scratch — the analogue of the cluster's ClusterGather'd q/k/v
  in SMEM;
* *attention* steps ``[n_p, n_p + n_blocks)`` — FlashDecoding partial
  over one KV-cache block per step, online-softmax accumulators carried
  in VMEM scratch (the sequential analogue of ClusterReduce over
  concurrent blocks);
* *output* steps — the first folds in the new token's own (k, v) and
  finalizes the softmax statistics; each then streams one head tile of
  ``wo`` and projects those heads (one HBM write per tile).

Weights never sit whole in VMEM: at published widths ``wqkv``/``wo`` are
tens of MiB, several times the chip's scoped VMEM.  Every in-kernel
matmul is 2-D (GQA query heads of one kv head fold into rows), and the
streamed operand reaches the MXU in its stored dtype with f32
accumulation (:func:`_mxu_dot`).

* HBM traffic = weights + **live prefix of** the KV cache + x + o (+ the
  k/v append, which the paper also pays) — no intermediate
  materialization, exactly the SplitToken property.  The scalar-prefetched
  block index map is clamped with ``cache_len``: grid steps beyond the
  live prefix re-address the already-resident block, so the pipeline
  issues no new HBM copies for dead blocks, and the ``@pl.when`` guard
  skips their compute.  Decode cost is therefore proportional to
  ``cache_len``, not to the allocated ``S`` (DESIGN.md §3).  Ragged
  batches ``vmap`` the kernel per slot with the scalar-prefetch operand
  batched, so the clamp and the rank-local live-span cull are
  **per-slot**: a retired slot (``cache_len ≤ 0``) runs zero attend
  steps while its batch neighbors keep streaming (DESIGN.md §6).
* interior blocks that are provably fully live (linear slot layout,
  no sliding window) take a mask-free fast path — no compare/select on
  the hot loop.

Cache slots carry explicit positions (``pos``; −1 ⇒ empty), which makes
full, sliding-window and ring caches uniform with the XLA dataflow's
``KVBlock.pos`` convention.  When the caller does not pass ``pos`` the
kernel assumes the linear layout ``pos[i] = i``.

Three modes:
* ``fuse_out=True``  — returns ``o [B, D_out]`` (O-projection fused);
  for single-chip-per-head-group layouts (cluster == 1).
* ``fuse_out=False`` — returns unnormalized ``(acc, m, l)`` partials for
  the cross-chip ClusterReduce combine (DESIGN.md §2, Level 2); the
  O-projection then runs after the combine, as in paper Alg. 3 lines 5–8.
  ``include_new`` gates the new token's own attention contribution so
  that, across a cluster, exactly the rank owning the append slot counts
  it.
* ``fuse_out="partial_o"`` — the Output-Projection tile runs INSIDE the
  kernel on the *unnormalized* accumulator, per head: with ``wo`` passed
  as 3-D per-head tiles ``[q_loc, hd, d_out]`` the kernel emits
  ``o [B, q_loc, d_out]`` projected partials plus ``(m, l)``.  Because
  the projection is linear per head, the flash-merge operator remains
  exact on ``(m, l, o)`` triples, so across a cluster the layer
  completes with exactly ONE fused ClusterReduce followed by a local
  normalize-and-sum-over-heads — the full Alg. 3 fusion scope.  The
  serve layout passes FULL-width rows (d_out = D) so every cluster
  rank's partial lives in the same output basis (DESIGN.md §2,
  serving/prepack.py).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import tracecount

# Largest streamed weight tile (bytes, one buffer; the pipeline holds
# two).  Sized so the attention kernels' weight, KV and scratch buffers
# together stay well inside VMEM_LIMIT_BYTES at published widths.
WEIGHT_TILE_BYTES = 2 * 2**20
# Scoped-VMEM limit the attention kernels ask Mosaic for.  The default
# scoped limit on a TPU v5e is 16 MiB of the core's 128 MiB VMEM.
VMEM_LIMIT_BYTES = 48 * 2**20

_NN = (((1,), (0,)), ((), ()))      # [M, K] @ [K, N]
_NT = (((1,), (1,)), ((), ()))      # [M, K] @ [N, K]ᵀ


def _mxu_dot(a, w, dims=_NN, *, exact: bool = False):
    """``a · w`` with f32 accumulation, feeding the MXU ``w``'s own dtype.

    ``a`` is f32.  Unless it is exactly representable in ``w.dtype``
    (``exact``), it is split into a high and a low ``w.dtype`` part fed in
    two passes — about 16 mantissa bits of ``a``, close to an f32 product,
    without upcasting the streamed ``w`` tile."""
    if w.dtype == jnp.float32:
        return lax.dot_general(a, w, dims, preferred_element_type=jnp.float32)
    hi = a.astype(w.dtype)
    out = lax.dot_general(hi, w, dims, preferred_element_type=jnp.float32)
    if not exact:
        lo = (a - hi.astype(jnp.float32)).astype(w.dtype)
        out = out + lax.dot_general(lo, w, dims,
                                    preferred_element_type=jnp.float32)
    return out


def head_tile(n_heads: int, head_bytes: int, head_width: int, align: int,
              budget: int = WEIGHT_TILE_BYTES) -> int:
    """Heads per streamed weight tile: the most that fit ``budget`` and
    divide ``n_heads``, where a partial tile's tiled extent
    (``th · head_width``) must be a multiple of ``align`` (128 for a lane
    dim, 8 for a sublane dim, 1 for an untiled one).  Falls back to the
    smallest legal tile when none fits."""
    legal = [t for t in range(1, n_heads + 1) if n_heads % t == 0
             and (t == n_heads or (t * head_width) % align == 0)]
    fit = [t for t in legal if t * head_bytes <= budget]
    return max(fit) if fit else min(legal)


def _rope(t, cos, sin):
    half = t.shape[-1] // 2
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                           axis=-1)


def _kernel(scalars_ref,                         # scalar prefetch (SMEM):
                                                 # [cache_len, include_new,
                                                 #  pos_base]
            x_ref, wqkv_ref, bqkv_ref, wo_ref, cos_ref, sin_ref, norm_ref,
            k_blk_ref, v_blk_ref, pos_blk_ref,
            o_ref, k_new_ref, v_new_ref, m_out_ref, l_out_ref,
            xn_s, qkv_s, q_s, kn_s, vn_s, m_s, l_s, acc_s, ah_s, oacc_s,
            *, blk_s: int, n_blocks: int, q_loc: int, kv_loc: int,
            hd: int, scale: float, cap: float, window: int, ring: bool,
            fuse_out, fuse_norm: bool, norm_eps: float, n_p: int,
            th_p: int, n_o: int, th_o: int):
    j = pl.program_id(0)
    cache_len = scalars_ref[0]
    B = x_ref.shape[0]
    qpk = q_loc // kv_loc
    a0 = n_p                      # first attention step
    f0 = n_p + n_blocks           # finalize + first output step

    # ---------------- projection phase: one wqkv column tile per step ----
    @pl.when(j == 0)
    def _init():
        x = x_ref[...].astype(jnp.float32)               # [B, D]
        if fuse_norm:
            # Pre-attention RMSNorm fused into the projection phase: the
            # RAW residual stream crosses HBM; the normed copy exists only
            # in VMEM.  The dtype round-trip reproduces the XLA oracle's
            # rms_norm output exactly (it returns x.dtype).
            g = norm_ref[...].astype(jnp.float32)        # [1, D] scale
            var = jnp.mean(x * x, axis=-1, keepdims=True)
            x = x * jax.lax.rsqrt(var + norm_eps) * (1.0 + g)
            x = x.astype(x_ref.dtype).astype(jnp.float32)
        xn_s[...] = x
        m_s[...] = jnp.full_like(m_s[...], -1e30)
        l_s[...] = jnp.zeros_like(l_s[...])
        acc_s[...] = jnp.zeros_like(acc_s[...])

    @pl.when(j < n_p)
    def _proj():
        # x holds x.dtype values, so it is exact in a same-dtype weight
        qkv_s[j] = _mxu_dot(xn_s[...], wqkv_ref[...],
                            exact=x_ref.dtype == wqkv_ref.dtype)

    @pl.when(j == n_p - 1)
    def _split():
        # bias + RoPE (at position cache_len; cos/sin precomputed outside)
        # and the per-kv-head layout: q rows b·qpk + r of slab k hold
        # query head k·qpk + r of batch row b
        cos = cos_ref[...].astype(jnp.float32)           # [1, hd//2]
        sin = sin_ref[...].astype(jnp.float32)

        def head(h):
            t, off = divmod(h, th_p)
            return (qkv_s[t, :, off * hd:(off + 1) * hd]
                    + bqkv_ref[:, h * hd:(h + 1) * hd].astype(jnp.float32))

        for k in range(kv_loc):
            for r in range(qpk):
                q = _rope(head(k * qpk + r), cos, sin)   # [B, hd]
                for b in range(B):
                    q_s[k, b * qpk + r:b * qpk + r + 1, :] = q[b:b + 1]
            kn = _rope(head(q_loc + k), cos, sin)
            vn = head(q_loc + kv_loc + k)
            kn_s[k] = kn
            vn_s[k] = vn
            k_new_ref[k] = kn.astype(k_new_ref.dtype)
            v_new_ref[k] = vn.astype(v_new_ref.dtype)

    # ---------------- attention phase: FlashDecoding over cache blocks ----
    blk_start = (j - a0) * blk_s
    pos_base = scalars_ref[2]
    # Rank-local live span: linear slots hold position pos_base + index,
    # so this rank's live prefix ends at cache_len − pos_base (a non-owner
    # rank whose shard starts beyond cache_len has NO live slots and runs
    # no attend steps).  Ring slot i maps to a global ring slot ≥ i, first
    # written once cache_len exceeds it, so the same bound is a valid
    # (conservative) cull there, with pos_base = −1 ⇒ eff = cache_len.
    eff_len = cache_len - jnp.maximum(pos_base, 0)
    in_range = (j >= a0) & (j < f0) & (blk_start < eff_len)
    if ring:
        # Ring cache: slot offsets are NOT positions once wrapped, so the
        # window bound cannot cull by offset — every resident block may
        # hold in-window entries; the stored-pos mask does the exact cut.
        live = in_range
    else:
        lo = cache_len - window - jnp.maximum(pos_base, 0) \
            if window > 0 else -1
        live = in_range & (blk_start + blk_s > lo)
    # Mask-free fast path: slots are position-linear (pos_base >= 0, i.e.
    # pos[i] = pos_base + i) and the whole block is inside the live prefix.
    full = (live & (pos_base >= 0)
            & (pos_base + blk_start + blk_s <= cache_len)
            & (window == 0))

    def _attend(masked: bool):
        valid = None
        if masked:
            pos = pos_blk_ref[...]                       # [1, blk]
            valid = (pos >= 0) & (pos < cache_len)
            if window > 0:
                valid &= pos > cache_len - window
        for k in range(kv_loc):
            kb = k_blk_ref[:, k * hd:(k + 1) * hd]        # [blk, hd]
            vb = v_blk_ref[:, k * hd:(k + 1) * hd]
            s = _mxu_dot(q_s[k], kb, _NT) * scale        # [B·qpk, blk]
            if cap > 0:
                s = jnp.tanh(s / cap) * cap
            if masked:
                s = jnp.where(valid, s, -1e30)
            m_prev, l_prev = m_s[k], l_s[k]              # [B·qpk, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            if masked:
                p = jnp.where(valid, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            m_s[k] = m_new
            l_s[k] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_s[k] = acc_s[k] * corr + _mxu_dot(p, vb)

    @pl.when(full)
    def _attend_full():
        _attend(masked=False)

    @pl.when(live & jnp.logical_not(full))
    def _attend_masked():
        _attend(masked=True)

    # ---------------- output phase: new-token KV, finalize, O-proj -------
    def rows(v):
        """[B, hd] per batch row → [B·qpk, hd] in the q_s row order."""
        if B == 1:
            return v
        return jnp.concatenate(
            [jnp.broadcast_to(v[b:b + 1], (qpk, v.shape[-1]))
             for b in range(B)], axis=0)

    @pl.when(j == f0)
    def _finalize():
        # append the new token's (k, v) contribution from scratch; across a
        # cluster only the slot-owning rank counts it (include_new).
        include_new = scalars_ref[1] > 0
        for k in range(kv_loc):
            q = q_s[k]                                   # [B·qpk, hd]
            s = jnp.sum(q * rows(kn_s[k]), axis=-1, keepdims=True) * scale
            if cap > 0:
                s = jnp.tanh(s / cap) * cap
            s = jnp.where(include_new, s, -1e30)
            m_prev, l_prev = m_s[k], l_s[k]
            m_new = jnp.maximum(m_prev, s)
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_fin = l_prev * corr + p
            acc = acc_s[k] * corr + p * rows(vn_s[k])
            m_out_ref[k] = m_new
            l_out_ref[k] = l_fin
            if fuse_out is True:
                # max guard: a fully inactive slot (empty cache, include_new
                # gated off — ragged scheduler free slots) has l == 0; emit
                # 0, not NaN (the partial modes defer the divide).
                acc = acc / jnp.maximum(l_fin, 1e-30)
            # head-major copy (head k·qpk + r) for the output steps
            dst = o_ref if fuse_out is False else ah_s
            for r in range(qpk):
                for b in range(B):
                    row = acc[b * qpk + r:b * qpk + r + 1]
                    dst[k * qpk + r, b:b + 1, :] = row.astype(dst.dtype)

    if fuse_out is False:
        return

    @pl.when(j >= f0)
    def _project():
        t = j - f0
        if fuse_out == "partial_o":
            # per-head Output-Projection of the UNNORMALIZED accumulator:
            # o[b, h, :] = Σ_d acc[b, h, d] · wo[h, d, :].  Linear per head,
            # so the cross-chip flash merge on (m, l, o) stays exact and
            # the normalization (÷ l_g) + head sum happen after ONE
            # ClusterReduce.
            for i in range(th_o):
                o_ref[0, i * B:(i + 1) * B, :] = _mxu_dot(
                    ah_s[t * th_o + i], wo_ref[i])
        else:
            @pl.when(t == 0)
            def _zero():
                oacc_s[...] = jnp.zeros_like(oacc_s[...])

            for i in range(th_o):
                oacc_s[...] += _mxu_dot(ah_s[t * th_o + i],
                                        wo_ref[i * hd:(i + 1) * hd, :])

            @pl.when(t == n_o - 1)
            def _write():
                o_ref[...] = oacc_s[...].astype(o_ref.dtype)


def _live_block_bounds(cache_len, blk_s: int, n_blocks: int, window: int,
                       ring: bool = False, pos_base=0):
    """[lo, hi] inclusive block-index range the pipeline may address.

    Blocks outside it are dead (wholly beyond the live prefix, or wholly
    below the sliding window); the index map clamps into this range so
    dead grid steps re-address a resident block instead of issuing a new
    HBM copy.  Exposed at module level so tests can assert the maps stop
    advancing past the live prefix.

    ``pos_base`` rank-localizes the bounds on a sharded linear cache
    (slot i holds position pos_base + i): a rank whose shard starts past
    ``cache_len`` addresses only block 0.  ``ring=True`` (wrapped slot
    layout, pos_base < 0): offsets are not positions, so only the
    fill-order upper bound applies — slot i is first written when
    ``cache_len`` exceeds its global ring slot (≥ i), hence blocks with
    ``blk_start >= cache_len`` are still provably unwritten.
    """
    cache_len = jnp.asarray(cache_len, jnp.int32)
    eff = cache_len - jnp.maximum(jnp.asarray(pos_base, jnp.int32), 0)
    hi = jnp.clip((eff + blk_s - 1) // blk_s - 1, 0, n_blocks - 1)
    if window > 0 and not ring:
        lo = jnp.clip((eff - window) // blk_s, 0, hi)
    else:
        lo = jnp.zeros_like(hi)
    return lo, hi


def _cache_block_index(j, cache_len, *, blk_s: int, n_blocks: int,
                       window: int, ring: bool = False, pos_base=0):
    """Block index fetched at grid step ``j`` (step 0 is the projection
    phase; steps 1..n_blocks are attention; the final step re-addresses
    the last live block)."""
    lo, hi = _live_block_bounds(cache_len, blk_s, n_blocks, window, ring,
                                pos_base)
    return jnp.clip(j - 1, lo, hi)


def fused_decode_attention(
    x: jax.Array,                 # [B, D]
    wqkv: jax.Array,              # [D, (q_loc + 2 kv_loc) * hd]
    bqkv: Optional[jax.Array],    # [(q_loc + 2 kv_loc) * hd] or None
    wo: jax.Array,                # [q_loc * hd, D_out]; [q_loc, hd, d_out]
                                  # per-head tiles when fuse_out="partial_o"
    k_cache: jax.Array,           # [S, kv_loc, hd]
    v_cache: jax.Array,           # [S, kv_loc, hd]
    cache_len: jax.Array,         # scalar int32: tokens already cached
    cos: jax.Array,               # [hd//2] RoPE at position cache_len
    sin: jax.Array,
    *,
    q_heads: int,
    kv_heads: int,
    scale: Optional[float] = None,
    attn_softcap: float = 0.0,
    window: int = 0,
    ring: bool = False,   # slots wrap (pos ≠ index): window culls by stored
                          # pos only, never by block offset
    block_s: int = 512,
    fuse_out=True,        # True | False | "partial_o"
    interpret: bool = False,
    pos: Optional[jax.Array] = None,          # [S] slot positions (−1 empty)
    include_new: Optional[jax.Array] = None,  # count the new token's own
                                              # attention (cluster: owner only)
    pos_base: Optional[jax.Array] = None,     # pos[i] = pos_base + i when the
                                              # layout is linear; −1 otherwise
    norm_scale: Optional[jax.Array] = None,   # [D] fused pre-attention
                                              # RMSNorm scale; None = caller
                                              # pre-normed x (legacy)
    norm_eps: float = 1e-6,
    weight_tile_bytes: int = WEIGHT_TILE_BYTES,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Returns ``(o, k_new, v_new, m, l)``.

    ``fuse_out=True``: o = [B, D_out] (final).  ``fuse_out=False``:
    o = [B, q_loc, hd] *unnormalized* accumulator; combine across chips
    with ``cluster_flash_combine`` and project afterwards.
    ``fuse_out="partial_o"``: o = [B, q_loc, d_out] *unnormalized*
    per-head Output-Projection tiles (``wo`` must be ``[q_loc, hd,
    d_out]``); flash-merge the (m, l, o) triple across chips, then
    normalize per head and sum over heads — one ClusterReduce total.

    ``weight_tile_bytes`` caps one streamed weight tile (whole heads;
    :func:`head_tile`); the default suits the chip, and tests shrink it
    to force several tiles at small widths.
    """
    tracecount.bump("pallas_kernel")
    B, D = x.shape
    S, kv_loc, hd = k_cache.shape
    q_loc = q_heads
    assert kv_loc == kv_heads
    qpk = q_loc // kv_loc
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    blk_s = min(block_s, S)
    assert S % blk_s == 0, (S, blk_s)
    n_blocks = S // blk_s
    if fuse_out == "partial_o":
        assert wo.ndim == 3 and wo.shape[:2] == (q_loc, hd), \
            ("partial_o needs per-head wo tiles [q_loc, hd, d_out]",
             wo.shape, q_loc, hd)
    d_out = wo.shape[-1]
    P = wqkv.shape[1]
    if bqkv is None:
        bqkv = jnp.zeros((P,), wqkv.dtype)
    if pos is None:
        pos = jnp.arange(S, dtype=jnp.int32)
        if pos_base is None:
            pos_base = jnp.int32(0)
    if pos_base is None:
        pos_base = jnp.int32(-1)
    if include_new is None:
        include_new = jnp.int32(1)
    scalars = jnp.stack([
        jnp.asarray(cache_len, jnp.int32).reshape(()),
        jnp.asarray(include_new, jnp.int32).reshape(()),
        jnp.asarray(pos_base, jnp.int32).reshape(()),
    ])
    fuse_norm = norm_scale is not None
    norm_op = (jnp.asarray(norm_scale, jnp.float32).reshape(1, D)
               if fuse_norm else jnp.zeros((1, 1), jnp.float32))

    # streamed weight tiles: whole heads of wqkv columns / wo rows
    isz = jnp.dtype(wqkv.dtype).itemsize
    th_p = head_tile(P // hd, D * hd * isz, hd, 128, weight_tile_bytes)
    n_p = P // (th_p * hd)
    if fuse_out is False:
        th_o, n_o = q_loc, 1
    else:
        th_o = head_tile(q_loc, hd * d_out * jnp.dtype(wo.dtype).itemsize,
                         hd, 1 if fuse_out == "partial_o" else 8,
                         weight_tile_bytes)
        n_o = q_loc // th_o
    f0 = n_p + n_blocks

    kernel = functools.partial(
        _kernel, blk_s=blk_s, n_blocks=n_blocks, q_loc=q_loc, kv_loc=kv_loc,
        hd=hd, scale=scale, cap=attn_softcap, window=window, ring=ring,
        fuse_out=fuse_out, fuse_norm=fuse_norm, norm_eps=norm_eps,
        n_p=n_p, th_p=th_p, n_o=n_o, th_o=th_o)

    def out_tile(j):
        return jnp.clip(j - f0, 0, n_o - 1)

    if fuse_out == "partial_o":
        wo_spec = pl.BlockSpec((th_o, hd, d_out),
                               lambda j, *_: (out_tile(j), 0, 0))
        o_shape, o_block = (n_o, th_o * B, d_out), (1, th_o * B, d_out)
        o_map = lambda j, *_: (out_tile(j), 0, 0)
    elif fuse_out:
        wo_spec = pl.BlockSpec((th_o * hd, d_out),
                               lambda j, *_: (out_tile(j), 0))
        o_shape = o_block = (B, d_out)
        o_map = lambda j, *_: (0, 0)
    else:
        wo = jnp.zeros((1, 1), x.dtype)           # O-proj runs after combine
        wo_spec = pl.BlockSpec((1, 1), lambda j, *_: (0, 0))
        o_shape = o_block = (q_loc, B, hd)
        o_map = lambda j, *_: (0, 0, 0)

    def cache_map(j, s_ref):
        b = _cache_block_index(j - n_p + 1, s_ref[0], blk_s=blk_s,
                               n_blocks=n_blocks, window=window, ring=ring,
                               pos_base=s_ref[2])
        return (b, 0)

    def pos_map(j, s_ref):
        return (0, cache_map(j, s_ref)[0])

    R = B * qpk
    const2 = lambda j, *_: (0, 0)
    const3 = lambda j, *_: (0, 0, 0)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_p + n_blocks + n_o,),
            in_specs=[
                pl.BlockSpec((B, D), const2),                           # x
                pl.BlockSpec((D, th_p * hd),
                             lambda j, *_: (0, jnp.minimum(j, n_p - 1))),
                pl.BlockSpec((1, P), const2),                           # bqkv
                wo_spec,                                                # wo
                pl.BlockSpec((1, hd // 2), const2),                     # cos
                pl.BlockSpec((1, hd // 2), const2),                     # sin
                pl.BlockSpec(norm_op.shape, const2),                    # ln1
                pl.BlockSpec((blk_s, kv_loc * hd), cache_map),          # k
                pl.BlockSpec((blk_s, kv_loc * hd), cache_map),          # v
                pl.BlockSpec((1, blk_s), pos_map),                      # pos
            ],
            out_specs=[
                pl.BlockSpec(o_block, o_map),
                pl.BlockSpec((kv_loc, B, hd), const3),
                pl.BlockSpec((kv_loc, B, hd), const3),
                pl.BlockSpec((kv_loc, R, 1), const3),
                pl.BlockSpec((kv_loc, R, 1), const3),
            ],
            scratch_shapes=[
                pltpu.VMEM((B, D), jnp.float32),                # normed x
                pltpu.VMEM((n_p, B, th_p * hd), jnp.float32),   # qkv tiles
                pltpu.VMEM((kv_loc, R, hd), jnp.float32),       # q
                pltpu.VMEM((kv_loc, B, hd), jnp.float32),       # k_new
                pltpu.VMEM((kv_loc, B, hd), jnp.float32),       # v_new
                pltpu.VMEM((kv_loc, R, 1), jnp.float32),        # m
                pltpu.VMEM((kv_loc, R, 1), jnp.float32),        # l
                pltpu.VMEM((kv_loc, R, hd), jnp.float32),       # acc
                pltpu.VMEM((q_loc, B, hd), jnp.float32),        # acc by head
                pltpu.VMEM((B, d_out) if fuse_out is True else (1, 1),
                           jnp.float32),                        # o acc
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(o_shape, x.dtype if fuse_out is True
                                 else jnp.float32),
            jax.ShapeDtypeStruct((kv_loc, B, hd), k_cache.dtype),
            jax.ShapeDtypeStruct((kv_loc, B, hd), v_cache.dtype),
            jax.ShapeDtypeStruct((kv_loc, R, 1), jnp.float32),
            jax.ShapeDtypeStruct((kv_loc, R, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="fused_decode",
    )(scalars,
      x, wqkv, bqkv.reshape(1, -1), wo,
      cos.reshape(1, -1), sin.reshape(1, -1), norm_op,
      k_cache.reshape(S, kv_loc * hd), v_cache.reshape(S, kv_loc * hd),
      jnp.asarray(pos, jnp.int32).reshape(1, S))
    o, k_new, v_new, m, l = out
    if fuse_out == "partial_o":
        o = o.reshape(q_loc, B, d_out).transpose(1, 0, 2)
    elif fuse_out is False:
        o = o.transpose(1, 0, 2)

    def per_head(t):                   # [kv, B·qpk, 1] → [B, q_loc]
        return t.reshape(kv_loc, B, qpk).transpose(1, 0, 2).reshape(B, q_loc)

    return (o, k_new.transpose(1, 0, 2), v_new.transpose(1, 0, 2),
            per_head(m), per_head(l))
