"""Fused weight-absorbed MLA decode kernel (paper Alg. 4, Level-1 TPU form).

Phases (one ``pallas_call``, sequential 1-D grid):
  projection steps ``[0, n_p)``: Down-Projection and RoPE of the new
      latent entry (first step), then one head tile of Q-Projection +
      K-up absorption (q_lat = q_nope·W_UK) + RoPE per step, streaming
      ``wq``/``wuk`` tiles; all results stay in VMEM scratch.
  attention steps: FlashDecoding in *latent space* over the compressed cache
      (this is MLA's whole point — the cache is [S, l+rope] shared by all
      heads, MQA-style).  The block index map is clamped with ``cache_len``
      (scalar prefetch), so grid steps beyond the live prefix re-address
      the resident block — HBM traffic is proportional to ``cache_len``,
      not the allocated ``S`` (DESIGN.md §3) — and interior fully-live
      blocks take a mask-free fast path.
  output steps: the first adds the new entry's contribution (gated by
      ``include_new`` — across a cluster only the append-slot owner counts
      it) and finalizes the online softmax; each then streams one head
      tile of the value Up-Projection (A·W_UV) / Output-Projection
      weights.  Weights never sit whole in VMEM, and every in-kernel
      matmul is 2-D (heads fold into rows).

Cache slots carry explicit positions (``pos``; −1 ⇒ empty) matching the
XLA dataflow's ``KVBlock.pos`` convention; without ``pos`` the linear
layout ``pos[i] = i`` is assumed.

Three modes:
* ``fuse_out=True``  — returns final ``o [B, D_out]``.
* ``fuse_out=False`` — returns the *unnormalized* latent flash partials
  ``acc [B, q, l_rank]`` plus ``(m, l)`` for the cross-chip
  ClusterReduce combine (paper Alg. 4 lines 8–10); the value
  Up-Projection and Output-Projection then run after the combine.
* ``fuse_out="partial_o"`` — value Up-Projection AND Output-Projection
  fused into the kernel: ``wuv`` carries the prepacked per-head product
  ``W_UV · W_O(cols)`` (``[q, l_rank, d_out]``, serving/prepack.py) and
  the kernel emits unnormalized projected tiles ``o [B, q, d_out]``.
  The projection is linear per head, so the flash merge on ``(m, l, o)``
  stays exact: ONE fused ClusterReduce, then a local normalize + head
  sum, completes the layer — and Alg. 4's value-up partial-sum
  ClusterReduce (lines 11–12) disappears entirely.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import tracecount
from repro.kernels.fused_decode.fused_decode import (
    _NT, VMEM_LIMIT_BYTES, WEIGHT_TILE_BYTES, _cache_block_index, _mxu_dot,
    _rope, head_tile)


def _kernel(scalars_ref,          # [cache_len, include_new, pos_base] (SMEM)
            x_ref, wq_ref, wdkv_ref, wuk_ref, wuv_ref, wo_ref,
            cos_ref, sin_ref, norm_ref, c_blk_ref, pos_blk_ref,
            o_ref, c_new_ref, m_out_ref, l_out_ref,
            xn_s, cn_s, qt_s, q_s, m_s, l_s, acc_s, ah_s, oacc_s,
            *, blk_s: int, n_blocks: int, q_loc: int, nope: int,
            rope_d: int, l_rank: int, v_dim: int, scale: float,
            fuse_out, fuse_norm: bool, norm_eps: float, n_p: int,
            th_p: int, n_o: int, th_o: int):
    j = pl.program_id(0)
    cache_len = scalars_ref[0]
    B = x_ref.shape[0]
    a0 = n_p
    f0 = n_p + n_blocks
    hq = nope + rope_d

    # ---------------- projection phase ---------------------------------
    @pl.when(j == 0)
    def _init():
        x = x_ref[...].astype(jnp.float32)                   # [B, D]
        if fuse_norm:
            # fused pre-attention RMSNorm (raw residual stream crossed
            # HBM; dtype round-trip matches the XLA oracle's rms_norm)
            g = norm_ref[...].astype(jnp.float32)            # [1, D]
            var = jnp.mean(x * x, axis=-1, keepdims=True)
            x = x * jax.lax.rsqrt(var + norm_eps) * (1.0 + g)
            x = x.astype(x_ref.dtype).astype(jnp.float32)
        xn_s[...] = x
        c = _mxu_dot(x, wdkv_ref[...],
                     exact=x_ref.dtype == wdkv_ref.dtype)    # [B, l+r]
        cos = cos_ref[...].astype(jnp.float32)               # [1, rope//2]
        sin = sin_ref[...].astype(jnp.float32)
        c = jnp.concatenate([c[:, :l_rank], _rope(c[:, l_rank:], cos, sin)],
                            axis=-1)
        cn_s[...] = c
        c_new_ref[...] = c.astype(c_new_ref.dtype)
        m_s[...] = jnp.full_like(m_s[...], -1e30)
        l_s[...] = jnp.zeros_like(l_s[...])
        acc_s[...] = jnp.zeros_like(acc_s[...])

    @pl.when(j < n_p)
    def _proj():
        # one tile of th_p query heads: q = x·Wq, q_lat = q_nope·W_UK
        q = _mxu_dot(xn_s[...], wq_ref[...],
                     exact=x_ref.dtype == wq_ref.dtype)      # [B, th_p·hq]
        for i in range(th_p):
            qh = q[:, i * hq:(i + 1) * hq]
            q_lat = _mxu_dot(qh[:, :nope], wuk_ref[i])       # [B, l]
            q_rope = _rope(qh[:, nope:], cos_ref[...].astype(jnp.float32),
                           sin_ref[...].astype(jnp.float32))
            qt_s[j, i * B:(i + 1) * B, :] = jnp.concatenate(
                [q_lat, q_rope], axis=-1)

    @pl.when(j == n_p - 1)
    def _gather_q():
        # rows h·B + b: query head h of batch row b
        for t in range(n_p):
            q_s[t * th_p * B:(t + 1) * th_p * B, :] = qt_s[t]

    # ---------------- attention phase: latent-space FlashDecoding -------
    blk_start = (j - a0) * blk_s
    pos_base = scalars_ref[2]
    # rank-local live span (slot i holds position pos_base + i)
    eff_len = cache_len - jnp.maximum(pos_base, 0)
    live = (j >= a0) & (j < f0) & (blk_start < eff_len)
    full = (live & (pos_base >= 0)
            & (pos_base + blk_start + blk_s <= cache_len))

    def _attend(masked: bool):
        cb = c_blk_ref[...]                                   # [blk, l+r]
        s = _mxu_dot(q_s[...], cb, _NT) * scale               # [q·B, blk]
        valid = None
        if masked:
            pos = pos_blk_ref[...]                            # [1, blk]
            valid = (pos >= 0) & (pos < cache_len)
            s = jnp.where(valid, s, -1e30)
        m_prev, l_prev = m_s[...], l_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_s[...] = m_new
        l_s[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + _mxu_dot(p, c_blk_ref[:, :l_rank])

    @pl.when(full)
    def _attend_full():
        _attend(masked=False)

    @pl.when(live & jnp.logical_not(full))
    def _attend_masked():
        _attend(masked=True)

    # ---------------- output phase --------------------------------------
    def rows(v):
        """[B, n] per batch row → [q·B, n] in the q_s row order."""
        return v if B == 1 else jnp.concatenate([v] * q_loc, axis=0)

    @pl.when(j == f0)
    def _finalize():
        include_new = scalars_ref[1] > 0
        c_new = cn_s[...]                                     # [B, l+r]
        s = jnp.sum(q_s[...] * rows(c_new), axis=-1, keepdims=True) * scale
        s = jnp.where(include_new, s, -1e30)
        m_prev, l_prev = m_s[...], l_s[...]
        m_new = jnp.maximum(m_prev, s)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_fin = l_prev * corr + p
        acc = acc_s[...] * corr + p * rows(c_new[:, :l_rank])
        m_out_ref[...] = m_new
        l_out_ref[...] = l_fin
        if fuse_out is False:
            o_ref[...] = acc                                  # unnormalized
            return
        if fuse_out is True:
            # max guard: an inactive slot (ragged decode) has l == 0
            acc = acc / jnp.maximum(l_fin, 1e-30)
        for h in range(q_loc):                                # head-major
            ah_s[h] = acc[h * B:(h + 1) * B]

    if fuse_out is False:
        return

    @pl.when(j >= f0)
    def _project():
        t = j - f0
        if fuse_out == "partial_o":
            # fused value-up + Output-Projection of the UNNORMALIZED latent
            # accumulator through the prepacked per-head W_UV·W_O tiles;
            # normalization (÷ l_g) + head sum run after the ClusterReduce.
            for i in range(th_o):
                o_ref[0, i * B:(i + 1) * B, :] = _mxu_dot(
                    ah_s[t * th_o + i], wuv_ref[i])
        else:
            @pl.when(t == 0)
            def _zero():
                oacc_s[...] = jnp.zeros_like(oacc_s[...])

            for i in range(th_o):
                # value Up-Projection (A · W_UV) then Output-Projection
                o_head = _mxu_dot(ah_s[t * th_o + i], wuv_ref[i])   # [B, v]
                oacc_s[...] += _mxu_dot(
                    o_head, wo_ref[i * v_dim:(i + 1) * v_dim, :])

            @pl.when(t == n_o - 1)
            def _write():
                o_ref[...] = oacc_s[...].astype(o_ref.dtype)


def fused_mla_decode_attention(
    x: jax.Array,                 # [B, D]
    wq: jax.Array,                # [D, q_loc * (nope+rope)]
    wdkv: jax.Array,              # [D, l_rank + rope]
    wuk: jax.Array,               # [q_loc, nope, l_rank]
    wuv: jax.Array,               # [q_loc, l_rank, v_dim]; the prepacked
                                  # W_UV·W_O tiles when fuse_out="partial_o"
    wo: jax.Array,                # [q_loc * v_dim, D_out] (unused for
                                  # fuse_out="partial_o")
    c_cache: jax.Array,           # [S, l_rank + rope] latent cache
    cache_len: jax.Array,
    cos: jax.Array,               # [rope//2] at position cache_len
    sin: jax.Array,
    *,
    q_heads: int, nope: int, rope_d: int, l_rank: int, v_dim: int,
    block_s: int = 512, fuse_out=True, interpret: bool = False,
    pos: Optional[jax.Array] = None,
    include_new: Optional[jax.Array] = None,
    pos_base: Optional[jax.Array] = None,
    norm_scale: Optional[jax.Array] = None,   # [D] fused pre-attention
                                              # RMSNorm scale (None = legacy)
    norm_eps: float = 1e-6,
    weight_tile_bytes: int = WEIGHT_TILE_BYTES,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Returns ``(o, c_new, m, l)``.

    ``fuse_out=True``: o = [B, D_out] (final; m/l informational).
    ``fuse_out=False``: o = [B, q, l_rank] *unnormalized* latent
    accumulator — combine across chips with ``cluster_flash_combine``,
    then Up-Project and Output-Project.
    ``fuse_out="partial_o"``: o = [B, q, v_dim] *unnormalized* projected
    tiles through the prepacked per-head ``wuv`` (= W_UV·W_O columns);
    flash-merge across chips, normalize per head, sum over heads.

    ``weight_tile_bytes`` caps one streamed weight tile (whole heads;
    ``fused_decode.head_tile``); the default suits the chip, and tests
    shrink it to force several tiles at small widths.
    """
    tracecount.bump("pallas_kernel")
    B, D = x.shape
    S, lr = c_cache.shape
    assert lr == l_rank + rope_d
    scale = 1.0 / math.sqrt(nope + rope_d)
    blk_s = min(block_s, S)
    assert S % blk_s == 0
    n_blocks = S // blk_s
    d_out = wo.shape[1]
    hq = nope + rope_d
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

    # streamed weight tiles: whole query heads of wq (+ their wuk), and
    # whole heads of the value-up / output weights
    isz = jnp.dtype(wq.dtype).itemsize
    th_p = head_tile(q_heads, (D * hq + nope * l_rank) * isz, hq, 128,
                     weight_tile_bytes)
    n_p = q_heads // th_p
    if fuse_out == "partial_o":
        assert wuv.shape == (q_heads, l_rank, v_dim), (wuv.shape,)
        th_o = head_tile(q_heads, l_rank * v_dim * isz, l_rank, 1,
                         weight_tile_bytes)
        wo = jnp.zeros((1, 1), x.dtype)
        o_shape, o_block = (q_heads // th_o, th_o * B, v_dim), \
            (1, th_o * B, v_dim)
    elif fuse_out:
        th_o = head_tile(q_heads, (l_rank + d_out) * v_dim * isz, v_dim, 8,
                         weight_tile_bytes)
        o_shape = o_block = (B, d_out)
    else:
        th_o = q_heads
        wuv = jnp.zeros((1, 1, 1), x.dtype)
        o_shape = o_block = (q_heads * B, l_rank)
    n_o = q_heads // th_o if fuse_out is not False else 1
    f0 = n_p + n_blocks
    kernel = functools.partial(
        _kernel, blk_s=blk_s, n_blocks=n_blocks, q_loc=q_heads, nope=nope,
        rope_d=rope_d, l_rank=l_rank, v_dim=v_dim, scale=scale,
        fuse_out=fuse_out, fuse_norm=fuse_norm, norm_eps=norm_eps,
        n_p=n_p, th_p=th_p, n_o=n_o, th_o=th_o)

    def proj_tile(j):
        return jnp.minimum(j, n_p - 1)

    def out_tile(j):
        return jnp.clip(j - f0, 0, n_o - 1)

    def cache_map(j, s_ref):
        b = _cache_block_index(j - n_p + 1, s_ref[0], blk_s=blk_s,
                               n_blocks=n_blocks, window=0,
                               pos_base=s_ref[2])
        return (b, 0)

    def pos_map(j, s_ref):
        return (0, cache_map(j, s_ref)[0])

    const2 = lambda j, *_: (0, 0)
    if fuse_out is False:
        wuv_spec = pl.BlockSpec((1, 1, 1), lambda j, *_: (0, 0, 0))
        wo = jnp.zeros((1, 1), x.dtype)
        wo_spec = pl.BlockSpec((1, 1), const2)
        o_map = const2
    elif fuse_out == "partial_o":
        wuv_spec = pl.BlockSpec((th_o, l_rank, v_dim),
                                lambda j, *_: (out_tile(j), 0, 0))
        wo_spec = pl.BlockSpec((1, 1), const2)
        o_map = lambda j, *_: (out_tile(j), 0, 0)
    else:
        wuv_spec = pl.BlockSpec((th_o, l_rank, v_dim),
                                lambda j, *_: (out_tile(j), 0, 0))
        wo_spec = pl.BlockSpec((th_o * v_dim, d_out),
                               lambda j, *_: (out_tile(j), 0))
        o_map = const2
    R = q_heads * B
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_p + n_blocks + n_o,),
            in_specs=[
                pl.BlockSpec((B, D), const2),                       # x
                pl.BlockSpec((D, th_p * hq),
                             lambda j, *_: (0, proj_tile(j))),      # wq
                pl.BlockSpec(wdkv.shape, const2),                   # wdkv
                pl.BlockSpec((th_p, nope, l_rank),
                             lambda j, *_: (proj_tile(j), 0, 0)),   # wuk
                wuv_spec,                                           # wuv
                wo_spec,                                            # wo
                pl.BlockSpec((1, rope_d // 2), const2),             # cos
                pl.BlockSpec((1, rope_d // 2), const2),             # sin
                pl.BlockSpec(norm_op.shape, const2),                # ln1
                pl.BlockSpec((blk_s, lr), cache_map),               # cache
                pl.BlockSpec((1, blk_s), pos_map),                  # pos
            ],
            out_specs=[
                pl.BlockSpec(o_block, o_map),
                pl.BlockSpec((B, lr), const2),
                pl.BlockSpec((R, 1), const2),
                pl.BlockSpec((R, 1), const2),
            ],
            scratch_shapes=[
                pltpu.VMEM((B, D), jnp.float32),                # normed x
                pltpu.VMEM((B, lr), jnp.float32),               # c_new
                pltpu.VMEM((n_p, th_p * B, lr), jnp.float32),   # q tiles
                pltpu.VMEM((R, lr), jnp.float32),               # q
                pltpu.VMEM((R, 1), jnp.float32),                # m
                pltpu.VMEM((R, 1), jnp.float32),                # l
                pltpu.VMEM((R, l_rank), jnp.float32),           # acc
                pltpu.VMEM((q_heads, B, l_rank), jnp.float32),  # acc by head
                pltpu.VMEM((B, d_out) if fuse_out is True else (1, 1),
                           jnp.float32),                        # o acc
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(o_shape,
                                 x.dtype if fuse_out is True
                                 else jnp.float32),
            jax.ShapeDtypeStruct((B, lr), c_cache.dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="fused_mla_decode",
    )(scalars,
      x, wq, wdkv, wuk, wuv, wo, cos.reshape(1, -1), sin.reshape(1, -1),
      norm_op, c_cache, jnp.asarray(pos, jnp.int32).reshape(1, S))
    o, c_new, m, l = out
    if fuse_out == "partial_o":
        o = o.reshape(q_heads, B, v_dim).transpose(1, 0, 2)
    elif fuse_out is False:
        o = o.reshape(q_heads, B, l_rank).transpose(1, 0, 2)
    return (o, c_new, m.reshape(q_heads, B).T, l.reshape(q_heads, B).T)
