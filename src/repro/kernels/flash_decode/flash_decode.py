"""Plain FlashDecoding attention kernel (the paper's *baseline* dataflow:
attention alone, projections in separate kernels).

Same attention phase as ``fused_decode`` but takes q as input and returns
the normalized attention output — used for the fusion-ablation benchmark
(paper Fig. 9/18: ClusterFusion vs unfused) and as a standalone op.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(cache_len_ref, q_ref, k_blk_ref, v_blk_ref,
            o_ref, m_s, l_s, acc_s,
            *, blk_s: int, n_blocks: int, kv_loc: int, qpk: int,
            hd: int, scale: float, cap: float, window: int):
    j = pl.program_id(0)
    cache_len = cache_len_ref[0]
    B = q_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s[...], -1e30)
        l_s[...] = jnp.zeros_like(l_s[...])
        acc_s[...] = jnp.zeros_like(acc_s[...])

    blk_start = j * blk_s
    lo = cache_len - window if window > 0 else -1
    live = (j < n_blocks) & (blk_start < cache_len) & \
        (blk_start + blk_s > lo)

    @pl.when(live)
    def _attend():
        q = q_ref[...].astype(jnp.float32).reshape(B, kv_loc, qpk, hd)
        kb = k_blk_ref[...].astype(jnp.float32)
        vb = v_blk_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((3,), (2,)), ((1,), (1,))))
        s = jnp.moveaxis(s, 0, 1) * scale
        if cap > 0:
            s = jnp.tanh(s / cap) * cap
        pos = blk_start + lax.broadcasted_iota(jnp.int32, (1, 1, 1, blk_s), 3)
        valid = pos < cache_len
        if window > 0:
            valid &= pos > cache_len - window
        s = jnp.where(valid, s, -1e30)
        m_prev, l_prev = m_s[...], l_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_s[...] = m_new
        l_s[...] = l_prev * corr + jnp.sum(p, axis=-1)
        pv = jnp.moveaxis(
            jax.lax.dot_general(p, vb, (((3,), (0,)), ((1,), (1,)))), 0, 1)
        acc_s[...] = acc_s[...] * corr[..., None] + pv

    @pl.when(j == n_blocks)
    def _finalize():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[...] = (acc_s[...] / l[..., None]).reshape(
            B, kv_loc * qpk, hd).astype(o_ref.dtype)


def flash_decode_attention(
    q: jax.Array,                 # [B, q_loc, hd]
    k_cache: jax.Array,           # [S, kv_loc, hd]
    v_cache: jax.Array,
    cache_len: jax.Array,
    *,
    scale: Optional[float] = None,
    attn_softcap: float = 0.0,
    window: int = 0,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, q_loc, hd = q.shape
    S, kv_loc, _ = k_cache.shape
    qpk = q_loc // kv_loc
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    blk_s = min(block_s, S)
    assert S % blk_s == 0
    n_blocks = S // blk_s

    kernel = functools.partial(
        _kernel, blk_s=blk_s, n_blocks=n_blocks, kv_loc=kv_loc, qpk=qpk,
        hd=hd, scale=scale, cap=attn_softcap, window=window)

    def cache_map(j, *_):
        return (jnp.clip(j, 0, n_blocks - 1), 0, 0)

    (o,) = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks + 1,),
            in_specs=[
                pl.BlockSpec((B, q_loc, hd), lambda j, *_: (0, 0, 0)),
                pl.BlockSpec((blk_s, kv_loc, hd), cache_map),
                pl.BlockSpec((blk_s, kv_loc, hd), cache_map),
            ],
            out_specs=[pl.BlockSpec((B, q_loc, hd), lambda j, *_: (0, 0, 0))],
            scratch_shapes=[
                pltpu.VMEM((B, kv_loc, qpk), jnp.float32),
                pltpu.VMEM((B, kv_loc, qpk), jnp.float32),
                pltpu.VMEM((B, kv_loc, qpk, hd), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, q_loc, hd), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="flash_decode",
    )(jnp.asarray(cache_len, jnp.int32).reshape(1), q, k_cache, v_cache)
    return o
