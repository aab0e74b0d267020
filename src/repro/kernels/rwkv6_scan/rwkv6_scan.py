"""RWKV-6 WKV recurrence kernel with the matrix state resident in VMEM.

grid = (head_blocks, seq_blocks); heads parallel, sequence sequential with
the [B, hb, hd, hd] state carried in VMEM scratch (fp32).  Per timestep:

    o_t = r_t · (S + u ⊙ (k_tᵀ v_t))
    S  ← diag(w_t) S + k_tᵀ v_t

This is the fusion-scope philosophy applied to the attention-free arch
(DESIGN.md §4: the paper's head-cluster dataflow is inapplicable to
RWKV-6, so the recurrence gets its own fused kernel instead).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
            o_ref, s_fin_ref, s_s,
            *, blk_t: int, n_tblocks: int, hb: int, hd: int):
    tj = pl.program_id(1)
    B = r_ref.shape[0]

    @pl.when(tj == 0)
    def _init():
        s_s[...] = s0_ref[...].astype(jnp.float32)

    r = r_ref[...].astype(jnp.float32)      # [B, blk_t, hb, hd]
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)      # [1, hb, hd]

    def step(t, s):
        kt = k[:, t]                         # [B, hb, hd]
        vt = v[:, t]
        kv = kt[..., :, None] * vt[..., None, :]      # [B,hb,hd,hd]
        o = jnp.einsum("bhi,bhij->bhj", r[:, t], s + u[..., :, None] * kv)
        o_ref[:, t] = o.astype(o_ref.dtype)
        return w[:, t][..., :, None] * s + kv

    s = lax.fori_loop(0, blk_t, step, s_s[...])
    s_s[...] = s

    @pl.when(tj == n_tblocks - 1)
    def _fin():
        s_fin_ref[...] = s.astype(s_fin_ref.dtype)


def rwkv6_scan_kernel(r, k, v, w, u, s0, *, block_t: int = 64,
                      block_h: int = 4, interpret: bool = False):
    """r/k/v/w: [B, S, H, hd]; u: [H, hd]; s0: [B, H, hd, hd].

    Returns (o [B, S, H, hd], s_final [B, H, hd, hd])."""
    B, S, H, hd = r.shape
    hb = min(block_h, H)
    blk_t = min(block_t, S)
    assert S % blk_t == 0 and H % hb == 0
    n_t, n_h = S // blk_t, H // hb

    kernel = functools.partial(_kernel, blk_t=blk_t, n_tblocks=n_t, hb=hb,
                               hd=hd)
    o, s_fin = pl.pallas_call(
        kernel,
        grid=(n_h, n_t),
        in_specs=[
            pl.BlockSpec((B, blk_t, hb, hd), lambda h, t: (0, t, h, 0)),
            pl.BlockSpec((B, blk_t, hb, hd), lambda h, t: (0, t, h, 0)),
            pl.BlockSpec((B, blk_t, hb, hd), lambda h, t: (0, t, h, 0)),
            pl.BlockSpec((B, blk_t, hb, hd), lambda h, t: (0, t, h, 0)),
            pl.BlockSpec((1, hb, hd), lambda h, t: (0, h, 0)),
            pl.BlockSpec((B, hb, hd, hd), lambda h, t: (0, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((B, blk_t, hb, hd), lambda h, t: (0, t, h, 0)),
            pl.BlockSpec((B, hb, hd, hd), lambda h, t: (0, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, hd), r.dtype),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((B, hb, hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="rwkv6_scan",
    )(r, k, v, w, u.reshape(1, H, hd), s0)
    return o, s_fin
