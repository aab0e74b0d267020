"""RG-LRU sequence-scan kernel (Griffin recurrence) with VMEM-resident state.

grid = (channel_blocks, seq_blocks); channel blocks are independent
("parallel"), sequence blocks are sequential ("arbitrary") with the
recurrent state carried in VMEM scratch — the whole scan runs without
HBM round-trips for the state (beyond-paper fusion for the attention-free
architectures, same philosophy as the paper's decode fusion).

Gate math is precomputed outside (it is a dense matmul — MXU-friendly in
the main graph); the kernel consumes ``log_a`` and the gated input ``b``
and performs ``h_t = exp(log_a_t)·h_{t−1} + b_t`` sequentially.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(log_a_ref, b_ref, h0_ref, out_ref, h_fin_ref, h_s,
            *, blk_t: int, n_tblocks: int):
    tj = pl.program_id(1)

    @pl.when(tj == 0)
    def _init():
        h_s[...] = h0_ref[...].astype(jnp.float32)

    log_a = log_a_ref[...].astype(jnp.float32)     # [B, blk_t, C]
    b = b_ref[...].astype(jnp.float32)

    def step(t, h):
        h = jnp.exp(log_a[:, t]) * h + b[:, t]
        out_ref[:, t] = h.astype(out_ref.dtype)
        return h

    h = lax.fori_loop(0, blk_t, step, h_s[...])
    h_s[...] = h

    @pl.when(tj == n_tblocks - 1)
    def _fin():
        h_fin_ref[...] = h.astype(h_fin_ref.dtype)


def rglru_scan_kernel(log_a: jax.Array, b: jax.Array, h0: jax.Array,
                      *, block_t: int = 128, block_c: int = 512,
                      interpret: bool = False):
    """log_a/b: [B, S, C]; h0: [B, C] → (h_seq [B, S, C], h_final [B, C])."""
    B, S, C = log_a.shape
    blk_t = min(block_t, S)
    blk_c = min(block_c, C)
    assert S % blk_t == 0 and C % blk_c == 0
    n_t, n_c = S // blk_t, C // blk_c

    kernel = functools.partial(_kernel, blk_t=blk_t, n_tblocks=n_t)
    out, h_fin = pl.pallas_call(
        kernel,
        grid=(n_c, n_t),
        in_specs=[
            pl.BlockSpec((B, blk_t, blk_c), lambda c, t: (0, t, c)),
            pl.BlockSpec((B, blk_t, blk_c), lambda c, t: (0, t, c)),
            pl.BlockSpec((B, blk_c), lambda c, t: (0, c)),
        ],
        out_specs=[
            pl.BlockSpec((B, blk_t, blk_c), lambda c, t: (0, t, c)),
            pl.BlockSpec((B, blk_c), lambda c, t: (0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, C), log_a.dtype),
            jax.ShapeDtypeStruct((B, C), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((B, blk_c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="rglru_scan",
    )(log_a, b, h0)
    return out, h_fin
