"""Fused transformer-block tail: post-attention RMSNorm + gated FFN +
both residual adds in ONE ``pallas_call`` (DESIGN.md §7).

After the fused attention kernel emits the full-width attention output
``a`` for a layer, the rest of the block is still ~6 loose XLA ops plus
a per-layer ``psum_model`` all-reduce on the FFN activations — repeated
HBM round-trips for the ``[B, D]`` activation.  This kernel runs the
whole tail per rank:

* grid = (F_loc / block_f,), sequential.  Step 0 additionally computes
  the *prologue* in VMEM scratch: optional post-attention norm of ``a``
  (Gemma-2 ``post_ln1``), the first residual add ``r = x + a``, and the
  pre-FFN RMSNorm ``h = rms(r, ln2)`` — the raw residual stream and the
  raw attention output are the only activations that cross HBM.
* every step streams one ``block_f`` column tile of the up (and gate)
  projection plus the matching ``block_f``-row tile of the down
  projection, accumulating ``act(h·Wg)·(h·Wi) @ Wo_tile`` into a
  ``[B, D]`` f32 scratch accumulator.
* the last step folds the second residual add and writes once.

**Full-width down rows.**  ``w_out`` tiles are FULL-width ``[bf, D]``
rows (the Megatron row-sharded layout — every rank's partial lives in
the same output basis), so one fused ClusterReduce over the model axis
sums the per-rank partials exactly — the same invariant that makes the
attention kernel's ``partial_o`` combinable (see
``PackedSplitTokenWeights.wo``).  The residual ``r`` is folded into
exactly ONE rank's partial (``add_r = 1.0`` there, ``0.0`` elsewhere —
an exact multiplicative gate), so the reduce completes the layer output
``x + a + f`` directly and the per-layer ``ctx.psum_model`` disappears.

Post-norm models (``post_ln2``) normalize the SUMMED FFN output — a
nonlinearity over the full reduction — so there ``add_r = 0``: the
kernel emits the raw partial plus ``r`` (second output), and the caller
applies ``r + rms(reduce(partial), post_ln2)`` after the combine.

Ragged decode needs no gating here: the FFN is position-independent and
slot-local, so free slots simply flow through (their output is ignored
by the scheduler), exactly as on the XLA path.

**Stacked weights.**  The decode scan keeps every layer's weights in one
``[L, …]`` stack.  Handed the stacks (3-D ``w_in``/``w_gate``/``w_out``)
and an int32 ``layer``, the kernel reads that layer's tiles in place:
``layer`` rides a scalar-prefetch operand and leads the weight index
maps, over a squeezed leading block dim.  Without it XLA would copy the
layer's slice into a fresh buffer before every call (a Pallas operand
cannot be a strided view).  2-D weights take the plain grid.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import tracecount
from repro.models.layers import activation


def _kernel(x_ref, a_ref, wi_ref, wg_ref, wo_ref, ln2_ref, post1_ref,
            addr_ref,
            o_ref, r_ref,
            r_s, h_s, acc_s,
            *, n_f: int, act: str, eps: float, gated: bool,
            has_post1: bool):
    j = pl.program_id(0)

    def rms(v, scale):                  # v f32 [B, D]; dtype round-trip
        var = jnp.mean(v * v, axis=-1, keepdims=True)
        out = v * jax.lax.rsqrt(var + eps) * (1.0 + scale)
        return out.astype(x_ref.dtype).astype(jnp.float32)

    # ---------------- prologue: norms + first residual add -------------
    @pl.when(j == 0)
    def _prologue():
        x = x_ref[...].astype(jnp.float32)
        a = a_ref[...].astype(jnp.float32)
        if has_post1:
            a = rms(a, post1_ref[...].astype(jnp.float32))
        r = (x + a).astype(x_ref.dtype).astype(jnp.float32)
        r_s[...] = r
        h_s[...] = rms(r, ln2_ref[...].astype(jnp.float32))
        acc_s[...] = jnp.zeros_like(acc_s[...])

    # ---------------- one d_ff tile per grid step -----------------------
    # intermediates round to the model dtype at the same op boundaries the
    # XLA path rounds at, so fused-vs-unfused drift stays at reduce-
    # association level (keeps greedy decode token-stable)
    def q(v):
        return v.astype(x_ref.dtype).astype(jnp.float32)

    h = h_s[...]
    act_fn = activation(act)
    u = q(jax.lax.dot(h, wi_ref[...].astype(jnp.float32)))     # [B, bf]
    if gated:
        g = q(jax.lax.dot(h, wg_ref[...].astype(jnp.float32)))
        hm = q(act_fn(g) * u)
    else:
        hm = q(act_fn(u))
    acc_s[...] += jax.lax.dot(hm, wo_ref[...].astype(jnp.float32))

    # ---------------- epilogue: second residual add + one HBM write -----
    @pl.when(j == n_f - 1)
    def _epilogue():
        add_r = addr_ref[...].astype(jnp.float32)              # [1, 1]
        o_ref[...] = (acc_s[...] + r_s[...] * add_r).astype(o_ref.dtype)
        r_ref[...] = r_s[...].astype(r_ref.dtype)


def _skip_layer(body, layer_ref, *refs):
    """The kernel body on a stack: the layer scalar only steers the
    weight index maps."""
    body(*refs)


def fused_ffn_block(
    x: jax.Array,                     # [B, D] raw residual stream
    a: jax.Array,                     # [B, D] attention output (pre-residual)
    w_in: jax.Array,                  # [D, F_loc] up-projection columns
                                      # (or an [L, D, F_loc] stack)
    w_gate: Optional[jax.Array],      # [D, F_loc] gate columns, or None
    w_out: jax.Array,                 # [F_loc, D] FULL-width down rows
    ln2: jax.Array,                   # [D] pre-FFN RMSNorm scale
    post_ln1: Optional[jax.Array],    # [D] post-attention norm (Gemma-2)
    add_r: jax.Array,                 # [] 1.0 on the single rank folding the
                                      # residual into its partial, else 0.0
    *,
    act: str,
    eps: float = 1e-6,
    block_f: int = 512,
    interpret: bool = False,
    layer: Optional[jax.Array] = None,  # int32 layer of [L, …] weight stacks
) -> Tuple[jax.Array, jax.Array]:
    """Returns ``(o, r)``.

    ``o [B, D]``: this rank's down-projection partial (+ ``add_r · r``),
    in ``x.dtype`` — ClusterReduce over the model axis completes the
    layer.  ``r [B, D]``: the post-first-residual stream (needed only by
    ``post_ln2`` callers, which apply the second residual add outside).

    With 3-D weights (``[L, D, F_loc]`` / ``[L, F_loc, D]`` stacks) the
    kernel reads layer ``layer`` of each stack in place.
    """
    tracecount.bump("pallas_kernel")
    tracecount.bump("ffn_pallas_kernel")
    stacked = w_in.ndim == 3
    if stacked:
        assert layer is not None, "stacked FFN weights need a layer index"
        tracecount.bump("weights_by_index")
    B, D = x.shape
    F_loc = w_in.shape[-1]
    bf = min(block_f, F_loc)
    assert F_loc % bf == 0, (F_loc, bf)
    n_f = F_loc // bf
    gated = w_gate is not None
    has_post1 = post_ln1 is not None
    wg_op = w_gate if gated else jnp.zeros((1, 1), w_in.dtype)
    post1_op = (jnp.asarray(post_ln1, jnp.float32).reshape(1, D)
                if has_post1 else jnp.zeros((1, 1), jnp.float32))
    ln2_op = jnp.asarray(ln2, jnp.float32).reshape(1, D)
    addr_op = jnp.asarray(add_r, jnp.float32).reshape(1, 1)

    kernel = functools.partial(
        _kernel, n_f=n_f, act=act, eps=eps, gated=gated,
        has_post1=has_post1)

    def col_tile(j):
        return (0, j)

    def weight_spec(block, index):
        """A weight tile; on a stack the squeezed leading dim is read at
        the prefetched layer scalar."""
        if not stacked:
            return pl.BlockSpec(block, index)
        return pl.BlockSpec((None,) + block,
                            lambda j, s: (s[0],) + index(j))

    def const(j, *_):
        return (0, 0)

    wg_spec = (weight_spec((D, bf), col_tile) if gated
               else pl.BlockSpec((1, 1), const))
    specs = dict(
        in_specs=[
            pl.BlockSpec((B, D), const),                       # x
            pl.BlockSpec((B, D), const),                       # a
            weight_spec((D, bf), col_tile),                    # w_in tile
            wg_spec,                                           # w_gate tile
            weight_spec((bf, D), lambda j: (j, 0)),            # w_out rows
            pl.BlockSpec(ln2_op.shape, const),                 # ln2
            pl.BlockSpec(post1_op.shape, const),               # post_ln1
            pl.BlockSpec((1, 1), const),                       # add_r
        ],
        out_specs=[
            pl.BlockSpec((B, D), const),
            pl.BlockSpec((B, D), const),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, D), jnp.float32),                   # r
            pltpu.VMEM((B, D), jnp.float32),                   # h (normed)
            pltpu.VMEM((B, D), jnp.float32),                   # accumulator
        ],
    )
    if stacked:
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_f,), **specs))
        lead = (jnp.asarray(layer, jnp.int32).reshape(1),)
        kernel = functools.partial(_skip_layer, kernel)
    else:
        grid = dict(grid=(n_f,), **specs)
        lead = ()

    out = pl.pallas_call(
        kernel,
        **grid,
        out_shape=[
            jax.ShapeDtypeStruct((B, D), x.dtype),
            jax.ShapeDtypeStruct((B, D), x.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fused_ffn",
    )(*lead, x, a, w_in, wg_op, w_out, ln2_op, post1_op, addr_op)
    return tuple(out)
