"""Fused LM-head/sampling tail: final RMSNorm + vocab-tiled logits +
softcap + streaming top-k partials in ONE ``pallas_call`` (DESIGN.md §7).

After the last fused layer, the decode step still ended with a loose XLA
tail: final ``rms_norm``, a full ``[B, V_loc]`` logits tensor
materialized in HBM, ``softcap``, and the local max/argmax feeding
``greedy_sample``'s (value, index) tree reduce.  The logits tensor is
the single largest activation a decode step writes — and it is never
needed: sampling only consumes each slot's k best ``(value, index)``
candidates (k = 1 is greedy).  This kernel runs the whole tail per
vocab shard:

* grid = (V_loc / block_v,), sequential.  Step 0 additionally computes
  the *prologue* in VMEM scratch: the final RMSNorm of the raw residual
  stream ``h = rms(x, ln)`` with a model-dtype round-trip, so the fused
  value is bit-identical to the unfused ``rms_norm`` (the same contract
  as the in-kernel ``ln1`` of the fused attention kernels).
* every step streams one ``[block_v, D]`` tile of the (possibly tied)
  embedding table, computes the logit tile ``h @ tileᵀ`` in f32 —
  exactly ``lm_head_logits``'s pinned f32 staging, so fused and unfused
  logits are bit-identical — applies ``logit_softcap`` in-tile (f32),
  and folds the tile into ``[B, k]`` running (value, index) scratch via
  ``select_topk`` over the concatenated carry + tile (k unrolled
  max/min-index passes — sort-free, Pallas-safe); the ``[B, V]`` logits
  NEVER exist outside one VMEM tile.
* the last step writes the per-shard sorted top-k partials — two
  ``[B, k]`` matrices, the only HBM output.

**Tie-breaking.**  ``select_topk`` orders candidates value-descending
with ties to the LOWEST global index — within a tile, across tiles
(earlier tiles carry lower global ids) and across shards alike: the
caller lifts local indices to the global vocab (``+ shard · V_loc``)
and merges shards with ONE tree ClusterReduce using the same operator
(``topk.topk_pair_merge``), so the fused tail reproduces the unfused
full-logits top-k token-exactly, and k = 1 reproduces ``greedy_sample``
(the PR-5 ``_greedy_pair_merge`` contract, verbatim at width k).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import tracecount
from repro.kernels.fused_head.topk import _INT32_MAX, select_topk


def _kernel(x_ref, tab_ref, ln_ref,
            mx_ref, ix_ref,
            h_s, m_s, i_s,
            *, n_v: int, bv: int, k: int, eps: float, cap: float):
    j = pl.program_id(0)

    # ---------------- prologue: final RMSNorm in VMEM -------------------
    @pl.when(j == 0)
    def _prologue():
        xf = x_ref[...].astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        h = xf * jax.lax.rsqrt(var + eps) \
            * (1.0 + ln_ref[...].astype(jnp.float32))
        # model-dtype round-trip: bit-identical to the unfused rms_norm
        h_s[...] = h.astype(x_ref.dtype).astype(jnp.float32)
        m_s[...] = jnp.full_like(m_s[...], -jnp.inf)
        i_s[...] = jnp.full_like(i_s[...], _INT32_MAX)

    # ---------------- one vocab tile per grid step ----------------------
    # logits stay in f32, matching `lm_head_logits`'s pinned staging (the
    # rounded-rms h against the f32-upcast table, softcap in f32) — so
    # fused-vs-unfused values are bit-identical and the top-k partials
    # are token-exact
    h = h_s[...]
    lf = jax.lax.dot_general(h, tab_ref[...].astype(jnp.float32),
                             (((1,), (1,)), ((), ())))          # [B, bv]
    if cap > 0:
        lf = jnp.tanh(lf / cap) * cap
    ids = jax.lax.broadcasted_iota(jnp.int32, lf.shape, 1) + j * bv
    # fold the tile into the running [B, k] carry: one select_topk over
    # the concatenated (carry, tile) candidates — the (-inf, INT32_MAX)
    # init rows lose every comparison, so tile 0 is a pure select
    nv, ni = select_topk(jnp.concatenate([m_s[...], lf], axis=-1),
                         jnp.concatenate([i_s[...], ids], axis=-1), k)
    m_s[...] = nv
    i_s[...] = ni

    # ---------------- epilogue: write the [B, k] partials once ----------
    @pl.when(j == n_v - 1)
    def _epilogue():
        mx_ref[...] = m_s[...]
        ix_ref[...] = i_s[...]


def fused_head_block(
    x: jax.Array,                     # [B, D] raw residual stream
    table: jax.Array,                 # [V_loc, D] vocab-sharded head table
                                      # (aliases the embed table when tied)
    ln: jax.Array,                    # [D] final RMSNorm scale
    *,
    eps: float = 1e-6,
    logit_softcap: float = 0.0,
    block_v: int = 1024,
    k: int = 1,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns ``(values [B, k] f32, local_indices [B, k] int32)`` over
    this rank's vocab shard, sorted value-descending (ties to the lowest
    index) — the streaming top-k partials.  The caller adds
    ``shard · V_loc`` and tree-reduces the candidate sets across the
    model axis with ``topk.topk_pair_merge``; ``[B, V]`` logits never
    touch HBM.  ``k = 1`` is the greedy (max, argmax) pair.
    """
    tracecount.bump("pallas_kernel")
    tracecount.bump("head_pallas_kernel")
    B, D = x.shape
    V_loc = table.shape[0]
    bv = min(block_v, V_loc)
    assert V_loc % bv == 0, (V_loc, bv)
    n_v = V_loc // bv
    ln_op = jnp.asarray(ln, jnp.float32).reshape(1, D)

    kernel = functools.partial(_kernel, n_v=n_v, bv=bv, k=k, eps=eps,
                               cap=float(logit_softcap or 0.0))

    out = pl.pallas_call(
        kernel,
        grid=(n_v,),
        in_specs=[
            pl.BlockSpec((B, D), lambda j: (0, 0)),            # x
            pl.BlockSpec((bv, D), lambda j: (j, 0)),           # table tile
            pl.BlockSpec((1, D), lambda j: (0, 0)),            # ln
        ],
        out_specs=[
            pl.BlockSpec((B, k), lambda j: (0, 0)),
            pl.BlockSpec((B, k), lambda j: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, D), jnp.float32),                   # h (normed)
            pltpu.VMEM((B, k), jnp.float32),                   # running vals
            pltpu.VMEM((B, k), jnp.int32),                     # running ids
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fused_head",
    )(x, table, ln_op)
    return out[0], out[1]
