"""Trace-time operation counters.

Decode hot-loop structure is asserted by counting *call sites as they
trace* (one trace = one compiled step, so trace-time counts are exact
per-step op counts under ``jit``).  The counters are free in production:
``bump`` is a no-op unless a :func:`counting` context is active, and the
instrumented sites only pay a dict lookup at trace time, never at run
time.

Labels used across the codebase:

* ``tree_reduce`` / ``tree_gather`` — cluster-collective tree schedules
  (:mod:`repro.core.primitives`); counted once per collective call, not
  per round.
* ``weight_gather`` — per-step ClusterGather of *weight* segments (the
  Level-2 hoisted gathers the prepack layout eliminates — DESIGN.md §2).
* ``weight_slice`` — per-layer ``lax.dynamic_slice`` weight slicing in
  the train-layout adapters (``_split_token_weights``/``_mla_weights``).
* ``weight_slice_hoisted`` — the once-per-step rank slices hoisted out
  of the layer scan (non-prepacked fast path).
* ``weights_by_index`` — ``fused_ffn`` calls traced with ``[L, …]``
  weight stacks and a layer index: the kernel reads the layer's tiles in
  place, so the decode scan writes no per-layer copy of the FFN weights.
  On the prepacked Pallas path one per scanned dense-FFN layer position;
  zero for the unstacked tail and for any caller passing one layer's
  weights.  Not the same as ``weight_slice``, which counts rank slicing
  and read zero on the prepacked path even while the scan still copied
  each layer.
* ``pallas_kernel`` — ``pallas_call`` launch counter: one bump per
  kernel invocation as it traces (a vmapped kernel traces once, so this
  is the per-step launch count under ``jit``).
* ``ffn_pallas_kernel`` — the fused-FFN block-tail megakernel's own
  launches (a subset of ``pallas_kernel``).
* ``psum_model`` — per-step activation all-reduces over the model axis
  (``ParallelCtx.psum_model``: embedding assembly + the per-layer FFN
  combine on the unfused path).
* ``ffn_cluster_reduce`` — the fused ClusterReduce that replaces the
  per-layer FFN ``psum_model`` on the full-block path (DESIGN.md §7).
* ``head_pallas_kernel`` — the fused LM-head/sampling tail kernel's
  launches (a subset of ``pallas_kernel``; kernels/fused_head).
* ``head_cluster_reduce`` — the single (value, index) pair tree reduce
  that merges the fused head's per-shard greedy partials.
* ``lm_head_logits`` — materializations of the ``[B, V_loc]`` logits
  tensor (``models.layers.lm_head_logits``).  The fused head path must
  trace ZERO of these: the logits exist only as VMEM tiles inside the
  kernel, never in HBM.

Evidence targets (tests/test_prepack.py, tests/test_fused_head.py):
the prepacked Pallas path traces with ``weight_gather == weight_slice
== 0`` and exactly one ``pallas_kernel`` + one ``tree_reduce`` on the
cluster axis per attention layer; the FULL-block path (fused FFN)
traces with exactly TWO ``pallas_kernel`` per dense-FFN attention
layer and ``psum_model == 1`` per decode step (the embedding lookup —
zero per-layer activation psums); the fused-head step adds exactly ONE
``head_pallas_kernel`` + ONE ``head_cluster_reduce`` and ZERO
``lm_head_logits`` — embed psum + 2 launches/layer + 1 head launch +
1 head reduce is the complete dense decode step.

* ``finite_guard`` — the per-step integrity sentinel traced into the
  decode/admit steps when ``ServeConfig.check_finite`` is on
  (``serving/engine._finite_violations``): one bump per guarded program,
  proof the guard is IN the compiled step (and absent when the flag is
  off — the bench path must trace zero of these).
* ``kv_fp_update`` — the incremental KV-cache checksum update traced
  into the decode/admit steps when ``ServeConfig.kv_fingerprint`` is on
  (serving/integrity.py): one bump per fingerprinting program, proof
  the SDC accumulator is IN the compiled step (and absent when off).

Besides the trace-time counters, this module hosts the RUNTIME work
counters for ragged decode (:func:`live_attend_blocks`): a pure-jnp
mirror of the kernels' live-block formula
(``fused_decode._live_block_bounds`` + the per-step liveness guard)
that the engine accumulates per slot into ``state["work_blocks"]``
when ``ServeConfig.track_work`` is on.  Trace-time counts prove the
*structure* of a step; these prove the *amount* of attend-step work a
slot actually paid — the scheduler tests assert a retired slot's
counter stops moving while its batch neighbors keep streaming.

A third family, the DETECTION-SIGNAL counters (:func:`record_signal`),
is host-side and always on: the fleet router (serving/router.py) records
one count per integrity probe that fires — labels:

* ``detect_nonfinite`` — the ``check_finite`` sentinel leaf reported a
  non-finite residual/head output for an active slot.
* ``detect_lens_bounds`` — ``cache_lens`` left ``[−1, max_seq]`` or the
  shards disagreed on it.
* ``detect_journal_stale`` — the device ``cache_lens`` diverged from the
  scheduler's host-side journal model (dropped/duplicated admit,
  blackholed replica echoing stale tokens).
* ``detect_journal_mismatch`` — a recovery replay re-emitted a token
  that differs from the journaled stream (divergent replica weights —
  out of the fault model, asserted zero in tests; DESIGN.md §9).
* ``detect_heartbeat`` — the replica raised (killed) inside its step.
* ``replica_failed`` — one per replica the router drained.
* ``detect_kv_fingerprint`` — a KV-cache bit-pattern checksum diverged
  from the device fingerprint leaf (serving/integrity.py): silent data
  corruption in cached K/V, below the non-finite floor.
* ``detect_weight_fingerprint`` — a serve-tree leaf's checksum diverged
  from its prepack-time reference (rotating spot-check).
* ``detect_shadow_recompute`` — the host shadow recompute of a slot's
  winning logit disagreed with the device's ``head_val`` beyond
  tolerance (head-path SDC the checksums cannot see).
* ``replica_healed`` — a weight-SDC replica re-materialized its serve
  layout from the train view, re-verified every fingerprint, and
  rejoined the fleet (serving/router.py).
* ``request_failed`` — a request hit the router's ``max_requeues`` cap
  and was terminally FAILED instead of re-queued (requeue-storm guard).

These are plain host counters (no trace interaction) so chaos tests can
assert detection latency in *scheduler ticks* without parsing events.

A fourth family, the PROBE-OVERHEAD counters (:func:`record_probe`),
accounts what the SDC probes themselves cost: ``probe_ticks`` (one per
monitor probe call) and ``probe_bytes_kv`` / ``probe_bytes_weights`` /
``probe_bytes_shadow`` (host bytes pulled per probe family) — the
bench's ``sdc_sweep.fault_free.probe_bytes_per_tick`` column divides
these out, so per-tick probe overhead is a gated, tracked number.

A fifth family, the SERVE-LOOP counters (:func:`record_serve`), is
host-side and always on, like the two above; the slot scheduler and the
router record (serving/scheduler.py, serving/router.py):

* ``host_syncs`` / ``host_sync_bytes`` — every blocking device→host read
  of the serve loop (``scheduler.fetch``) and the bytes it brought back:
  per tick the router probe's ``cache_lens``, per decode the next tokens
  and the integrity latch's ``cache_lens``, per admit the first tokens
  (and the ``nonfinite`` leaf beside each ``cache_lens`` where the step
  has one).
* ``admit_rows`` — real prompt tokens admitted.
* ``admit_positions`` — positions the padded admit ran:
  ``slots × prompt_cap`` per admit call.  ``1 − admit_rows /
  admit_positions`` is the share of the admit that is padding.

Decode calls and ticks are not counted here: ``SlotScheduler`` and
``Router`` keep those (``decode_calls``, ``tick``).  The same loop opens
``jax.profiler.TraceAnnotation`` spans named ``serve.*`` (``serve.tick``
and its children: ``serve.dispatch``, ``serve.admit`` /
``serve.admit.wait``, ``serve.decode`` / ``serve.decode.wait``,
``serve.retire``, ``serve.latch``, ``serve.probe``, ``serve.commit``);
they cost about a microsecond each when no profiler trace is active.
"""
from __future__ import annotations

from collections import Counter
from contextlib import contextmanager

import jax.numpy as jnp

_COUNTS: Counter = Counter()
_ACTIVE: int = 0


def bump(name: str, n: int = 1) -> None:
    """Increment ``name`` when a :func:`counting` context is active."""
    if _ACTIVE:
        _COUNTS[name] += n


def live_attend_blocks(cache_lens, *, s_blk: int, block_s: int, rank,
                       window: int = 0, ring: bool = False):
    """Per-slot attend-step (KV-block) count for one attention layer.

    Mirrors the Pallas index-map clamp / ``@pl.when`` liveness and the
    XLA path's bucket liveness, per slot: a slot whose rank-local live
    span is empty (``cache_len ≤ pos_base``, including retired slots at
    ``cache_len = −1``) counts ZERO blocks.  ``rank`` is this rank's
    cluster index (traced inside shard_map); ``ring=True`` is the
    wrapped sliding-window layout where only the fill-order upper bound
    applies.  Returns int32 [B] (or a scalar for lockstep input).
    """
    cl = jnp.asarray(cache_lens, jnp.int32)
    blk = min(block_s, s_blk)
    n_blocks = max(1, s_blk // max(blk, 1))
    if ring:
        eff = cl
    else:
        eff = cl - jnp.asarray(rank, jnp.int32) * s_blk
    hi = jnp.clip((eff + blk - 1) // blk - 1, 0, n_blocks - 1)
    if window > 0 and not ring:
        lo = jnp.clip((eff - window) // blk, 0, hi)
    else:
        lo = jnp.zeros_like(hi)
    return jnp.where(eff > 0, hi - lo + 1, 0).astype(jnp.int32)


_SIGNALS: Counter = Counter()


def record_signal(name: str, n: int = 1) -> None:
    """Record a detection-signal firing (always on, host-side — see the
    label list in the module docstring)."""
    _SIGNALS[name] += n


def signal_totals() -> Counter:
    """Snapshot of the detection-signal counters."""
    return Counter(_SIGNALS)


def reset_signals() -> None:
    """Zero the detection-signal counters (test isolation)."""
    _SIGNALS.clear()


_PROBES: Counter = Counter()


def record_probe(name: str, n: int = 1) -> None:
    """Account SDC-probe overhead (always on, host-side — see the
    probe-counter label list in the module docstring)."""
    _PROBES[name] += n


def probe_totals() -> Counter:
    """Snapshot of the probe-overhead counters."""
    return Counter(_PROBES)


def reset_probes() -> None:
    """Zero the probe-overhead counters (test / bench isolation)."""
    _PROBES.clear()


_SERVE: Counter = Counter()


def record_serve(name: str, n: int = 1) -> None:
    """Count serve-loop work (always on, host-side — see the serve-loop
    label list in the module docstring)."""
    _SERVE[name] += n


def serve_totals() -> Counter:
    """Snapshot of the serve-loop counters."""
    return Counter(_SERVE)


def reset_serve() -> None:
    """Zero the serve-loop counters (test isolation)."""
    _SERVE.clear()


@contextmanager
def counting():
    """Enable counters; yields the live Counter (read totals inside or
    right after the block).  Entering the outermost context resets the
    counts; nested contexts share the same Counter."""
    global _ACTIVE
    if _ACTIVE == 0:
        _COUNTS.clear()
    _ACTIVE += 1
    try:
        yield _COUNTS
    finally:
        _ACTIVE -= 1
