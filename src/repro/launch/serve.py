"""Serving driver: prefill a batch of prompts, then decode with the
ClusterFusion dataflow.

On the CPU (``JAX_PLATFORMS=cpu`` with 8 host devices, Pallas in
interpret mode) the CLI runs a ``reduced()`` smoke config.  On a TPU it
runs over a ``(1, n)`` mesh of the chips JAX finds; ``--layers N``
serves the published widths with the depth cut to N layers
(``chip_smoke.py`` drives that path through the slot scheduler).

Two serving modes share the engine:

* :func:`generate` — lockstep batch completion (all prompts together).
* :mod:`repro.serving.scheduler` — continuous batching over the ragged
  decode engine: :func:`build_engine_full` additionally jits the
  targeted prefill-insert (``admit``) and the slot-release (``retire``)
  steps the scheduler drives (DESIGN.md §6).
"""
from __future__ import annotations

import argparse
import dataclasses
import time
import warnings
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import depth_cut, get_config, reduced
from repro.core.autotune import tune_serving
from repro.launch.mesh import (device_mesh, dp_axes_of, dp_size_of,
                               make_test_mesh)
from repro.launch.runtime import (check_interpret, enable_compile_cache,
                                  require_tpu)
from repro.launch.specs import _unwrap2, _wrap2, ctx_for, serving_layout
from repro.configs.base import ShapeConfig
from repro.models.transformer import init_device_major, param_specs
from repro.serving.engine import (EngineOptions, ServeConfig, decode_step,
                                  init_decode_state)
from repro.serving.prefill import prefill
from repro.serving.sampling import (SAMPLING_LEAVES, host_sampling_rows,
                                    reset_sampling_state)


class EngineHandle(NamedTuple):
    """Everything a serving loop needs.  ``params`` is the
    ``{"train", "serve"}`` layout pair; ``prefill_fn``/``decode_fn`` are
    the classic lockstep steps; ``admit_fn``/``retire_fn`` drive
    continuous batching (serving/scheduler.py):

    * ``admit_fn(params["train"], state, tokens [B, S_cap],
      lengths [B], samp=None)`` — targeted prefill-insert: slots with
      ``lengths[b] > 0`` get the padded prompt row ``b`` prefilled into
      their cache at offset 0, take their per-request sampling rows
      (``samp``: the ``state["sampling"]`` leaf layout,
      serving/sampling.py; ``None`` = greedy defaults — the legacy
      4-argument call keeps working) and sample their first token;
      every other slot's state rides through untouched.
    * ``retire_fn(state, mask [B])`` — frees the masked slots
      (``cache_lens ← −1``: no KV writes, zero attend work, sampling
      params back to the greedy defaults).
    """
    params: Any
    prefill_fn: Callable
    decode_fn: Callable
    admit_fn: Callable
    retire_fn: Callable
    state: Any
    lay: Any
    scfg: ServeConfig
    cfg: Any
    mesh: Any
    batch_global: int
    # re-materialize the serve layout from the train view — the
    # weight-SDC healing path (serving/integrity.py) calls this after a
    # fingerprint mismatch, then re-verifies before the replica rejoins
    repack_fn: Optional[Callable] = None


def build_engine(cfg, mesh, *, max_seq: int, batch_global: int,
                 fused_combine: bool = False, cluster: Optional[int] = None,
                 backend: str = "xla", interpret: bool = False,
                 block_s: Optional[int] = None, prepack="auto",
                 autotune_table: Optional[str] = None):
    """Returns (params, jitted prefill fn, jitted decode fn, state, lay,
    scfg) — the classic 6-tuple; see :func:`build_engine_full` for the
    scheduler-ready handle with the admit/retire steps."""
    h = build_engine_full(
        cfg, mesh, max_seq=max_seq, batch_global=batch_global,
        options=EngineOptions(
            fused_combine=fused_combine, cluster=cluster, backend=backend,
            interpret=interpret, block_s=block_s, prepack=prepack,
            autotune_table=autotune_table))
    return h.params, h.prefill_fn, h.decode_fn, h.state, h.lay, h.scfg


_LEGACY_KWARGS_WARNED = False


def _resolve_options(options: Optional[EngineOptions],
                     legacy: dict) -> EngineOptions:
    """Deprecation shim: fold ``build_engine_full``'s pre-options keyword
    arguments into an :class:`EngineOptions`, warning ONCE per process.
    Unknown names raise immediately (same contract as a real keyword
    mismatch) instead of silently building a differently-shaped engine."""
    if not legacy:
        return options or EngineOptions()
    unknown = set(legacy) - set(EngineOptions.__dataclass_fields__)
    if unknown:
        raise TypeError(
            f"build_engine_full() got unexpected keyword arguments "
            f"{sorted(unknown)}")
    global _LEGACY_KWARGS_WARNED
    if not _LEGACY_KWARGS_WARNED:
        _LEGACY_KWARGS_WARNED = True
        warnings.warn(
            "passing engine construction knobs as individual keyword "
            "arguments to build_engine_full is deprecated — pass "
            "options=EngineOptions(...) instead (the legacy kwargs keep "
            "working through this shim)",
            DeprecationWarning, stacklevel=3)
    return dataclasses.replace(options or EngineOptions(), **legacy)


def build_engine_full(cfg, mesh, *, max_seq: int, batch_global: int,
                      options: Optional[EngineOptions] = None,
                      **legacy_kwargs) -> EngineHandle:
    """Build every jitted serving step for (cfg × mesh).

    All construction knobs live on ONE object:
    ``options=EngineOptions(...)`` (serving/engine.py) — backend /
    interpret / block sizes / prepack / the state-leaf flags
    (track_work, check_finite, kv_fingerprint, shadow_head,
    stash_candidates) /
    fused_combine / cluster / autotune_table / fuse_head /
    plan_seq_len.  The pre-options surface (the same names as
    individual keyword arguments) still works through a deprecation
    shim that warns once per process and folds them into ``options``.

    ``options.backend``: "xla" | "pallas" | "auto" — local-stage compute
    for the decode dataflow (DESIGN.md §2); ``interpret`` runs the
    Pallas kernels in interpret mode (CPU only — it raises on a TPU
    mesh); ``block_s/f/v``
    override the autotuned tiles; ``autotune_table`` persists plans
    across launches.

    ``options.prepack``: "auto" | "on" | "off" — serve-layout weight
    prepack (serving/prepack.py); auto enables it whenever the Pallas
    backend is selected.  ``params`` is returned as
    ``{"train": …, "serve": …}``: the training-layout tree (prefill /
    checkpoints) and the decode-plan tree, materialized ONCE at load
    with ``out_shardings`` (identical to "train" when prepack is off).
    ``generate`` routes each to its step.

    ``options.track_work`` adds the per-slot attend-step counters
    (``state["work_blocks"]``, core/tracecount.py) the scheduler tests
    read.  ``check_finite`` adds the per-slot integrity sentinel
    (``state["nonfinite"]``) the fleet router's health probes poll
    (serving/router.py, DESIGN.md §9); off by default so the bench path
    traces an identical step.  ``kv_fingerprint`` adds the incremental
    per-slot/per-layer KV checksum leaves and ``shadow_head`` the
    committed-token (residual, head_val, token) stash the SDC monitor
    verifies on probe (serving/integrity.py) — both off by default for
    the same reason.  ``fuse_head=False`` skips the LM-head/sampling
    tail bundle on the prepacked path (ablation/parity knob: same fused
    layers, loose XLA head tail — tests prove the two sample
    token-identically).  ``plan_seq_len`` keys the autotune bucket on
    the EXPECTED MAX LIVE length rather than the allocated ``max_seq``
    — ragged serving allocates slack capacity that no slot's live span
    ever reaches, and the plan (block_s, cluster) should follow the
    live spans (DESIGN.md §6).
    """
    opt = _resolve_options(options, legacy_kwargs)
    fused_combine, cluster = opt.fused_combine, opt.cluster
    backend, interpret = opt.backend, opt.interpret
    block_s, block_f, block_v = opt.block_s, opt.block_f, opt.block_v
    prepack, autotune_table = opt.prepack, opt.autotune_table
    track_work, fuse_head = opt.track_work, opt.fuse_head
    check_finite = opt.check_finite
    kv_fingerprint, shadow_head = opt.kv_fingerprint, opt.shadow_head
    plan_seq_len = opt.plan_seq_len
    check_interpret(interpret, mesh)
    ms = mesh.shape["model"]
    dp_axes = dp_axes_of(mesh)
    dp = dp_size_of(mesh)
    shape = ShapeConfig("serve", max_seq, batch_global, "decode")
    lay = serving_layout(cfg, shape, ms)
    if cluster is not None:
        from repro.models.transformer import Layout
        lay = Layout(ms, heads_sub=ms // cluster)
    ctx = ctx_for(mesh, lay, fused_combine=fused_combine)
    b_loc = batch_global // dp if batch_global % dp == 0 else batch_global
    b_shard = batch_global % dp == 0 and batch_global >= dp
    # tune with the PER-DEVICE batch — the kernel VMEM tiles and per-chip
    # byte model see b_loc, not the global batch
    plan = tune_serving(cfg, seq_len=plan_seq_len or max_seq, batch=b_loc,
                        model_axis=ms, backend=backend, prepack=prepack,
                        table_path=autotune_table)
    scfg = ServeConfig(max_seq=max_seq, batch_local=b_loc,
                       backend=plan.backend, interpret=interpret,
                       block_s=block_s or plan.block_s,
                       block_f=block_f or plan.block_f,
                       block_v=block_v or plan.block_v,
                       prepack=plan.prepack, track_work=track_work,
                       check_finite=check_finite,
                       kv_fingerprint=kv_fingerprint,
                       shadow_head=shadow_head,
                       stash_candidates=opt.stash_candidates)
    params_abs = jax.eval_shape(
        lambda: init_device_major(cfg, lay, jax.random.PRNGKey(0)))
    p_specs = param_specs(cfg, params_abs)
    out_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs)
    params = jax.jit(lambda: init_device_major(cfg, lay,
                                               jax.random.PRNGKey(0)),
                     out_shardings=out_sh)()

    # Serve-layout prepack: ONE jitted re-layout at load time; the decode
    # step then performs zero weight gathers / slices (DESIGN.md §2).
    # Only the attention subtree goes through the pack — every other
    # leaf of the serve tree aliases the training tree's buffers, so the
    # extra residency is just the packed attention tensors (DESIGN.md §5).
    if scfg.prepack:
        from functools import partial as _partial
        from repro.serving.prepack import (attn_subtree, bundle_ffn,
                                           bundle_head, merge_packed,
                                           prepack_for_serving)
        pp_fn = _partial(prepack_for_serving, cfg, lay,
                         backend=scfg.backend)
        sub_abs = jax.eval_shape(pp_fn, attn_subtree(params_abs))
        sub_specs = param_specs(cfg, sub_abs)
        sub_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), sub_specs)
        jit_pack = jax.jit(pp_fn, out_shardings=sub_sh)
        packed_attn = jit_pack(attn_subtree(params))
        # dense-FFN and LM-head bundles are pure aliasing (no jit, no
        # copy): the Megatron layout already IS the fused-FFN serve
        # layout, and the head bundle binds the tied-embed/lm_head table
        # + final_norm scale for the fused sampling tail
        def _bundles(tree):
            tree = bundle_ffn(cfg, tree, backend=scfg.backend)
            if fuse_head:
                tree = bundle_head(cfg, tree, backend=scfg.backend)
            return tree
        params_serve = _bundles(merge_packed(params, packed_attn))
        sv_specs = _bundles(merge_packed(p_specs, sub_specs))

        def repack_fn(train_tree):
            # the healing re-materialization runs the SAME jitted pack +
            # alias bundles the load path ran, so a healed serve tree is
            # bit-identical to the original (fingerprints re-verify)
            return _bundles(merge_packed(
                train_tree, jit_pack(attn_subtree(train_tree))))
    else:
        params_serve, sv_specs = params, p_specs

        def repack_fn(train_tree):
            return train_tree     # prepack off: serve tree IS train tree
    params = {"train": params, "serve": params_serve}

    from repro.launch.specs import state_spec_tree
    s_abs_local = jax.eval_shape(lambda: init_decode_state(cfg, scfg, ctx))
    s_specs = state_spec_tree(
        jax.tree.map(lambda l: jax.ShapeDtypeStruct((dp, ms) + tuple(l.shape),
                                                    l.dtype), s_abs_local),
        dp_axes)

    def init_body():
        return _wrap2(init_decode_state(cfg, scfg, ctx))

    state = jax.jit(shard_map(init_body, mesh=mesh, in_specs=(),
                              out_specs=s_specs, check_vma=False))()

    tok1 = P(dp_axes) if b_shard else P()

    def pf_body(params, state, tokens, fe, lengths, sampling=None):
        st = _unwrap2(state)
        nxt, new = prefill(ctx, cfg, scfg, params, st, tokens, fe,
                           lengths=lengths, sampling=sampling)
        return nxt, _wrap2(new)

    def dec_body(params, state, tokens):
        st = _unwrap2(state)
        nxt, new = decode_step(ctx, cfg, scfg, params, st, tokens)
        return nxt, _wrap2(new)

    def rt_body(state, mask):
        st = dict(_unwrap2(state))
        st["cache_lens"] = jnp.where(mask > 0, jnp.int32(-1),
                                     st["cache_lens"])
        st["sampling"] = reset_sampling_state(st["sampling"], mask > 0)
        if "nonfinite" in st:        # retired slot: clear its sentinel
            st["nonfinite"] = jnp.where(mask > 0, jnp.int32(0),
                                        st["nonfinite"])
        return _wrap2(st)

    fe_spec = P(*tok1, None, None) if cfg.frontend is not None else P()
    pf = jax.jit(shard_map(
        lambda p, s, t, fe: pf_body(p, s, t, fe, None), mesh=mesh,
        in_specs=(p_specs, s_specs, P(*tok1, None), fe_spec),
        out_specs=(tok1, s_specs), check_vma=False))
    samp_specs = {name: tok1 for name in SAMPLING_LEAVES}
    admit_jit = jax.jit(shard_map(
        lambda p, s, t, ln, sp: pf_body(p, s, t, None, ln, sp), mesh=mesh,
        in_specs=(p_specs, s_specs, P(*tok1, None), tok1, samp_specs),
        out_specs=(tok1, s_specs), check_vma=False))

    def admit(params, state, tokens, lengths, samp=None):
        # host wrapper: the legacy 4-argument admit keeps working — a
        # missing ``samp`` fills every row with the greedy defaults, so
        # admitted slots land exactly where the pre-sampling engine put
        # them (bit-identical first token)
        if samp is None:
            samp = host_sampling_rows(batch_global)
        return admit_jit(params, state, tokens, lengths, samp)
    dec = jax.jit(shard_map(dec_body, mesh=mesh,
                            in_specs=(sv_specs, s_specs, tok1),
                            out_specs=(tok1, s_specs), check_vma=False))
    retire = jax.jit(shard_map(rt_body, mesh=mesh,
                               in_specs=(s_specs, tok1),
                               out_specs=s_specs, check_vma=False))
    return EngineHandle(params, pf, dec, admit, retire, state, lay, scfg,
                        cfg, mesh, batch_global, repack_fn)


def build_replicas(cfg, mesh, *, n_replicas: int, max_seq: int,
                   batch_global: int,
                   options: Optional[EngineOptions] = None, **kw):
    """N engine replicas for the fleet router (serving/router.py).

    Each replica is an independent :class:`EngineHandle` on ``mesh``
    (in production each would own its own mesh slice; tests run N
    single-mesh engines), initialized from the SAME PRNG seed — so any
    replica produces the identical stream for a given (prefix, sampling
    params, emit offset), which is the invariant reconstructive recovery
    relies on: a request re-queued onto a survivor continues
    token-for-token where the dead replica's journal left off — sampled
    requests included, via the journaled seed + emit offset
    (DESIGN.md §9).

    ``check_finite``/``kv_fingerprint``/``shadow_head`` default ON here
    (unlike ``build_engine_full``): the router's health probes read the
    per-slot non-finite sentinel and the SDC monitor's fingerprint /
    shadow leaves (serving/integrity.py).  Pass
    ``options=EngineOptions(...)`` to override; bare keyword arguments
    still route through ``build_engine_full``'s deprecation shim.
    """
    if options is None:
        options = EngineOptions(check_finite=True, kv_fingerprint=True,
                                shadow_head=True)
    return [build_engine_full(cfg, mesh, max_seq=max_seq,
                              batch_global=batch_global,
                              options=options, **kw)
            for _ in range(n_replicas)]


def generate(cfg, params, pf, dec, state, prompts: jnp.ndarray,
             n_new: int, fe=None):
    """prompts: [B, S_prompt] → tokens [B, n_new] (greedy).

    ``params`` is build_engine's ``{"train", "serve"}`` pair: prefill
    consumes the training layout, the decode loop the serve layout.
    """
    p_train, p_serve = params["train"], params["serve"]
    nxt, state = pf(p_train, state, prompts, fe)
    out = [nxt]
    for _ in range(n_new - 1):
        nxt, state = dec(p_serve, state, nxt)
        out.append(nxt)
    return jnp.stack(out, axis=-1), state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--backend", default="xla",
                    choices=("xla", "pallas", "auto"))
    ap.add_argument("--interpret", action="store_true",
                    help="Pallas interpret mode (CPU)")
    ap.add_argument("--prepack", default="auto",
                    choices=("auto", "on", "off"),
                    help="serve-layout weight prepack (auto: on whenever "
                         "the Pallas backend is selected)")
    ap.add_argument("--layers", type=int, default=0,
                    help="serve the published widths with the depth cut "
                         "to this many layers (default: the reduced() "
                         "smoke config)")
    args = ap.parse_args()
    enable_compile_cache()
    cfg = get_config(args.arch)
    cfg = depth_cut(cfg, args.layers) if args.layers else reduced(cfg)
    on_cpu = jax.default_backend() == "cpu"
    mesh = make_test_mesh() if on_cpu else device_mesh(require_tpu())
    params, pf, dec, state, lay, scfg = build_engine(
        cfg, mesh, max_seq=args.prompt_len + args.tokens + 8,
        batch_global=args.batch, backend=args.backend,
        interpret=args.interpret, prepack=args.prepack)
    key = jax.random.PRNGKey(0)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    fe = None
    if cfg.frontend is not None:
        fe = jax.random.normal(key, (args.batch, cfg.frontend.num_positions,
                                     cfg.frontend.feature_dim))
    t0 = time.time()
    toks, _ = generate(cfg, params, pf, dec, state, prompts, args.tokens, fe)
    dt = time.time() - t0
    print(f"generated {args.tokens} tokens × {args.batch} seqs in {dt:.2f}s "
          f"(cluster={lay.cluster})")
    print(np.asarray(toks)[:2])


if __name__ == "__main__":
    main()
