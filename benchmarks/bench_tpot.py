"""Paper Fig. 17 analogue: end-to-end time-per-output-token.

Measurements per arch:

* ``tpot_<variant>_<arch>`` — the fully fused decode step (one dispatch
  for embed + L layers + head + sampling) on the test mesh, per backend
  variant: ``xla``, ``pallas`` (PR-1 adapter path, per-step weight
  gathers) and ``pallas_prepack`` (serve-layout weights + in-kernel
  Output-Projection, serving/prepack.py).
* ``tpot_unfused_<arch>``  — a REAL per-layer decode loop on one device:
  the same transformer blocks, but embed / each layer / head+sample are
  separate ``jit`` dispatches (the per-op launch-boundary regime the
  paper's baseline pays).  The fused/unfused ratio is the honest fusion
  speedup — same FLOPs, different dispatch granularity.
* ``tpot_cachelen_<variant>_<arch>_<L>`` — cache-length sweep: decode
  step time after prefilling L tokens (cost ∝ live prefix, DESIGN.md §3).
* ``tpot_sampling_<s>_<variant>_<arch>`` — sampling-variant sweep
  (``greedy`` / ``topk8`` / ``topp0.9``): the SAME jitted decode step
  timed under different per-slot sampling-param state leaves
  (serving/sampling.py) — evidence temperature/top-k/top-p stay in the
  fused tail (no retrace, no extra dispatch).  The report also carries
  ``head_sample_k`` (the fused tail's candidate width, gated exactly)
  and the k-wide ``head_ici_bytes_per_step`` model.
* ``--trace`` — ragged-arrival trace mode: a random request trace runs
  through the continuous-batching scheduler (serving/scheduler.py) and
  the report gains a ``ragged_trace`` section with per-request TPOT,
  slot occupancy, decode-dispatch count and the per-slot attend-block
  work counters (DESIGN.md §6) — plus a ``router_chaos`` section: the
  multi-replica router (serving/router.py) driven through every fault
  kind (serving/faults.py), emitting deterministic detection-latency /
  recovery-steps / availability / oracle-exactness columns that
  scripts/check_bench.py gates exactly (DESIGN.md §9) — plus an
  ``sdc_sweep`` section: the single-bit silent-data-corruption
  coverage matrix (serving/sweep.py — detection coverage, latency,
  oracle exactness per (fault kind × bit), and the fault-free
  false-positive / probe-overhead control row).

Besides the CSV rows, the run emits a machine-readable ``BENCH_tpot.json``
(``--out``) carrying TPOT per (arch × variant × cache_len bucket) plus
the MODELED per-step ICI weight-gather bytes
(``repro.core.autotune.weight_gather_bytes_per_step``) — which must read
0 on the prepacked Pallas path — so the perf trajectory is tracked
across PRs.  ``--smoke`` runs a tiny single-arch sweep for CI (Pallas in
interpret mode on CPU).
"""
import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_fn
from repro.configs import get_config, reduced
from repro.core import tracecount
from repro.core.autotune import (ffn_cluster_reduce_bytes_per_step,
                                 ffn_psum_bytes_per_step,
                                 head_hbm_logits_bytes_per_step,
                                 head_ici_bytes_per_step,
                                 weight_gather_bytes_per_step)
from repro.launch.mesh import make_test_mesh
from repro.launch.serve import build_engine
from repro.models import layout_for, single_device_ctx, unwrap_local
from repro.models.transformer import init_device_major
from repro.serving.engine import (ServeConfig, decode_block,
                                  init_decode_state)
from repro.serving.sampling import CAND_K


def _unfused_decode_us(cfg, max_seq: int, batch: int, iters: int = 15):
    """(unfused_us, fused_us) per-token times on one device.

    Unfused: every layer is its own jit call (plus embed and
    head+sample), i.e. L+2 real dispatches of real work per token — the
    launch-bound baseline the paper compares against, not a stand-in.
    Fused: the identical work as ONE ``decode_step`` dispatch.  Each
    dispatch is a trivial 1×1 ``shard_map`` so the dataflow's axis names
    exist (all collectives degenerate to no-ops at size 1).
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    mesh1 = jax.make_mesh((1, 1), ("data", "model"))
    ctx = single_device_ctx()
    lay = layout_for(cfg, 1)
    params_dm = init_device_major(cfg, lay, jax.random.PRNGKey(0))
    params = unwrap_local(params_dm)
    scfg = ServeConfig(max_seq=max_seq, batch_local=batch)
    state = init_decode_state(cfg, scfg, ctx)
    kinds = cfg.layer_kinds
    period = len(cfg.block_pattern)
    n_groups = cfg.n_layers // period

    import math
    from repro.models.layers import (EmbedParams, embed_lookup,
                                     lm_head_logits, rms_norm, softcap)
    from repro.serving.engine import greedy_sample

    def _sm(fn, n_args):
        return jax.jit(shard_map(fn, mesh=mesh1, in_specs=(P(),) * n_args,
                                 out_specs=P(), check_vma=False))

    embed_step = _sm(lambda tok: embed_lookup(
        ctx, EmbedParams(params["embed"]), tok)
        * (jnp.asarray(math.sqrt(cfg.d_model), jnp.bfloat16)
           if cfg.tie_embeddings else 1), 1)

    def _mk_group(kind):
        # one dispatch = slice group gi, run the block, write the cache back
        def f(blks, gi, x, caches, cl):
            blk = jax.tree.map(lambda l: l[gi], blks)
            cache_i = jax.tree.map(lambda l: l[gi], caches)
            x, nc = decode_block(ctx, cfg, kind, blk, x, cache_i, cl, scfg)
            new = jax.tree.map(
                lambda full, upd: full.at[gi].set(upd.astype(full.dtype)),
                caches, nc)
            return x, new
        return _sm(f, 5)

    def _mk_tail(kind):
        def f(blk, x, cache, cl):
            return decode_block(ctx, cfg, kind, blk, x, cache, cl, scfg)
        return _sm(f, 4)

    _group = {k: _mk_group(k) for k in set(kinds)}
    _tail = {k: _mk_tail(k) for k in set(kinds[n_groups * period:])} \
        if cfg.n_layers > n_groups * period else {}

    def _head(x):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        logits = lm_head_logits(ctx, table, x)
        if cfg.logit_softcap:
            logits = softcap(logits, cfg.logit_softcap)
        return greedy_sample(ctx, logits)

    head_step = _sm(_head, 1)

    def one_token(tok, state):
        cache_len = state["cache_lens"]
        x = embed_step(tok)
        for gi in range(n_groups):
            for p_i in range(period):
                x, state["layers"][p_i] = _group[kinds[p_i]](
                    params["blocks"][p_i], jnp.int32(gi), x,
                    state["layers"][p_i], cache_len)
        for t_i, blk in enumerate(params["tail"]):
            x, state["tail"][t_i] = _tail[kinds[n_groups * period + t_i]](
                blk, x, state["tail"][t_i], cache_len)
        return head_step(x), state

    tok = jnp.zeros((batch,), jnp.int32)
    st = {**state, "layers": list(state["layers"]),
          "tail": list(state["tail"])}
    t_unfused = time_fn(lambda: one_token(tok, st)[0], iters=iters)

    # apples-to-apples fused reference: the SAME single-device work as ONE
    # dispatch (full decode_step under a single jit)
    from repro.serving.engine import decode_step
    fused = _sm(lambda p, s, t: decode_step(ctx, cfg, scfg, p, s, t), 3)
    t_fused = time_fn(lambda: fused(params_dm, state, tok), iters=iters)
    return t_unfused, t_fused


# Per-slot sampling-param overrides for the sampling-variant TPOT
# sweep: the decode step's signature is sampling-independent (the
# params are state leaves — serving/sampling.py), so each variant is
# the SAME jitted program timed under different leaf values.  The
# greedy row must cost the same as the other two: any spread beyond
# noise means sampling left the fused tail.
_SAMPLING_VARIANTS = (
    ("greedy", {}),                                   # default leaves
    ("topk8", {"temp": 0.7, "topk": 8}),
    ("topp0.9", {"temp": 0.7, "topp": 0.9}),
)


_VARIANTS = (
    # (label, build_engine kwargs)
    ("xla", dict(backend="xla")),
    ("pallas", dict(backend="pallas", prepack="off")),      # PR-1 path
    ("pallas_prepack", dict(backend="pallas", prepack="on")),
    # forced cluster=2: the configuration where the PR-1 path actually
    # pays per-step weight-gather ICI (nonzero modeled column) and the
    # prepacked path reads 0
    ("pallas_c2", dict(backend="pallas", prepack="off", cluster=2)),
    ("pallas_prepack_c2", dict(backend="pallas", prepack="on", cluster=2)),
)


def _bench_variant(cfg, arch, label, kw, *, max_seq, batch, prompt_len,
                   cache_lens, iters, interpret, rows):
    mesh = make_test_mesh()
    params, pf, dec, state, lay, scfg = build_engine(
        cfg, mesh, max_seq=max_seq, batch_global=batch,
        interpret=interpret and kw.get("backend") != "xla", **kw)
    key = jax.random.PRNGKey(0)
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)
    fe = None
    if cfg.frontend is not None:
        fe = jax.random.normal(key, (batch, cfg.frontend.num_positions,
                                     cfg.frontend.feature_dim))
    p_serve = params["serve"]
    # Trace-time structure counters — measured BEFORE the first dispatch
    # (a cached trace would skip the counting hooks): exact per-step
    # pallas_call launch and activation-psum counts of this variant.
    tok0 = jnp.zeros((batch,), jnp.int32)
    with tracecount.counting() as c:
        jax.eval_shape(dec, p_serve, state, tok0)
    launches = int(c.get("pallas_kernel", 0))
    psums = int(c.get("psum_model", 0))
    nxt, st = pf(params["train"], state, prompts, fe)
    t = time_fn(lambda: dec(p_serve, st, nxt), iters=iters)
    samp_us = {}
    for s_label, over in _SAMPLING_VARIANTS:
        st_s = dict(st)
        st_s["sampling"] = {
            name: (jnp.full_like(leaf, over[name]) if name in over
                   else leaf)
            for name, leaf in st["sampling"].items()}
        t_s = time_fn(lambda: dec(p_serve, st_s, nxt), iters=iters)
        samp_us[s_label] = t_s
        rows.append(row(f"tpot_sampling_{s_label}_{label}_{arch}", t_s,
                        f"k={CAND_K}," + (",".join(
                            f"{n}={v}" for n, v in over.items()) or
                            "greedy_defaults")))
    byte_kw = dict(model_axis=mesh.shape["model"], batch=scfg.batch_local,
                   backend=scfg.backend, prepack=scfg.prepack)
    gather_bytes = weight_gather_bytes_per_step(
        cfg, model_axis=mesh.shape["model"], cluster_size=lay.cluster,
        backend=scfg.backend, prepack=scfg.prepack)
    ffn_psum_bytes = ffn_psum_bytes_per_step(cfg, **byte_kw)
    ffn_reduce_bytes = ffn_cluster_reduce_bytes_per_step(cfg, **byte_kw)
    head_ici = head_ici_bytes_per_step(cfg, **byte_kw)
    head_hbm = head_hbm_logits_bytes_per_step(cfg, **byte_kw)
    rows.append(row(f"tpot_{label}_{arch}", t,
                    f"cluster={lay.cluster},prepack={scfg.prepack},"
                    f"ici_weight_gather_bytes={gather_bytes:.0f},"
                    f"ffn_psum_bytes={ffn_psum_bytes:.0f},"
                    f"head_hbm_logits_bytes={head_hbm:.0f},"
                    f"pallas_launches={launches},psum_model={psums}"))
    sweep = {}
    for L in cache_lens:
        pr = jax.random.randint(key, (batch, L), 0, cfg.vocab_size)
        nxt_l, st_l = pf(params["train"], state, pr, fe)
        t_l = time_fn(lambda: dec(p_serve, st_l, nxt_l), iters=iters)
        sweep[L] = t_l
        rows.append(row(f"tpot_cachelen_{label}_{arch}_{L}", t_l,
                        f"live={L}/{max_seq}"))
    return {
        "tpot_us": t,
        "cachelen_us": {str(L): sweep[L] for L in cache_lens},
        "cluster": lay.cluster,
        "backend": scfg.backend,
        "prepack": scfg.prepack,
        "ici_weight_gather_bytes_per_step": gather_bytes,
        # full-block fusion evidence (DESIGN.md §7): per-layer FFN psum
        # bytes eliminated by the fused ClusterReduce, its replacement's
        # tree-traffic, and the measured trace-time launch/psum counts
        "ffn_psum_ici_bytes_per_step": ffn_psum_bytes,
        "ffn_fused_reduce_ici_bytes_per_step": ffn_reduce_bytes,
        # LM-head/sampling-tail evidence (DESIGN.md §7 L5): the modeled
        # per-chip HBM bytes of the [B, V_loc] logits tensor the fused
        # head deletes (0 on the prepacked Pallas path) and the (value,
        # index) pair tree-reduce ICI traffic both tails pay
        "head_hbm_logits_bytes_per_step": head_hbm,
        "head_ici_bytes_per_step": head_ici,
        # candidate width of the fused tail's streaming top-k — gated
        # exactly (a width change moves the ICI model AND the sampling
        # exactness envelope, so it must never drift silently)
        "head_sample_k": CAND_K,
        # same jitted step under the three sampling-param settings:
        # wall-noise on CPU, but the spread is the evidence sampling
        # stays in-state (no per-variant retrace)
        "sampling_tpot_us": samp_us,
        "pallas_launches_per_step": launches,
        "psum_model_per_step": psums,
    }


def _bench_ragged_trace(arch, *, n_slots=3, prompt_cap=12, max_new_cap=10,
                        n_requests=8, backend="xla", interpret=False,
                        rows=None, seed=0):
    """Random arrival trace through the slot scheduler: per-request TPOT
    (wall time from admission to finish over tokens emitted) and slot
    occupancy.  CPU walls are relative indicators; the occupancy /
    dispatch-count / work-counter columns are exact."""
    import time as _time

    from repro.launch.mesh import make_test_mesh as _mk
    from repro.launch.serve import EngineOptions, build_engine_full
    from repro.serving.scheduler import Request, SlotScheduler

    cfg = reduced(get_config(arch))
    mesh = _mk(data=1, model=8)          # scheduler batch rides unsharded
    eng = build_engine_full(
        cfg, mesh, max_seq=prompt_cap + max_new_cap + 8,
        batch_global=n_slots,
        options=EngineOptions(
            backend=backend, interpret=interpret, track_work=True,
            plan_seq_len=prompt_cap + max_new_cap))  # bucket on max LIVE len
    sched = SlotScheduler(eng, prompt_cap=prompt_cap)
    rng = np.random.default_rng(seed)
    trace = []
    for rid in range(n_requests):
        arrival = int(rng.integers(0, max(1, n_requests // 2)))
        plen = int(rng.integers(2, prompt_cap + 1))
        n_new = int(rng.integers(2, max_new_cap + 1))
        trace.append((arrival, Request(
            rid, [int(t) for t in rng.integers(0, cfg.vocab_size, plen)],
            n_new)))
    pending = sorted(trace, key=lambda ar: ar[0])
    i, tick_wall = 0, []
    while (i < len(pending) or not sched.idle()) and sched.tick < 10_000:
        while i < len(pending) and pending[i][0] <= sched.tick:
            sched.submit(pending[i][1])
            i += 1
        t0 = _time.perf_counter()
        sched.step()
        tick_wall.append(_time.perf_counter() - t0)
    assert sched.idle(), "ragged trace did not drain"
    per_request = {}
    for rid, res in sched.results.items():
        span_us = sum(tick_wall[res.admit_tick:res.finish_tick + 1]) * 1e6
        per_request[str(rid)] = {
            "tpot_us": span_us / max(1, len(res.tokens)),
            "n_tokens": len(res.tokens),
            "slot": res.slot,
            "admit_tick": res.admit_tick,
            "finish_tick": res.finish_tick,
        }
    occ = float(np.mean(sched.occupancy)) if sched.occupancy else 0.0
    mean_tpot = float(np.mean([r["tpot_us"] for r in per_request.values()]))
    if rows is not None:
        rows.append(row(f"tpot_ragged_trace_{arch}", mean_tpot,
                        f"occupancy={occ:.2f},ticks={sched.tick},"
                        f"dispatches={sched.decode_calls}"))
    return {
        "arch": arch,
        "backend": eng.scfg.backend,
        "n_slots": n_slots,
        "n_requests": n_requests,
        "ticks": sched.tick,
        "decode_dispatches": sched.decode_calls,
        "mean_slot_occupancy": occ,
        "mean_tpot_us": mean_tpot,
        "per_request": per_request,
        "work_blocks_per_slot": [int(w) for w in sched.work_blocks()],
        "note": "wall-times are relative on CPU; occupancy, dispatch and "
                "work-block columns are exact",
    }


def _bench_router_chaos(arch, *, n_replicas=2, prompt_cap=8, max_new_cap=8,
                        n_requests=6, fault_step=2, rows=None, seed=0):
    """Fleet chaos sweep: a fixed arrival trace through the multi-replica
    router once fault-free (the oracle), then once per fault kind with a
    deterministic mid-trace injection (serving/faults.py).  Every
    emitted column is TICK ARITHMETIC — detection latency, recovery
    steps, availability and oracle-exactness are identical on every
    machine, so check_bench.py gates them exactly like the launch/psum
    counters."""
    from repro.launch.mesh import make_test_mesh as _mk
    from repro.launch.serve import EngineOptions, build_replicas
    from repro.serving.faults import FAULT_KINDS, FaultInjector, FaultSpec
    from repro.serving.router import Router
    from repro.serving.scheduler import Request

    cfg = reduced(get_config(arch))
    if cfg.moe is not None:
        import dataclasses
        cfg = dataclasses.replace(cfg, moe=None)
    mesh = _mk(data=1, model=1)
    engines = build_replicas(cfg, mesh, n_replicas=n_replicas,
                             max_seq=prompt_cap + max_new_cap + 8,
                             batch_global=2,
                             options=EngineOptions(
                                 backend="xla", check_finite=True,
                                 kv_fingerprint=True, shadow_head=True))
    rng = np.random.default_rng(seed)
    trace = []
    for rid in range(n_requests):
        plen = int(rng.integers(2, prompt_cap - 1))
        trace.append((int(rng.integers(0, 4)), Request(
            rid, [int(t) for t in rng.integers(1, cfg.vocab_size, plen)],
            int(rng.integers(3, max_new_cap - 1)))))

    def _run(injectors=None):
        r = Router(engines, prompt_cap=prompt_cap, max_new_cap=max_new_cap,
                   injectors=injectors)
        journal = r.run([(t, Request(q.rid, q.prompt, q.max_new))
                         for t, q in trace])
        return r, {rid: list(e.tokens) for rid, e in journal.items()}

    _, oracle = _run()
    faults = {}
    for kind in FAULT_KINDS:
        inj = FaultInjector(
            [FaultSpec(kind, step=fault_step, target=0, replica=0)])
        router, toks = _run({0: inj})
        lat = router.detection_latency(inj)
        exact = sum(toks[r] == oracle[r] for r in oracle)
        cell = {
            "detect_steps": max(lat) if lat else -1,
            "recovery_steps": router.recovery_steps(),
            "availability_pct": round(100.0 * router.availability(), 2),
            "oracle_exact_pct": round(100.0 * exact / len(oracle), 2),
            "ticks": router.tick,
        }
        faults[kind] = cell
        if rows is not None:
            rows.append(row(
                f"router_chaos_{kind}_{arch}", float(cell["ticks"]),
                f"detect_steps={cell['detect_steps']},"
                f"recovery_steps={cell['recovery_steps']},"
                f"availability={cell['availability_pct']:.1f}%,"
                f"oracle_exact={cell['oracle_exact_pct']:.0f}%"))
    return {
        "arch": arch,
        "n_replicas": n_replicas,
        "n_requests": n_requests,
        "fault_step": fault_step,
        "faults": faults,
        "note": "all columns are deterministic tick arithmetic — gated "
                "exactly by scripts/check_bench.py (ROUTER_GATED_COLUMNS)",
    }


def _bench_sdc_sweep(arch, *, n_replicas=2, prompt_cap=8, max_new=6,
                     n_requests=3, bits=(0, 7, 14), fault_step=2,
                     rows=None, seed=0):
    """Silent-data-corruption coverage sweep: single-bit KV and weight
    flips at representative bf16 positions (mantissa 0, exponent 7/14)
    through the systematic FaultSweep grid, plus the fault-free control
    row (zero false positives, streams byte-equal to the probes-off
    oracle, per-tick probe bytes).  Every coverage/latency column is
    deterministic tick arithmetic; the probe-bytes column is exact shape
    arithmetic — all gated by check_bench.py (SDC_GATED_COLUMNS).  The
    full 16-bit grid runs in the nightly sweep (tests + CI); the bench
    keeps the representative sub-grid so --trace stays fast."""
    from repro.launch.mesh import make_test_mesh as _mk
    from repro.launch.serve import EngineOptions, build_replicas
    from repro.serving.faults import FaultSweep
    from repro.serving.integrity import IntegrityConfig
    from repro.serving.sweep import run_sdc_sweep

    cfg = reduced(get_config(arch))
    if cfg.moe is not None:
        import dataclasses
        cfg = dataclasses.replace(cfg, moe=None)
    mesh = _mk(data=1, model=1)
    engines = build_replicas(cfg, mesh, n_replicas=n_replicas,
                             max_seq=prompt_cap + max_new + 8,
                             batch_global=2,
                             options=EngineOptions(
                                 backend="xla", check_finite=True,
                                 kv_fingerprint=True, shadow_head=True))
    rng = np.random.default_rng(seed)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size,
                                             int(rng.integers(2, 6)))]
               for _ in range(n_requests)]
    cells = run_sdc_sweep(
        engines, prompts=prompts, max_new=max_new, prompt_cap=prompt_cap,
        sweep=FaultSweep(bits=tuple(bits), steps=(fault_step,),
                         targets=(0,), seed=seed),
        icfg=IntegrityConfig(weight_leaves_per_tick=4))
    if rows is not None:
        ff = cells["fault_free"]
        rows.append(row(
            f"sdc_sweep_fault_free_{arch}", ff["probe_bytes_per_tick"],
            f"false_positives={ff['false_positive_signals']:.0f},"
            f"streams_match={ff['streams_match']:.0f}"))
        for key in sorted(k for k in cells if k != "fault_free"):
            c = cells[key]
            rows.append(row(
                f"sdc_sweep_{key}_{arch}", float(c["detect_steps"]),
                f"detected={c['detected_pct']:.0f}%,"
                f"oracle_exact={c['oracle_exact_pct']:.0f}%"))
    return {
        "arch": arch,
        "n_replicas": n_replicas,
        "n_requests": n_requests,
        "fault_step": fault_step,
        "bits": list(bits),
        "cells": cells,
        "note": "coverage/latency columns are deterministic tick "
                "arithmetic; probe_bytes_per_tick is exact shape "
                "arithmetic — gated by scripts/check_bench.py "
                "(SDC_GATED_COLUMNS)",
    }


def main(archs=("llama2-7b", "deepseek-v2-lite"), *, max_seq=256, batch=4,
         prompt_len=64, cache_lens=(16, 64, 192), iters=15,
         out_path="BENCH_tpot.json", fusion_baseline=True,
         ragged_trace=False):
    interpret = jax.default_backend() == "cpu"
    rows = []
    report = {
        "meta": {"device_backend": jax.default_backend(),
                 "pallas_interpret": interpret, "max_seq": max_seq,
                 "batch": batch, "iters": iters,
                 "note": "CPU wall-times are relative indicators "
                         "(interpret-mode Pallas); the modeled ICI bytes "
                         "column is exact"},
        "archs": {},
    }
    for arch in archs:
        cfg = reduced(get_config(arch))
        entry = {"variants": {}}
        for label, kw in _VARIANTS:
            entry["variants"][label] = _bench_variant(
                cfg, arch, label, kw, max_seq=max_seq, batch=batch,
                prompt_len=prompt_len, cache_lens=cache_lens, iters=iters,
                interpret=interpret, rows=rows)
        pp = entry["variants"]["pallas_prepack"]["cachelen_us"]
        p1 = entry["variants"]["pallas"]["cachelen_us"]
        entry["prepack_speedup_by_bucket"] = {
            k: p1[k] / max(pp[k], 1e-9) for k in pp}
        # Wall-clock comparison is meaningful only when the Pallas kernels
        # actually compile (TPU); interpret-mode CPU walls are evaluation
        # noise — there the exact modeled ICI column carries the claim.
        entry["prepack_le_pallas_all_buckets"] = (
            all(pp[k] <= p1[k] for k in pp) if not interpret else None)
        # (no assert on the modeled prepack bytes being 0 — that is true
        # by construction of the model; the MEASURED guarantee of zero
        # per-step weight movement lives in tests/test_prepack.py's
        # trace-time counters)

        if fusion_baseline:
            # REAL per-layer dispatch baseline: L+2 jit calls of actual
            # work, vs the same single-device work fused into one dispatch.
            t_unfused, t_fused1 = _unfused_decode_us(
                cfg, max_seq=max_seq, batch=batch, iters=iters)
            rows.append(row(f"tpot_fused1_{arch}", t_fused1,
                            "n_dispatches=1"))
            rows.append(row(
                f"tpot_unfused_{arch}", t_unfused,
                f"n_dispatches={cfg.n_layers + 2},"
                f"fusion_speedup={t_unfused / max(t_fused1, 1e-9):.2f}x"))
            entry["fusion"] = {"tpot_fused1_us": t_fused1,
                               "tpot_unfused_us": t_unfused}
        report["archs"][arch] = entry
    if ragged_trace:
        # the scheduler requires a dense-FFN decoder-only arch; fall back
        # to llama2 when the benched arch isn't one (e.g. MoE deepseek)
        trace_arch = archs[0]
        tc = reduced(get_config(trace_arch))
        if tc.moe is not None or tc.frontend is not None \
                or tc.encoder is not None:
            trace_arch = "llama2-7b"
        report["ragged_trace"] = _bench_ragged_trace(trace_arch, rows=rows)
        # fleet chaos sweep: deterministic detection/recovery/availability
        # columns per fault kind, gated by scripts/check_bench.py
        # (ROUTER_GATED_COLUMNS) against the committed baseline
        report["router_chaos"] = _bench_router_chaos(trace_arch, rows=rows)
        # SDC coverage sweep: single-bit flip detection/latency/false-
        # positive matrix (serving/sweep.py), gated by check_bench.py
        # (SDC_GATED_COLUMNS) against the committed baseline
        report["sdc_sweep"] = _bench_sdc_sweep(trace_arch, rows=rows)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"# wrote {out_path}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", nargs="+",
                    default=["llama2-7b", "deepseek-v2-lite"])
    ap.add_argument("--out", default="BENCH_tpot.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny single-arch sweep for CI (interpret mode)")
    ap.add_argument("--trace", action="store_true",
                    help="add the ragged-arrival scheduler trace section")
    args = ap.parse_args()
    if args.smoke:
        main(archs=args.archs[:1], max_seq=64, prompt_len=16,
             cache_lens=(8, 48), iters=3, out_path=args.out,
             fusion_baseline=False, ragged_trace=args.trace)
    else:
        main(archs=tuple(args.archs), out_path=args.out,
             ragged_trace=args.trace)
