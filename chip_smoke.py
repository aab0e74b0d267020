"""Chip smoke test: serve granite-8b at its published widths on a TPU
through the fused decode path, and check it against the XLA path.

    python chip_smoke.py               # one chip (what CI on the chip runs)
    python chip_smoke.py --four-chips  # cluster-4 ClusterReduce vs cluster 1

The model is granite-8b (d_model 4096, 32 query heads over 8 kv heads,
head_dim 128, gated-SiLU d_ff 14336, tied vocab 49152) with random
weights from a fixed seed; only its depth is cut (:func:`smoke_config`).
One process does everything and starts no child; it needs a TPU — on any
other device it raises before serving anything.

One chip: an engine with ``backend="pallas"`` and the serve-layout
prepack on (8 slots, ``max_seq`` 2048) drains 6 seeded requests with
staggered arrivals through :class:`~repro.serving.scheduler.SlotScheduler`
— prompts of 128–1024 tokens, 16–32 new tokens, and one late arrival
that re-uses a retired slot.  The step must trace exactly 2 Pallas
launches per layer position plus 1 for the LM head, and no
``[B, V]`` logits.  The same drain then runs on ``backend="xla"`` with
the same weights, and the first decode step's candidate logits (the
fused head's sorted top-8 values) of the two paths must agree within
:data:`CAND_RTOL`.

``--four-chips`` runs only the cluster path: the same engine on a
``(1, 4)`` mesh at cluster 4 (SplitToken over 4 chips, fused
ClusterReduce) against cluster 1 on one of those chips, compared the
same way.

The last line of stdout is ``{"ok": true, "device": {...}}``; any failed
check raises, so the script then exits non-zero without it.  Tokens per
second printed here are a smoke figure, not a benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import depth_cut, get_config  # noqa: E402
from repro.core import tracecount  # noqa: E402
from repro.launch.mesh import device_mesh  # noqa: E402
from repro.launch.runtime import enable_compile_cache, require_tpu  # noqa: E402
from repro.launch.serve import EngineOptions, build_engine_full  # noqa: E402
from repro.serving.scheduler import Request, SlotScheduler  # noqa: E402

SEED = 0
SLOTS = 8
MAX_SEQ = 2048
PROMPTS = (128, 1024)        # prompt length range (tokens)
OUTPUTS = (16, 32)           # new-token range
N_REQUESTS = 6
LAYERS = 4
# Candidate logits of two decode paths must agree to within CAND_RTOL of
# the row's largest |candidate|.  Why 2**-5: the paths round to bf16 (8
# mantissa bits, relative step 2**-8) at different points — the XLA path
# rounds every matmul input and the attention output to bf16 (the TPU's
# default precision), the fused kernels carry f32 accumulators and
# project a two-part bf16 split — so the residual streams differ by a few
# bf16 steps per layer; over 4 layers and the final norm that stays well
# under 2**-5, while a kernel fault (wrong head, block, mask or tile)
# moves the logits by the order of their own size.
CAND_RTOL = 2.0 ** -5


def smoke_config():
    """granite-8b at its published widths.  The one cut: depth, from the
    published 36 layers to LAYERS (4), so two engines' weights, prepacked
    attention and caches fit one 16 GB v5e with room to spare."""
    return depth_cut(get_config("granite-8b"), LAYERS)


def make_trace(seed: int, vocab: int, *, n: int = N_REQUESTS,
               prompts=PROMPTS, outputs=OUTPUTS):
    """``n`` seeded requests as ``(arrival_tick, Request)`` pairs.

    Requests 0..2 arrive together (the first decode step serves three
    slots), request 0 with the shortest output; the rest of 1..n-2
    arrive on the following ticks; the last arrives once request 0 has
    retired and so takes its slot again (the scheduler admits into the
    lowest free slot)."""
    rng = np.random.default_rng(seed)
    trace = []
    for i in range(n):
        plen = int(rng.integers(prompts[0], prompts[1] + 1))
        new = outputs[0] if i == 0 else int(rng.integers(outputs[0],
                                                         outputs[1] + 1))
        tick = outputs[0] + 4 if i == n - 1 else max(0, i - 2)
        prompt = rng.integers(0, vocab, size=plen).tolist()
        trace.append((tick, Request(rid=i, prompt=prompt, max_new=new)))
    return trace


def launch_counts(eng) -> dict:
    """Trace-time counters of one decode step (core/tracecount.py)."""
    tok = jnp.zeros((eng.batch_global,), jnp.int32)
    with tracecount.counting() as c:
        jax.eval_shape(eng.decode_fn, eng.params["serve"], eng.state, tok)
        return dict(c)


def check_fused_counts(cfg, counts: dict) -> None:
    """The fused path really ran: 2 launches per layer position (fused
    attention + fused FFN tail), 1 LM-head launch, no [B, V] logits."""
    period = len(cfg.block_pattern)
    want = {"pallas_kernel": 2 * period + 1, "ffn_pallas_kernel": period,
            "head_pallas_kernel": 1}
    got = {k: counts.get(k, 0) for k in want}
    if got != want or counts.get("lm_head_logits", 0) != 0:
        raise AssertionError(f"fused decode step traced {counts}, "
                             f"want {want} and 0 lm_head_logits")


def build_and_drain(cfg, mesh, trace, *, backend: str, slots: int = SLOTS,
                    max_seq: int = MAX_SEQ, prompt_cap: int = PROMPTS[1],
                    cluster=None, interpret: bool = False) -> dict:
    """Build an engine on ``mesh``, drain ``trace`` through a
    SlotScheduler, and return host-side results: launch counts, the first
    decode step's candidates, emitted tokens and timings."""
    t0 = time.perf_counter()
    eng = build_engine_full(
        cfg, mesh, max_seq=max_seq, batch_global=slots,
        options=EngineOptions(backend=backend, prepack="on",
                              interpret=interpret, cluster=cluster,
                              stash_candidates=True))
    jax.block_until_ready(eng.params)
    out = {"build_s": time.perf_counter() - t0, "counts": launch_counts(eng),
           "cluster": eng.lay.cluster}
    sched = SlotScheduler(eng, prompt_cap=prompt_cap)
    pending = sorted(trace, key=lambda ar: ar[0])
    t_steady = tokens_steady = None
    while pending or not sched.idle():
        while pending and pending[0][0] <= sched.tick:
            sched.submit(pending.pop(0)[1])
        calls, t = sched.decode_calls, time.perf_counter()
        sched.step()
        if calls == 0 and sched.decode_calls == 1:
            # first decode: compiles admit + decode, and its candidates
            # are the ones compared across paths
            out["first_tick_s"] = time.perf_counter() - t
            first = [b for tick, kind, _, b in sched.events
                     if kind == "admit" and tick == sched.tick - 1]
            cv = np.asarray(jax.device_get(sched.state["cand_v"]))
            out["first_slots"] = first
            out["cand_v"] = cv.reshape(-1, slots, cv.shape[-1])[0][first]
            t_steady = time.perf_counter()
            tokens_steady = sum(len(r.tokens) for r in sched.results.values())
        if sched.tick > 10 * max_seq:
            raise AssertionError("scheduler did not drain")
    seconds = time.perf_counter() - t_steady
    results = sched.results
    out["tokens"] = {rid: r.tokens for rid, r in results.items()}
    out["tok_per_s"] = (sum(len(r.tokens) for r in results.values())
                        - tokens_steady) / seconds
    admits = [b for _, kind, _, b in sched.events if kind == "admit"]
    out["reused_slots"] = sorted({b for b in admits if admits.count(b) > 1})
    out["ticks"] = sched.tick
    for tick, req in trace:
        toks = results[req.rid].tokens
        if len(toks) != req.max_new or not all(
                0 <= t < cfg.vocab_size for t in toks):
            raise AssertionError(f"request {req.rid}: bad stream {toks}")
    if not out["reused_slots"]:
        raise AssertionError("no slot was retired and re-admitted")
    if not np.isfinite(out["cand_v"]).all():
        raise AssertionError("non-finite candidate logits")
    del sched, eng
    gc.collect()
    return out


def compare_candidates(a: dict, b: dict, label: str) -> float:
    """Largest |Δ| of the first decode step's sorted candidate values,
    relative to each row's largest |candidate|; raises above CAND_RTOL."""
    if a["first_slots"] != b["first_slots"]:
        raise AssertionError(f"{label}: first decode ran different slots "
                             f"{a['first_slots']} vs {b['first_slots']}")
    va, vb = a["cand_v"], b["cand_v"]
    scale = np.maximum(np.abs(va).max(axis=-1, keepdims=True), 1e-30)
    rel = float((np.abs(va - vb) / scale).max())
    print(f"{label}: candidate values max |diff| "
          f"{float(np.abs(va - vb).max())!r}, relative {rel!r} "
          f"(tolerance {CAND_RTOL!r}); top-1 rows "
          f"{va[:, 0].tolist()} vs {vb[:, 0].tolist()}")
    if not rel <= CAND_RTOL:
        raise AssertionError(f"{label}: candidates differ by {rel} > "
                             f"{CAND_RTOL} of the row scale")
    return rel


def _report(label: str, r: dict) -> None:
    n_tok = sum(len(t) for t in r["tokens"].values())
    print(f"{label}: cluster {r['cluster']}, build {r['build_s']!r} s, "
          f"first tick (admit + decode compile) {r['first_tick_s']!r} s, "
          f"{n_tok} tokens in {r['ticks']} ticks, slots re-admitted "
          f"{r['reused_slots']}")
    print(f"{label}: launch counts {r['counts']}")
    print(f"{label}: smoke figure, not a benchmark: "
          f"{r['tok_per_s']!r} tokens/s after the first tick")


def _peak_bytes(devices) -> list:
    return [d.memory_stats().get("peak_bytes_in_use")
            if d.memory_stats() else None for d in devices]


def run_one_chip(cfg, devices, trace, **kw) -> None:
    """Fused path vs XLA path on one device."""
    mesh = device_mesh(devices[:1])
    fused = build_and_drain(cfg, mesh, trace, backend="pallas", **kw)
    _report("pallas", fused)
    check_fused_counts(cfg, fused["counts"])
    xla = build_and_drain(cfg, mesh, trace, backend="xla", **kw)
    _report("xla", xla)
    compare_candidates(fused, xla, "pallas vs xla")
    print(f"peak bytes in use: {_peak_bytes(devices[:1])}")


def run_four_chips(cfg, devices, trace, **kw) -> None:
    """Cluster 4 over a (1, 4) mesh vs cluster 1 on one of its devices."""
    if len(devices) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found "
                           f"{len(devices)}")
    c4 = build_and_drain(cfg, device_mesh(devices[:4]), trace,
                         backend="pallas", cluster=4, **kw)
    _report("cluster4", c4)
    check_fused_counts(cfg, c4["counts"])
    if c4["cluster"] != 4:
        raise AssertionError(f"engine built cluster {c4['cluster']}")
    in_use = [d.memory_stats().get("bytes_in_use")
              if d.memory_stats() else None for d in devices[:4]]
    print(f"bytes in use per device after the cluster-4 drain: {in_use}")
    c1 = build_and_drain(cfg, device_mesh(devices[:1]), trace,
                         backend="pallas", cluster=1, **kw)
    _report("cluster1", c1)
    compare_candidates(c4, c1, "cluster4 vs cluster1")
    print(f"peak bytes in use: {_peak_bytes(devices[:4])}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cluster-4 vs cluster-1 comparison "
                         "on a (1, 4) mesh")
    args = ap.parse_args(argv)
    devices = require_tpu()
    cache = enable_compile_cache()
    d0 = devices[0]
    print(f"device: {d0.platform} {d0.device_kind} x{len(devices)}; "
          f"compile cache {cache}")
    cfg = smoke_config()
    print(f"config: {cfg.name} — granite-8b published widths (d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, head_dim "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}); depth "
          f"cut 36 -> {cfg.n_layers} layers; {SLOTS} slots, max_seq "
          f"{MAX_SEQ}, seed {SEED}")
    trace = make_trace(SEED, cfg.vocab_size)
    if args.four_chips:
        run_four_chips(cfg, devices, trace)
    else:
        run_one_chip(cfg, devices, trace)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
