"""Continuous batching demo: requests with staggered arrivals stream
through the slot scheduler over the ragged fused decode engine.

    PYTHONPATH=src python examples/serve_requests.py --arch llama2-7b

A short request retires mid-flight and its slot is re-admitted to a
later arrival while the long requests keep decoding — no lockstep
barrier, and free slots pay zero attend-step work (printed from the
per-slot work counters).

Fleet mode (``--replicas N``): the same trace runs through the
multi-replica router (serving/router.py) with queue-depth-aware
dispatch.  Add ``--fault KIND`` (any serving/faults.py kind) to inject
a deterministic fault into replica 0 mid-trace and watch the router
detect it, drain the replica, and recover every in-flight stream on the
survivors — the recap verifies the recovered streams byte-equal a
fault-free oracle run (DESIGN.md §9):

    PYTHONPATH=src python examples/serve_requests.py \\
        --replicas 2 --fault corrupt_kv

Single-bit SDC faults take ``--bit`` (``--fault flip_kv_bit --bit 7``
flips one exponent bit below the non-finite floor — only the integrity
fingerprints can see it).  ``--sweep`` runs the systematic single-bit
fault sweep (serving/sweep.py) over the fleet and prints the detection
coverage matrix (detected% / latency / oracle-exact% per fault kind ×
bit position, plus the fault-free false-positive control row):

    PYTHONPATH=src python examples/serve_requests.py --sweep
    PYTHONPATH=src python examples/serve_requests.py --sweep \\
        --sweep-bits all          # every bf16 bit position (nightly CI)
"""
import argparse
import os
import time

# CPU runs (JAX_PLATFORMS=cpu) emulate 8 host devices; on a TPU the
# mesh is built over the chips JAX finds
if (os.environ.get("JAX_PLATFORMS") == "cpu"
        and "xla_force_host_platform_device_count"
        not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.launch.mesh import device_mesh, make_test_mesh
from repro.launch.runtime import enable_compile_cache, require_tpu
from repro.launch.serve import EngineOptions, build_engine_full
from repro.serving.scheduler import Request, SlotScheduler, replay_trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b",
                    help="attention-only decoder configs")
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-cap", type=int, default=12)
    ap.add_argument("--backend", default="xla",
                    choices=("xla", "pallas", "auto"))
    ap.add_argument("--prepack", default="auto",
                    choices=("auto", "on", "off"),
                    help="serve-layout weight prepack (auto: on whenever "
                         "the backend resolves to pallas — parity with "
                         "serve_decode.py)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=1,
                    help="fleet mode: route the trace through a "
                         "multi-replica router (serving/router.py)")
    ap.add_argument("--fault", default=None,
                    help="inject a deterministic fault into replica 0 "
                         "(any serving/faults.py kind; implies fleet "
                         "mode with ≥2 replicas)")
    ap.add_argument("--fault-step", type=int, default=2,
                    help="fleet tick at which the fault arms")
    ap.add_argument("--bit", type=int, default=7,
                    help="bit position for the flip_* fault kinds "
                         "(bf16: 0-6 mantissa, 7-14 exponent, 15 sign)")
    ap.add_argument("--sweep", action="store_true",
                    help="run the systematic single-bit SDC fault sweep "
                         "and print the coverage matrix (implies fleet "
                         "mode)")
    ap.add_argument("--sweep-bits", default="0,7,14",
                    help="comma-separated bit positions for --sweep, or "
                         "'all' for the full 16-bit grid")
    args = ap.parse_args()
    if args.fault is not None or args.sweep:
        args.replicas = max(args.replicas, 2)
    if args.replicas > 1:
        return fleet_main(args)

    enable_compile_cache()
    cfg = reduced(get_config(args.arch))
    mesh = (make_test_mesh(data=1, model=8)
            if jax.default_backend() == "cpu" else device_mesh(require_tpu()))
    rng = np.random.default_rng(args.seed)
    max_new_cap = 12
    eng = build_engine_full(
        cfg, mesh, max_seq=args.prompt_cap + max_new_cap + 8,
        batch_global=args.slots,
        options=EngineOptions(
            backend=args.backend, prepack=args.prepack,
            interpret=(args.backend != "xla"
                       and jax.default_backend() == "cpu"),
            track_work=True,
            # autotune keys on the max LIVE length, not the allocation
            plan_seq_len=args.prompt_cap + max_new_cap))
    sched = SlotScheduler(eng, prompt_cap=args.prompt_cap)

    trace = []
    for rid in range(args.requests):
        arrival = int(rng.integers(0, 3)) + rid // args.slots * 2
        plen = int(rng.integers(2, args.prompt_cap + 1))
        n_new = int(rng.integers(2, max_new_cap + 1))
        prompt = list(rng.integers(0, cfg.vocab_size, plen))
        trace.append((arrival, Request(rid, prompt, n_new)))
        print(f"req {rid}: arrive t={arrival} prompt_len={plen} "
              f"max_new={n_new}")

    t0 = time.time()
    results = replay_trace(sched, trace)
    dt = time.time() - t0
    print(f"\ndrained {args.requests} requests over {sched.tick} ticks "
          f"({sched.decode_calls} decode dispatches) in {dt:.2f}s")
    print(f"mean slot occupancy: "
          f"{np.mean(sched.occupancy):.2f}")
    print(f"per-slot attend-block work: {sched.work_blocks()}")
    # Token printout goes through the SERVE view of the head — with
    # --prepack the fused head bundle is what sampling consumed, not the
    # train tree (reaching into eng.params["train"] was the footgun);
    # head_table_np smoke-asserts the serve view aliases the train-
    # layout head bytes on the way.
    from repro.serving.prepack import head_table_np
    table = head_table_np(cfg, eng.params)
    for rid in sorted(results):
        r = results[rid]
        assert all(0 <= t < table.shape[0] for t in r.tokens), r.tokens
        norms = np.linalg.norm(table[np.asarray(r.tokens, np.int32)],
                               axis=-1) if r.tokens else np.array([])
        print(f"req {rid}: slot {r.slot} ticks "
              f"[{r.admit_tick}, {r.finish_tick}] tokens {r.tokens} "
              f"|e|={np.round(norms, 2)}")


def fleet_main(args):
    from repro.launch.serve import build_replicas
    from repro.serving.faults import (ALL_FAULT_KINDS, BIT_FAULT_KINDS,
                                      FaultInjector, FaultSpec)
    from repro.serving.router import Router

    cfg = reduced(get_config(args.arch))
    if cfg.moe is not None:
        import dataclasses
        cfg = dataclasses.replace(cfg, moe=None)
    mesh = make_test_mesh(data=1, model=1)
    max_new_cap = 12
    rng = np.random.default_rng(args.seed)
    engines = build_replicas(
        cfg, mesh, n_replicas=args.replicas,
        max_seq=args.prompt_cap + max_new_cap + 8,
        batch_global=args.slots,
        options=EngineOptions(backend=args.backend, check_finite=True,
                              kv_fingerprint=True, shadow_head=True))
    trace = []
    for rid in range(args.requests):
        plen = int(rng.integers(2, args.prompt_cap + 1))
        trace.append((int(rng.integers(0, 4)), Request(
            rid, [int(t) for t in rng.integers(1, cfg.vocab_size, plen)],
            int(rng.integers(2, max_new_cap + 1)))))

    def run(injectors=None, integrity=None):
        r = Router(engines, prompt_cap=args.prompt_cap,
                   max_new_cap=max_new_cap, injectors=injectors,
                   integrity=integrity)
        journal = r.run([(t, Request(q.rid, q.prompt, q.max_new))
                         for t, q in trace])
        return r, journal

    print(f"fleet: {args.replicas} replicas, {args.requests} requests")
    if args.sweep:
        from repro.serving.faults import FaultSweep
        from repro.serving.integrity import IntegrityConfig
        from repro.serving.sweep import format_coverage, run_sdc_sweep
        bits = (tuple(range(16)) if args.sweep_bits == "all"
                else tuple(int(b) for b in args.sweep_bits.split(",")))
        print(f"systematic single-bit SDC sweep: kinds {BIT_FAULT_KINDS} "
              f"x bits {bits} x step {args.fault_step}")
        t0 = time.time()
        cells = run_sdc_sweep(
            engines, prompts=[q.prompt for _, q in trace],
            max_new=6, prompt_cap=args.prompt_cap,
            sweep=FaultSweep(bits=bits, steps=(args.fault_step,),
                             seed=args.seed),
            icfg=IntegrityConfig(weight_leaves_per_tick=4))
        print(format_coverage(cells))
        print(f"sweep drained in {time.time() - t0:.2f}s")
        return
    t0 = time.time()
    _, oracle = run()
    print(f"fault-free oracle drained in {time.time() - t0:.2f}s")
    if args.fault is None:
        for rid, e in sorted(oracle.items()):
            print(f"req {rid}: replicas {e.replicas} ticks "
                  f"[{e.submit_tick}, {e.finish_tick}] tokens {e.tokens}")
        return
    if args.fault not in ALL_FAULT_KINDS:
        raise SystemExit(f"--fault must be one of {ALL_FAULT_KINDS}")
    bit = args.bit if args.fault in BIT_FAULT_KINDS else -1
    inj = FaultInjector([FaultSpec(args.fault, step=args.fault_step,
                                   target=0, seed=args.seed, replica=0,
                                   bit=bit)])
    # single-bit faults are invisible to the PR-6 probes — they need the
    # integrity fingerprints (and the deferred-commit window they imply)
    icfg = None
    if args.fault in BIT_FAULT_KINDS:
        from repro.serving.integrity import IntegrityConfig
        icfg = IntegrityConfig(weight_leaves_per_tick=4)
    router, journal = run({0: inj}, integrity=icfg)
    print(f"\ninjected {args.fault} at tick {args.fault_step} "
          f"into replica 0")
    for d in router.detections:
        print(f"tick {d['tick']}: replica {d['replica']} FAILED — "
              f"signals {d['signals']}")
    lat = router.detection_latency(inj)
    print(f"detection latency: {lat} ticks | availability "
          f"{100 * router.availability():.1f}% | worst recovery "
          f"{router.recovery_steps()} ticks")
    exact = all(journal[r].tokens == oracle[r].tokens for r in oracle)
    for rid, e in sorted(journal.items()):
        mark = "=" if e.tokens == oracle[rid].tokens else "≠"
        flag = f" (requeued x{e.requeues})" if e.requeues else ""
        print(f"req {rid}: replicas {e.replicas}{flag} tokens "
              f"{e.tokens} {mark} oracle")
    print("zero-corruption recovery:",
          "OK — all streams byte-equal the oracle" if exact else "FAILED")
    assert exact


if __name__ == "__main__":
    main()
