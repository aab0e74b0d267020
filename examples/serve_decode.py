"""Batched serving with the ClusterFusion dataflow: prefill a batch of
prompts, decode with the fused SplitToken path, and compare the
paper-faithful combine against the beyond-paper fused-merge combine.

    PYTHONPATH=src python examples/serve_decode.py --arch gemma2-27b
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
from repro.launch.serve import build_engine, generate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-27b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--backend", default="xla",
                    choices=("xla", "pallas", "auto"),
                    help="local-stage compute backend (pallas runs the "
                         "fused decode kernels; interpret mode on CPU)")
    ap.add_argument("--prepack", default="auto",
                    choices=("auto", "on", "off"),
                    help="serve-layout weight prepack at load time "
                         "(auto: on whenever backend resolves to pallas; "
                         "checkpoints always keep the training layout)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = reduced(get_config(args.arch))
    mesh = (make_test_mesh() if jax.default_backend() == "cpu"
            else device_mesh(require_tpu()))
    key = jax.random.PRNGKey(0)
    prompts = jax.random.randint(key, (args.batch, 16), 0, cfg.vocab_size)
    fe = None
    if cfg.frontend is not None:
        fe = jax.random.normal(key, (args.batch,
                                     cfg.frontend.num_positions,
                                     cfg.frontend.feature_dim))
    outs = {}
    for fused_combine in (False, True):
        params, pf, dec, state, lay, scfg = build_engine(
            cfg, mesh, max_seq=64, batch_global=args.batch,
            fused_combine=fused_combine, backend=args.backend,
            prepack=args.prepack,
            interpret=(args.backend != "xla"
                       and jax.default_backend() == "cpu"))
        t0 = time.time()
        toks, _ = generate(cfg, params, pf, dec, state, prompts,
                           args.tokens, fe)
        dt = time.time() - t0
        label = "fused-merge" if fused_combine else "paper-faithful"
        label += f"/{args.backend}"
        if scfg.prepack:
            label += "+prepack"
        outs[fused_combine] = np.asarray(toks)
        print(f"{label:16s} combine: {args.tokens} tok × {args.batch} seq "
              f"in {dt:.2f}s  (cluster={lay.cluster})")
    agree = (outs[False] == outs[True]).mean()
    if scfg.prepack:
        # the prepacked partial_o path always runs the single-tree merge
        # (constitutive of its one-ClusterReduce contract), so the two
        # iterations above exercised the same combine schedule
        print("note: prepack unifies the combine — both rows ran the "
              "fused single-tree merge")
    print(f"paper-faithful vs fused-merge token agreement: {agree:.3f}")
    # Print the sample through the SERVE view of the head — the (table,
    # ln) decode actually sampled with.  With --prepack the fused head
    # bundle is what ran, not the train tree; head_table_np also
    # smoke-asserts the serve view aliases the train-layout head bytes
    # (reaching into params["train"] was the footgun this replaces).
    from repro.serving.prepack import head_table_np
    table = head_table_np(cfg, params)
    sample = outs[True][0][:12]
    assert (sample >= 0).all() and (sample < table.shape[0]).all(), sample
    norms = np.linalg.norm(table[sample], axis=-1)
    print("sample:", sample)
    print("serve-view head rows |e| of sample:", np.round(norms, 3))


if __name__ == "__main__":
    main()
