"""A tiny cell for the benchmark's CPU tests, in a temporary copy of
``bench``: a small dense GQA model and a mix with every request group."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {
    "name": "tiny-gqa", "arch": "granite-8b",
    "source": "https://arxiv.org/abs/2405.04324",
    "num_hidden_layers": 2, "hidden_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 64, "intermediate_size": 512,
    "vocab_size": 512, "hidden_act": "silu", "gated_ffn": True,
    "tie_word_embeddings": True, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "dtype": "bfloat16", "reduced": [],
    # read on the CPU at this size (the benchmark's own runs: cand_err
    # 0.0065-0.0080; the fp8 control: 0.084-0.093)
    "limits": {"logit_gap": 0.3, "cand_err": 0.02},
}
_P = {"kind": "uniform", "min": 8, "max": 48}
TINY_MIX = {
    "name": "tiny-mix", "slots": 4, "max_seq": 128, "prompt_cap": 48,
    "warmup_ticks": 2,
    "in_flight": {"count": 4, "prompt": _P,
                  "output": {"kind": "uniform", "min": 2, "max": 12}},
    "backlog": {"count": 2, "prompt": _P,
                "output": {"kind": "uniform", "min": 6, "max": 10}},
    "arrivals": {"process": "poisson", "rate": 2.0, "prompt": _P,
                 "output": {"kind": "uniform", "min": 4, "max": 8}},
    "drain_s": 30,
    "check": {"requests": 4, "max_tokens": 200},
}


def make_root(tmp, model=TINY_MODEL, mix=TINY_MIX, cell="tiny"):
    """A checkout-like directory: a copy of ``bench`` plus a
    ``BENCHMARK.json`` whose one cell runs ``model`` under ``mix``."""
    root = str(tmp)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(model, f)
    with open(os.path.join(root, "bench", "traffic",
                           mix["name"] + ".json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": model["name"], "source": model["source"],
                            "file": "bench/configs/tiny.json", "reduced": [],
                            "why": "a tiny stand-in for CPU tests"})
    spec["workloads"].append({"name": cell, "config": model["name"],
                              "traffic": mix["name"], "chips": 1,
                              "why": "CPU test cell"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and m["name"] in ("out_tok_s", "itl_p99_ms",
                                              "ttft_p50_ms", "step_mfu"):
            m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def run_tiny(root, seed, seconds=2.0, **kw):
    """One run of the tiny cell on the CPU, kernels in interpret mode:
    the harness's whole run without its look for a chip."""
    from bench.harness import serve, spec
    cell = spec.load_cell(root, "tiny", bench_root=os.path.join(root,
                                                                "bench"))
    return serve.run_cell(cell, seed, seconds, trace=False, interpret=True,
                          peaks=PEAKS, **kw)
