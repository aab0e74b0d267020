"""Quickstart: the ClusterFusion primitives and fused dataflow in 60 lines.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/quickstart.py
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import primitives as prim
from repro.core import dataflow as df

# --- 1. the paper's collectives on an 8-chip "cluster" -------------------
mesh = jax.make_mesh((8,), ("cluster",),
                     axis_types=(jax.sharding.AxisType.Auto,))
x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)

reduce8 = jax.jit(shard_map(
    lambda v: prim.cluster_reduce(v, "cluster", "sum"),
    mesh=mesh, in_specs=P("cluster", None), out_specs=P("cluster", None)))
print("ClusterReduce (Alg. 1, log2(8)=3 ppermute rounds):",
      np.asarray(reduce8(x))[0])

gather8 = jax.jit(shard_map(
    lambda v: prim.cluster_gather_tiled(v, "cluster", axis=1),
    mesh=mesh, in_specs=P("cluster", None), out_specs=P("cluster", None)))
print("ClusterGather (Alg. 2, doubling messages):",
      np.asarray(gather8(x))[0, :8], "...")

# --- 2. traffic model (paper §3.2) — why SplitToken wins at long S -------
for S in (1024, 16384):
    st = df.traffic_split_token(head_dim=128, model_dim=4096, n=4)
    sh = df.traffic_split_head(seq_len=S, model_dim=4096, n=4)
    print(f"S={S}: SplitToken traffic {st:.0f}B vs SplitHead {sh:.0f}B "
          f"({sh / st:.0f}× more)")

# --- 3. one fused decode step on a tiny model -----------------------------
from repro.configs import get_config, reduced
from repro.launch.mesh import make_test_mesh
from repro.launch.serve import build_engine, generate

cfg = reduced(get_config("llama2-7b"))
mesh2 = make_test_mesh()                    # (data=2, model=4)
params, pf, dec, state, lay, _ = build_engine(cfg, mesh2, max_seq=64,
                                              batch_global=2)
prompts = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0,
                             cfg.vocab_size)
tokens, _ = generate(cfg, params, pf, dec, state, prompts, 8)
print(f"fused decode (heads_sub={lay.heads_sub} × cluster={lay.cluster}):",
      np.asarray(tokens)[0])
