"""Paper Fig. 11 analogue: core-module latency vs cluster size N (and the
analytical v5e latency model that the autotuner uses).

The paper finds N=4 optimal for 32–64 heads on H100; our analytical model
reproduces the same *shape* (optimum at small-moderate N, degradation at
16) with ICI constants — see EXPERIMENTS.md §Paper-validation.
"""
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from benchmarks.common import row, time_fn
from repro.configs import get_config
from repro.core import dataflow as df
from repro.core import primitives as prim
from repro.core.autotune import sweep


def main():
    n_dev = min(8, jax.device_count())
    rows = []
    # measured: tiny decode attention at N ∈ {1,2,4,8} on 8 host devices
    B, D, hd, n_heads = 1, 256, 64, 8
    S = 8192
    key = jax.random.PRNGKey(0)
    for N in (1, 2, 4, 8):
        if N > n_dev:
            continue
        H = n_dev // N
        q_loc = n_heads // H
        heads_ax = prim.SubAxis("model", H, minor_size=N)
        clus_ax = prim.SubAxis("model", N, minor_size=1)
        mesh = jax.make_mesh((n_dev,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        ks = jax.random.split(key, 8)
        s_blk = S // N
        x = jax.random.normal(ks[0], (B, D)) * 0.3
        wq = jax.random.normal(ks[1], (n_dev, D, q_loc, hd // N)) * 0.05
        wk = jax.random.normal(ks[2], (n_dev, D, q_loc, hd // N)) * 0.05
        wv = jax.random.normal(ks[3], (n_dev, D, q_loc, hd // N)) * 0.05
        wo = jax.random.normal(ks[4], (n_dev, q_loc * hd, D // N)) * 0.05
        kc = jax.random.normal(ks[5], (n_dev, s_blk, B * q_loc, hd)) * 0.3
        vc = jax.random.normal(ks[6], (n_dev, s_blk, B * q_loc, hd)) * 0.3
        pos = jnp.tile(jnp.arange(s_blk, dtype=jnp.int32)[None], (n_dev, 1))
        spec = df.ClusterSpec(heads=heads_ax, cluster=clus_ax)

        def fn(x_, wq_, wk_, wv_, wo_, kc_, vc_, pos_):
            w = df.SplitTokenWeights(wq=wq_[0], wk=wk_[0], wv=wv_[0],
                                     wo=wo_[0])
            cache = df.KVBlock(k=kc_[0], v=vc_[0], pos=pos_[0])
            o_seg, _ = df.split_token_attention(spec, x_, w, cache,
                                                jnp.int32(S - 2))
            return prim.cluster_gather_tiled(o_seg, clus_ax, axis=1)[None]

        j = jax.jit(shard_map(fn, mesh=mesh,
                              in_specs=(P(),) + (P("model"),) * 7,
                              out_specs=P("model"), check_vma=False))
        t = time_fn(j, x, wq, wk, wv, wo, kc, vc, pos)
        tr = df.traffic_split_token(hd, D, N)
        rows.append(row(f"cluster_size_N{N}_S{S}", t, f"traffic_B={tr:.0f}"))

    # analytic sweep at production scale for two real archs (Fig. 11 shape)
    for arch in ("llama2-7b", "qwen2-72b"):
        cfg = get_config(arch)
        for pt in sweep(cfg, seq_len=16384, batch=1, model_axis=16):
            if pt.dataflow != "split_token":
                continue
            rows.append(row(
                f"analytic_{arch}_N{pt.cluster_size}",
                pt.est_seconds * 1e6,
                f"ici_s={pt.terms['ici']:.2e}"))
    return rows


if __name__ == "__main__":
    main()
