"""Paper Fig. 9/18 analogue: core QKV-Projection + Attention +
Output-Projection module — ClusterFusion fused dataflow (one computation)
vs the block-isolated baseline (three separate kernel launches with the
intermediates materialized between them).
"""
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from benchmarks.common import row, time_fn
from repro.core import dataflow as df
from repro.core import primitives as prim


def main(seqs=(1024, 4096, 16384)):
    n_dev = min(8, jax.device_count())
    H, N = (2, 4) if n_dev == 8 else (1, 1)
    mesh = jax.make_mesh((n_dev,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    heads_ax = prim.SubAxis("model", H, minor_size=N)
    clus_ax = prim.SubAxis("model", N, minor_size=1)
    B, D, hd = 1, 512, 64
    n_heads, n_kv = 8, 8
    q_loc, kv_loc = n_heads // H, n_kv // H
    key = jax.random.PRNGKey(0)
    rows = []
    for S in seqs:
        s_blk = S // N
        ks = jax.random.split(key, 8)
        x = jax.random.normal(ks[0], (B, D), jnp.float32) * 0.3
        wq = jax.random.normal(ks[1], (n_dev, D, q_loc, hd // N)) * 0.05
        wk = jax.random.normal(ks[2], (n_dev, D, kv_loc, hd // N)) * 0.05
        wv = jax.random.normal(ks[3], (n_dev, D, kv_loc, hd // N)) * 0.05
        wo = jax.random.normal(ks[4], (n_dev, q_loc * hd, D // N)) * 0.05
        kc = jax.random.normal(ks[5], (n_dev, s_blk, B * kv_loc, hd)) * 0.3
        vc = jax.random.normal(ks[6], (n_dev, s_blk, B * kv_loc, hd)) * 0.3
        pos = jnp.tile(jnp.arange(s_blk, dtype=jnp.int32)[None], (n_dev, 1))
        clen = jnp.int32(S - 2)
        spec = df.ClusterSpec(heads=heads_ax, cluster=clus_ax)

        def fused(x_, wq_, wk_, wv_, wo_, kc_, vc_, pos_):
            w = df.SplitTokenWeights(wq=wq_[0], wk=wk_[0], wv=wv_[0],
                                     wo=wo_[0])
            cache = df.KVBlock(k=kc_[0], v=vc_[0], pos=pos_[0])
            o_seg, _ = df.split_token_attention(spec, x_, w, cache, clen)
            return prim.cluster_gather_tiled(o_seg, clus_ax, axis=1)[None]

        fused_j = jax.jit(shard_map(
            fused, mesh=mesh,
            in_specs=(P(),) + (P("model"),) * 7,
            out_specs=P("model"), check_vma=False))

        # block-isolated baseline: 3 separate jitted "kernels" with HBM
        # round-trips between them (the paper's Fig. 3 dataflow)
        wq_f = jax.random.normal(ks[1], (D, n_heads, hd)) * 0.05
        wk_f = jax.random.normal(ks[2], (D, n_kv, hd)) * 0.05
        wv_f = jax.random.normal(ks[3], (D, n_kv, hd)) * 0.05
        wo_f = jax.random.normal(ks[4], (n_heads * hd, D)) * 0.05
        kc_f = jax.random.normal(ks[5], (S, n_kv, hd)) * 0.3
        vc_f = jax.random.normal(ks[6], (S, n_kv, hd)) * 0.3

        @jax.jit
        def k_qkv(x_):
            return (jnp.einsum("bd,dqh->bqh", x_, wq_f),
                    jnp.einsum("bd,dkh->bkh", x_, wk_f),
                    jnp.einsum("bd,dkh->bkh", x_, wv_f))

        @jax.jit
        def k_attn(q):
            s = jnp.einsum("bkh,skh->bks", q.reshape(B, n_heads, hd),
                           kc_f) / math.sqrt(hd)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bks,skh->bkh", p, vc_f)

        @jax.jit
        def k_out(a):
            return a.reshape(B, n_heads * hd) @ wo_f

        def baseline(x_):
            q, k, v = k_qkv(x_)
            a = k_attn(q)
            return k_out(a)

        t_f = time_fn(fused_j, x, wq, wk, wv, wo, kc, vc, pos)
        t_b = time_fn(baseline, x)
        rows.append(row(f"core_module_fused_S{S}", t_f,
                        f"traffic_B={df.traffic_split_token(hd, D, N):.0f}"))
        rows.append(row(f"core_module_baseline_S{S}", t_b,
                        f"speedup={t_b / max(t_f, 1e-9):.2f}x"))
    return rows


if __name__ == "__main__":
    main()
