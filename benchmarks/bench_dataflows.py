"""Paper Fig. 20 / App. B analogue: SplitToken vs SplitHead dataflow —
measured µs on 8 host devices + the analytical traffic crossover.
"""
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from benchmarks.common import row, time_fn
from repro.core import dataflow as df
from repro.core import primitives as prim


def main(seqs=(512, 2048, 8192, 32768)):
    n_dev = min(8, jax.device_count())
    H, N = 2, n_dev // 2
    heads_ax = prim.SubAxis("model", H, minor_size=N)
    clus_ax = prim.SubAxis("model", N, minor_size=1)
    mesh = jax.make_mesh((n_dev,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    B, D, hd, n_heads = 1, 256, 64, 4
    q_loc = n_heads // H
    key = jax.random.PRNGKey(0)
    rows = []
    for S in seqs:
        ks = jax.random.split(key, 8)
        x = jax.random.normal(ks[0], (B, D)) * 0.3
        hd_n = hd // N
        clen = jnp.int32(S - 2)
        spec = df.ClusterSpec(heads=heads_ax, cluster=clus_ax)

        # SplitToken: seq-sharded cache
        wq = jax.random.normal(ks[1], (n_dev, D, q_loc, hd_n)) * 0.05
        wk = jax.random.normal(ks[2], (n_dev, D, q_loc, hd_n)) * 0.05
        wv = jax.random.normal(ks[3], (n_dev, D, q_loc, hd_n)) * 0.05
        wo = jax.random.normal(ks[4], (n_dev, q_loc * hd, D // N)) * 0.05
        kc = jax.random.normal(ks[5], (n_dev, S // N, B * q_loc, hd)) * 0.3
        vc = jax.random.normal(ks[6], (n_dev, S // N, B * q_loc, hd)) * 0.3
        pos = jnp.tile(jnp.arange(S // N, dtype=jnp.int32)[None], (n_dev, 1))

        def st_fn(x_, wq_, wk_, wv_, wo_, kc_, vc_, pos_):
            w = df.SplitTokenWeights(wq=wq_[0], wk=wk_[0], wv=wv_[0],
                                     wo=wo_[0])
            cache = df.KVBlock(k=kc_[0], v=vc_[0], pos=pos_[0])
            o, _ = df.split_token_attention(spec, x_, w, cache, clen)
            return prim.cluster_gather_tiled(o, clus_ax, axis=1)[None]

        st_j = jax.jit(shard_map(st_fn, mesh=mesh,
                                 in_specs=(P(),) + (P("model"),) * 7,
                                 out_specs=P("model"), check_vma=False))
        t_st = time_fn(st_j, x, wq, wk, wv, wo, kc, vc, pos, iters=10)

        # SplitHead: head-dim-sharded cache over the FULL sequence
        woh = jax.random.normal(ks[4], (n_dev, q_loc * hd_n, D)) * 0.05
        kch = jax.random.normal(ks[5], (n_dev, S, B * q_loc, hd_n)) * 0.3
        vch = jax.random.normal(ks[6], (n_dev, S, B * q_loc, hd_n)) * 0.3
        posh = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (n_dev, 1))

        def sh_fn(x_, wq_, wk_, wv_, wo_, kc_, vc_, pos_):
            w = df.SplitHeadWeights(wq=wq_[0], wk=wk_[0], wv=wv_[0],
                                    wo=wo_[0])
            cache = df.KVBlock(k=kc_[0], v=vc_[0], pos=pos_[0])
            o, _ = df.split_head_attention(spec, x_, w, cache, clen)
            return o[None]

        sh_j = jax.jit(shard_map(sh_fn, mesh=mesh,
                                 in_specs=(P(),) + (P("model"),) * 7,
                                 out_specs=P("model"), check_vma=False))
        t_sh = time_fn(sh_j, x, wq, wk, wv, woh, kch, vch, posh, iters=10)

        tr_st = df.traffic_split_token(hd, D, N)
        tr_sh = df.traffic_split_head(S, D, N)
        rows.append(row(f"split_token_S{S}", t_st, f"traffic_B={tr_st:.0f}"))
        rows.append(row(f"split_head_S{S}", t_sh,
                        f"traffic_B={tr_sh:.0f},"
                        f"ratio={tr_sh / max(tr_st, 1):.1f}"))
    return rows


if __name__ == "__main__":
    main()
