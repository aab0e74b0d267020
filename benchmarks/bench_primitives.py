"""Paper Table 1 + Fig. 13 analogue: on-chip (tree over the cluster fabric)
ClusterReduce/ClusterGather vs the off-chip pattern (materialize all N
buffers, reduce locally), across transfer sizes.

Runs on an 8-host-device mesh; µs are CPU-relative, the derived column is
the fabric traffic from the paper's analytical model (§3.2).
"""
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from benchmarks.common import row, time_fn
from repro.core import primitives as prim


def main():
    n = min(8, jax.device_count())
    mesh = jax.make_mesh((n,), ("c",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rows = []
    for kb in (32, 64, 128, 256):
        elems = kb * 1024 // 4
        x = jnp.arange(n * elems, dtype=jnp.float32).reshape(n, elems)

        def mk(fn):
            return jax.jit(shard_map(fn, mesh=mesh, in_specs=P("c", None),
                                     out_specs=P("c", None)))

        on_r = mk(lambda v: prim.cluster_reduce(v, "c", "sum"))
        off_r = mk(lambda v: prim.offchip_reduce(v[0], "c")[None])
        on_g = mk(lambda v: prim.cluster_gather_tiled(v, "c", axis=1))
        off_g = mk(lambda v: jax.lax.all_gather(v[0], "c", axis=0,
                                                tiled=True)[None])
        t_on_r = time_fn(on_r, x)
        t_off_r = time_fn(off_r, x)
        t_on_g = time_fn(on_g, x)
        t_off_g = time_fn(off_g, x)
        tr = prim.traffic_reduce(kb * 1024, n)
        tg = prim.traffic_gather(kb * 1024, n)
        rows.append(row(f"cluster_reduce_onchip_{kb}KB", t_on_r,
                        f"traffic_B={tr:.0f}"))
        rows.append(row(f"cluster_reduce_offchip_{kb}KB", t_off_r,
                        f"speedup={t_off_r / max(t_on_r, 1e-9):.2f}x"))
        rows.append(row(f"cluster_gather_onchip_{kb}KB", t_on_g,
                        f"traffic_B={tg:.0f}"))
        rows.append(row(f"cluster_gather_offchip_{kb}KB", t_off_g,
                        f"speedup={t_off_g / max(t_on_g, 1e-9):.2f}x"))
    return rows


if __name__ == "__main__":
    main()
