"""Training driver: mesh setup, init (or resume), step loop with
checkpointing, exact-resume data, and straggler monitoring.

On this CPU container it runs reduced configs end-to-end (see
examples/train_tiny_lm.py); on real hardware the same driver scales — the
mesh and specs are identical to the dry-run's.
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, reduced
from repro.launch.runtime import enable_compile_cache
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, SyntheticLM, frontend_embeds_at
from repro.launch.mesh import dp_axes_of, dp_size_of, make_test_mesh
from repro.launch.specs import (abstract_opt_state, ctx_for,
                                state_spec_tree, train_layout)
from repro.models.transformer import (grad_sync_tree, init_device_major,
                                      param_specs)
from repro.training.optimizer import OptConfig
from repro.training.train_step import (TrainConfig, init_train_state,
                                       make_train_step)


class StragglerMonitor:
    """Flags steps (hosts, in multi-host runs) slower than p99 × 1.5.

    On real clusters per-host step barriers are timed via
    ``jax.experimental.multihost_utils``; here we keep the per-step record
    and the detection logic (exercised in tests)."""

    def __init__(self, window: int = 100, factor: float = 1.5):
        self.times: list = []
        self.window = window
        self.factor = factor

    def record(self, dt: float) -> bool:
        self.times.append(dt)
        hist = self.times[-self.window:]
        if len(hist) < 10:
            return False
        p50 = float(np.percentile(hist[:-1], 50))
        return dt > p50 * self.factor

    def summary(self):
        h = np.asarray(self.times)
        return {"p50": float(np.percentile(h, 50)),
                "p99": float(np.percentile(h, 99)),
                "max": float(h.max()), "steps": len(h)}


def run(arch: str, *, steps: int = 20, use_reduced: bool = True,
        ckpt_dir: Optional[str] = None, mesh=None, batch_override=None,
        seq_override=None, tcfg: Optional[TrainConfig] = None,
        log_every: int = 10):
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    mesh = mesh or make_test_mesh()
    ms = mesh.shape["model"]
    dp_axes = dp_axes_of(mesh)
    dp = dp_size_of(mesh)
    tcfg = tcfg or TrainConfig(opt=OptConfig(lr=1e-3))
    lay = train_layout(cfg, ms)
    ctx = ctx_for(mesh, lay)
    B = batch_override or 8
    S = seq_override or 64
    step_fn = make_train_step(
        ctx, cfg, tcfg, dp_axes, dp,
        sync_tree=None)  # sync tree built below with real params

    # ---- init (sharded via out_shardings; RNG is partition-consistent) --
    p_specs_holder = {}

    def init_all():
        params = init_device_major(cfg, lay, jax.random.PRNGKey(0))
        return params

    params_abs = jax.eval_shape(init_all)
    p_specs = param_specs(cfg, params_abs)
    sync = grad_sync_tree(cfg, lay, params_abs)
    step_fn = make_train_step(ctx, cfg, tcfg, dp_axes, dp, sync_tree=sync)
    out_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs)
    params = jax.jit(init_all, out_shardings=out_shardings)()

    def init_state():
        rank = jax.lax.axis_index(dp_axes)
        opt, ef = init_train_state(cfg, tcfg, params_abs_local(), dp, rank)
        from repro.launch.specs import _wrap2
        return _wrap2(opt), (_wrap2(ef) if ef is not None else None)

    def params_abs_local():
        return jax.tree.map(lambda l: l[0:1] if hasattr(l, "shape") else l,
                            params)

    # opt init inside shard_map so ZeRO slices are rank-correct
    def init_state_body(params_in):
        rank = jax.lax.axis_index(dp_axes)
        opt, ef = init_train_state(cfg, tcfg, params_in, dp, rank)
        from repro.launch.specs import _wrap2
        return _wrap2(opt), (_wrap2(ef) if ef is not None else None)

    opt_abs, ef_abs = abstract_opt_state(cfg, tcfg, params_abs, dp, ms)
    o_specs = state_spec_tree(opt_abs, dp_axes)
    e_specs = state_spec_tree(ef_abs, dp_axes) if ef_abs is not None else None
    opt_state, ef_state = jax.jit(shard_map(
        init_state_body, mesh=mesh, in_specs=(p_specs,),
        out_specs=(o_specs, e_specs), check_vma=False))(params)

    # ---- wrap the step --------------------------------------------------
    from repro.launch.specs import _unwrap2, _wrap2

    def body(params, opt, ef, batch):
        opt_l = _unwrap2(opt)
        ef_l = _unwrap2(ef) if ef is not None else None
        new_p, new_o, new_e, metrics = step_fn(params, opt_l, ef_l, batch)
        metrics = {k: v[None] for k, v in metrics.items()}
        return (new_p, _wrap2(new_o),
                _wrap2(new_e) if new_e is not None else None, metrics)

    b_specs = {"tokens": P(dp_axes, None), "targets": P(dp_axes, None)}
    if cfg.frontend is not None:
        b_specs["frontend_embeds"] = P(dp_axes, None, None)
    m_spec = {k: P(None) for k in ("loss", "grad_norm", "tokens")}
    train = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(p_specs, o_specs, e_specs, b_specs),
        out_specs=(p_specs, o_specs, e_specs, m_spec), check_vma=False))

    # ---- data + checkpoint + loop ---------------------------------------
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=S,
                                  batch_per_shard=B))
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start = 0
    if mgr is not None and mgr.latest_step() is not None:
        (params_h, opt_h, ef_h), extra = mgr.restore(
            (params, opt_state, ef_state))
        put = lambda tree, sp: jax.tree.map(
            lambda l, s: jax.device_put(jnp.asarray(l),
                                        NamedSharding(mesh, s)), tree, sp)
        params = put(params_h, p_specs)
        opt_state = put(opt_h, o_specs)
        ef_state = put(ef_h, e_specs) if ef_h is not None else None
        start = extra.get("step", mgr.latest_step())
        print(f"resumed from step {start}")
    mon = StragglerMonitor()
    losses = []
    for step in range(start, start + steps):
        b = data.batch_at(step)
        batch = {"tokens": jnp.asarray(b["tokens"]),
                 "targets": jnp.asarray(b["targets"])}
        if cfg.frontend is not None:
            batch["frontend_embeds"] = jnp.asarray(frontend_embeds_at(
                step, 0, B, cfg.frontend.num_positions,
                cfg.frontend.feature_dim))
        t0 = time.time()
        params, opt_state, ef_state, metrics = train(
            params, opt_state, ef_state, batch)
        loss = float(metrics["loss"][0])
        slow = mon.record(time.time() - t0)
        losses.append(loss)
        if step % log_every == 0 or slow:
            print(f"step {step} loss {loss:.4f} gnorm "
                  f"{float(metrics['grad_norm'][0]):.3f}"
                  + (" [STRAGGLER]" if slow else ""))
        if mgr is not None and (step + 1) % 10 == 0:
            mgr.save(step + 1, (params, opt_state, ef_state),
                     extra={"step": step + 1})
    if mgr is not None:
        mgr.wait()
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--full", action="store_true",
                    help="full config (real hardware only)")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    run(args.arch, steps=args.steps, use_reduced=not args.full,
        ckpt_dir=args.ckpt)


if __name__ == "__main__":
    main()
