"""Ahead-of-time compiles of the decode path's Pallas kernels for a
described TPU v5e chip, at published widths.

Interpret mode cannot see what the TPU compiler refuses (matmuls with
more than one batch dim, blocks larger than the scoped VMEM, unaligned
tiles).  These tests lower each kernel exactly as the serving engine
calls it — one batch-1 kernel per slot, ``vmap``-ed over 8 slots with
the scalar-prefetch operands batched (``core/dataflow.py``) — and
compile it for one chip of a ``v5e:2x2`` topology.  Nothing runs: a
compile that passes says nothing about results or times.  Each kernel's
``name=`` must survive into the compiled module as the name of its
custom call: a profiler trace reads per-kernel time by that name.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and pytest-xdist workers all
import this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.autotune import pick_block_f, pick_block_s, pick_block_v
from repro.core.dataflow import _fit_block_s
from repro.kernels.fused_decode.fused_decode import fused_decode_attention
from repro.kernels.fused_ffn.fused_ffn import fused_ffn_block
from repro.kernels.fused_head.fused_head import fused_head_block
from repro.kernels.fused_mla_decode.fused_mla_decode import (
    fused_mla_decode_attention)

SLOTS = 8          # chip_smoke.py's slot count
MAX_SEQ = 2048     # and its cache capacity
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_for(one_chip):
    """``compile_for(fn, *shapes)`` → the compiled executable.  The
    persistent cache stays off: an entry compiled for a described chip
    cannot be read back without one."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)

    def go(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()

    yield go
    jax.config.update("jax_enable_compilation_cache", was)


def _assert_kernel(compiled, *names):
    """The compiled module runs the Pallas kernels as TPU custom calls,
    each carrying one of the kernels' ``names`` and each name carried by
    some call: as the instruction's own name (``%<name>.<n> = ...``), or,
    where ``vmap`` turned the per-slot kernel into a loop of calls, in
    its ``op_name`` metadata (``.../vmap(<name>)/while/body/closed_call``)."""
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    found = set()
    for ln in calls:
        inst = re.match(r"\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = ", ln)
        path = re.search(r'op_name="([^"]*)"', ln).group(1).split("/")
        carried = [n for n in names if inst.group(1) == n or n in path
                   or f"vmap({n})" in path]
        assert carried, (inst.group(0), path, names)
        found.update(carried)
    assert found == set(names), (found, names)


@pytest.mark.parametrize("mode", ["partial_o", "partial_o_ring", "adapter"])
def test_fused_decode_compiles_granite(compile_for, mode):
    cfg = get_config("granite-8b")
    D, q, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ring = mode == "partial_o_ring"
    fuse_out = False if mode == "adapter" else "partial_o"
    blk = _fit_block_s(MAX_SEQ, pick_block_s(cfg, MAX_SEQ, 1, 1))

    def step(x, wqkv, wo, kc, vc, cl, cos, sin, pos, inc, ln1):
        def one(xb, kb, vb, c, cb, sb, pb, ib):
            out = fused_decode_attention(
                xb[None], wqkv, None, wo, kb, vb, c, cb, sb,
                q_heads=q, kv_heads=kv, window=1024 if ring else 0,
                ring=ring, block_s=blk, fuse_out=fuse_out, pos=pb,
                include_new=ib, pos_base=jnp.int32(-1 if ring else 0),
                norm_scale=None if mode == "adapter" else ln1)
            return tuple(o[0] for o in out)
        return jax.vmap(one, in_axes=(0, 1, 1, 0, 0, 0, 1, 0))(
            x, kc, vc, cl, cos, sin, pos, inc)

    wo = ((q, hd, D), BF16) if fuse_out else ((1, 1), BF16)
    c = compile_for(
        step, ((SLOTS, D), BF16), ((D, (q + 2 * kv) * hd), BF16), wo,
        ((MAX_SEQ, SLOTS, kv, hd), BF16), ((MAX_SEQ, SLOTS, kv, hd), BF16),
        ((SLOTS,), jnp.int32), ((SLOTS, hd // 2), jnp.float32),
        ((SLOTS, hd // 2), jnp.float32), ((MAX_SEQ, SLOTS), jnp.int32),
        ((SLOTS,), jnp.int32), ((D,), jnp.float32))
    _assert_kernel(c, "fused_decode")


def test_fused_ffn_compiles_granite(compile_for):
    cfg = get_config("granite-8b")
    D, F = cfg.d_model, cfg.d_ff
    bf = _fit_block_s(F, pick_block_f(cfg))

    def step(x, a, wi, wg, wo, ln2):
        return fused_ffn_block(x, a, wi, wg, wo, ln2, None, jnp.float32(1.0),
                               act=cfg.ffn_act, block_f=bf)

    c = compile_for(step, ((SLOTS, D), BF16), ((SLOTS, D), BF16),
                    ((D, F), BF16), ((D, F), BF16), ((F, D), BF16),
                    ((D,), BF16))
    _assert_kernel(c, "fused_ffn")


def _decode_step_3_layers(compile_for, topo, arch):
    """AOT-compile ``decode_step`` as ``launch/serve.build_engine_full``
    wraps it (prepacked Pallas path, fused head, ``SLOTS`` slots), for
    ``arch`` at its published widths cut to 3 layers, on one described
    chip.  Returns ``(cfg, compiled)``."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.configs import depth_cut
    from repro.configs.base import ShapeConfig
    from repro.core.autotune import tune_serving
    from repro.launch.mesh import device_mesh, dp_axes_of
    from repro.launch.specs import (_unwrap2, _wrap2, ctx_for,
                                    serving_layout, state_spec_tree)
    from repro.models.transformer import init_device_major, param_specs
    from repro.serving.engine import (ServeConfig, decode_step,
                                      init_decode_state)
    from repro.serving.prepack import (attn_subtree, bundle_ffn,
                                       bundle_head, merge_packed,
                                       prepack_for_serving)

    cfg = depth_cut(get_config(arch), 3)
    mesh = device_mesh(topo.devices[:1])
    lay = serving_layout(cfg, ShapeConfig("serve", MAX_SEQ, SLOTS, "decode"),
                         1)
    ctx = ctx_for(mesh, lay, fused_combine=False)
    plan = tune_serving(cfg, seq_len=MAX_SEQ, batch=SLOTS, model_axis=1,
                        backend="pallas", prepack="on", table_path=None)
    scfg = ServeConfig(max_seq=MAX_SEQ, batch_local=SLOTS, backend="pallas",
                       block_s=plan.block_s, block_f=plan.block_f,
                       block_v=plan.block_v, prepack=True)
    p_abs = jax.eval_shape(
        lambda: init_device_major(cfg, lay, jax.random.PRNGKey(0)))
    pack = lambda t: prepack_for_serving(cfg, lay, t, backend="pallas")
    a_abs = jax.eval_shape(pack, attn_subtree(p_abs))
    bundle = lambda t: bundle_head(cfg, bundle_ffn(cfg, t, backend="pallas"),
                                   backend="pallas")
    sv_abs = bundle(merge_packed(p_abs, a_abs))
    sv_specs = bundle(merge_packed(param_specs(cfg, p_abs),
                                   param_specs(cfg, a_abs)))
    st_abs = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((1, 1) + l.shape, l.dtype),
        jax.eval_shape(lambda: init_decode_state(cfg, scfg, ctx)))
    dp = dp_axes_of(mesh)
    st_specs = state_spec_tree(st_abs, dp)

    def body(params, state, tokens):
        nxt, new = decode_step(ctx, cfg, scfg, params, _unwrap2(state),
                               tokens)
        return nxt, _wrap2(new)

    dec = shard_map(body, mesh=mesh, in_specs=(sv_specs, st_specs, P(dp)),
                    out_specs=(P(dp), st_specs), check_vma=False)
    leaves, tree = jax.tree.flatten(
        (sv_abs, st_abs, jax.ShapeDtypeStruct((SLOTS,), jnp.int32)))
    c = compile_for(lambda *xs: dec(*jax.tree.unflatten(tree, xs)),
                    *[(l.shape, l.dtype) for l in leaves])
    return cfg, c


@pytest.mark.parametrize("arch", ["granite-8b", "minitron-4b"])
def test_decode_scan_reads_weight_stacks_granite(compile_for, topo, arch):
    """The real prepacked decode step, 3 layers at published widths (the
    gated granite FFN and the non-gated minitron one): the fused FFN
    kernel reads the ``[L, …]`` weight stacks by layer index, so the
    compiled module writes no buffer of one layer's FFN weights (a
    ``dynamic-slice`` fusion per weight and layer before) and copies no
    stack: a stack-shaped value is only the parameter itself, a bitcast
    or a loop-tuple element of it.  The temp bytes stay below one FFN
    weight matrix (236 MB before, at granite widths).  ``fused_decode``,
    ``fused_ffn`` and ``fused_head`` keep their names: a trace reads
    per-kernel time by them."""
    cfg, c = _decode_step_3_layers(compile_for, topo, arch)
    L, D, F = cfg.n_layers, cfg.d_model, cfg.d_ff
    shape = lambda *s: f"bf16[{','.join(map(str, s))}]"
    layer, stack = {shape(D, F), shape(F, D)}, {shape(L, D, F), shape(L, F, D)}
    aliases = ("parameter(", "get-tuple-element(", "bitcast(")
    writes = [ln.strip()[:160] for ln in c.as_text().splitlines()
              if (m := re.match(r"\s*(?:ROOT )?%\S+ = (\w+\[[\d,]*\])\S* (.*)",
                                ln))
              and (m.group(1) in layer
                   or (m.group(1) in stack
                       and not m.group(2).startswith(aliases)))]
    assert not writes, writes
    assert c.memory_analysis().temp_size_in_bytes < D * F * 2
    _assert_kernel(c, "fused_decode", "fused_ffn", "fused_head")


def test_fused_head_compiles_granite(compile_for):
    from repro.serving.sampling import CAND_K
    cfg = get_config("granite-8b")
    D, V = cfg.d_model, cfg.vocab_size
    bv = pick_block_v(cfg, batch=SLOTS, k=CAND_K)
    while V % bv:
        bv -= 1

    def step(x, table, ln):
        return fused_head_block(x, table, ln, block_v=bv, k=CAND_K)

    c = compile_for(step, ((SLOTS, D), BF16), ((V, D), BF16), ((D,), BF16))
    _assert_kernel(c, "fused_head")


def test_fused_mla_decode_compiles_deepseek_v2_lite(compile_for):
    cfg = get_config("deepseek-v2-lite")
    m = cfg.mla
    D, q = cfg.d_model, cfg.n_heads
    lr = m.kv_lora_rank + m.rope_head_dim
    blk = _fit_block_s(MAX_SEQ, pick_block_s(cfg, MAX_SEQ, 1, 1))

    def step(x, wq, wdkv, wuk, wproj, cc, cl, cos, sin, pos, inc, ln1):
        def one(xb, cb, c, cs, sn, pb, ib):
            out = fused_mla_decode_attention(
                xb[None], wq, wdkv, wuk, wproj, jnp.zeros((1, D), BF16), cb,
                c, cs, sn, q_heads=q, nope=m.nope_head_dim,
                rope_d=m.rope_head_dim, l_rank=m.kv_lora_rank, v_dim=D,
                block_s=blk, fuse_out="partial_o", pos=pb, include_new=ib,
                pos_base=jnp.int32(0), norm_scale=ln1)
            return tuple(o[0] for o in out)
        return jax.vmap(one, in_axes=(0, 1, 0, 0, 0, 1, 0))(
            x, cc, cl, cos, sin, pos, inc)

    c = compile_for(
        step, ((SLOTS, D), BF16),
        ((D, q * (m.nope_head_dim + m.rope_head_dim)), BF16),
        ((D, lr), BF16), ((q, m.nope_head_dim, m.kv_lora_rank), BF16),
        ((q, m.kv_lora_rank, D), BF16), ((MAX_SEQ, SLOTS, lr), BF16),
        ((SLOTS,), jnp.int32), ((SLOTS, m.rope_head_dim // 2), jnp.float32),
        ((SLOTS, m.rope_head_dim // 2), jnp.float32),
        ((MAX_SEQ, SLOTS), jnp.int32), ((SLOTS,), jnp.int32),
        ((D,), jnp.float32))
    _assert_kernel(c, "fused_mla_decode")
