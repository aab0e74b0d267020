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


def _assert_kernel(compiled, name):
    """The compiled module runs the Pallas kernel as a TPU custom call
    that carries the kernel's name: as the instruction's own name
    (``%<name>.<n> = ...``), or, where ``vmap`` turned the per-slot
    kernel into a loop of calls, in its ``op_name`` metadata
    (``.../vmap(<name>)/while/body/closed_call``)."""
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    for ln in calls:
        inst = re.match(r"\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = ", ln)
        path = re.search(r'op_name="([^"]*)"', ln).group(1).split("/")
        assert (inst.group(1) == name or name in path
                or f"vmap({name})" in path), (inst.group(0), path)


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
