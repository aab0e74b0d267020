"""The work counts behind ``step_mfu`` and ``decode_roofline``,
reproduced from the published widths by hand."""
from __future__ import annotations

import json
import os

import pytest

from bench.harness.widths import Widths

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def widths(name: str) -> Widths:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return Widths.from_config(json.load(f))


def test_granite_parameters():
    w = widths("granite-8b-L9")
    # q 4096², k and v 4096×1024 each, o 4096²; gated FFN 3×4096×14336;
    # two norm scales
    assert w.attn_params == 41_943_040
    assert w.ffn_params == 176_160_768
    assert round(w.layer_params / 1e6, 1) == 218.1
    assert w.head_params == 49152 * 4096 == 201_326_592
    # 9 layers plus the tied table, counted once as the head
    assert round(w.used_params / 1e9, 3) == 2.164
    # about 4.3 GFLOP per generated token before attention
    assert w.layer_matmul_flops + 2 * w.head_params == pytest.approx(
        4.33e9, rel=2e-3)


def test_minitron_parameters():
    w = widths("minitron-4b-L16")
    assert w.attn_params == 2 * 3072 * 3072 + 2 * 3072 * 1024
    assert w.ffn_params == 2 * 3072 * 9216          # not gated
    assert round(w.layer_params / 1e6, 1) == 81.8
    assert w.head_params == 256000 * 3072 == 786_432_000
    assert round(w.used_params / 1e9, 3) == 2.095


def test_decode_step_bytes_and_flops():
    w = widths("granite-8b-L9")
    weights = (9 * w.layer_params + w.head_params + 4096) * 2
    assert w.weight_bytes_total == weights
    # K and V, 8 kv heads × 128, bf16, 9 layers: 36 864 bytes a position
    assert w.kv_bytes_per_position() == 2 * 8 * 128 * 2 * 9 == 36_864
    lives = [100, 300, 0]
    # each slot reads its live positions and writes the new one
    assert w.decode_step_bytes(lives) == weights + 403 * 36_864
    per_tok = w.layer_matmul_flops + 2 * w.head_params
    attn = sum(4 * 32 * 128 * (n + 1) * 9 for n in lives)
    assert w.decode_step_flops(lives) == 3 * per_tok + attn


def test_prompt_flops_count_causal_attention_and_one_head():
    w = widths("minitron-4b-L16")
    n = 10
    want = (n * w.layer_matmul_flops + 4 * 24 * 128 * 16 * (n * (n + 1) // 2)
            + 2 * w.head_params)
    assert w.prompt_flops(n) == want


def test_step_mfu_of_a_granite_longgen_window():
    """225 tokens/s of granite-8b-L9 at live length ~700 is about 0.5% of
    197 TFLOP/s: counting padding or whole-cache attention would read
    several times more."""
    w = widths("granite-8b-L9")
    mfu = 225 * w.decode_flops(700) / 197e12
    assert 0.004 < mfu < 0.006
