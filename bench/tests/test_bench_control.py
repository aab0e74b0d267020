"""The control: the reference in the program's place at fp8, one step
below the configuration's bfloat16, fails the limits that the program's
own runs pass (CPU, tiny widths; the readings at the cells' own sizes
come from the chip and are in PERF.md)."""
from __future__ import annotations

import pytest

from bench_tiny import TINY_MODEL, run_tiny


@pytest.mark.parametrize("seed", [3000000301, 3000000302, 3000000303])
def test_program_passes_and_control_fails(tiny_root, seed):
    res = run_tiny(tiny_root, seed, control=True)
    v, lim = res["values"], TINY_MODEL["limits"]
    assert res["correct"] is True, v
    assert v["served_tokens"] >= 20
    assert v["cand_err"] <= lim["cand_err"] and v["logit_gap"] <= \
        lim["logit_gap"]
    # the control, judged by the same verdict, is not correct
    assert v["control_correct"] is False, v
