"""The harness's run with the timed path broken underneath: ``correct``
comes out false for each fault a served cell can have."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench_tiny import TINY_MODEL, run_tiny


def token_altered(sched, state_in, nxt, st):
    """Slot 0 serves the next id instead of the one the head chose."""
    return nxt.at[0].set((nxt[0] + 1) % TINY_MODEL["vocab_size"]), st


def state_unchanged(sched, state_in, nxt, st):
    """The step returns its K/V caches unchanged (lengths still move)."""
    st = dict(st)
    st["layers"] = state_in["layers"]
    return nxt, st


def half_the_slots(sched, state_in, nxt, st):
    """The upper half of the slots is left out: it serves slot 0's token."""
    b = nxt.shape[0]
    return nxt.at[b // 2:].set(nxt[0]), st


@pytest.mark.parametrize("fault", [token_altered, state_unchanged,
                                   half_the_slots])
def test_fault_makes_correct_false(tiny_root, fault):
    res = run_tiny(tiny_root, 3000000201, fault=fault)
    assert res["correct"] is False, res["values"]
    assert jnp.isfinite(res["values"]["logit_gap"])
