"""chip_smoke.py's control flow on the CPU.

The script itself needs a TPU and has no CPU path; these tests drive its
build-and-drain and comparison functions at a ``reduced()`` size with
Pallas interpret mode passed in here, on 8 emulated host devices (one for
the one-chip phase, four for the cluster phase), and check that the
script refuses to run without a TPU.
"""
import os
import subprocess
import sys

import pytest

from helpers import run_multidevice

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.mark.multidevice
def test_chip_smoke_phases_reduced_interpret():
    out = run_multidevice(f"""
    sys.path.insert(0, {REPO!r})
    import chip_smoke as cs
    from repro.configs import get_config, reduced

    cfg = reduced(get_config("granite-8b"), n_layers=2)
    trace = cs.make_trace(0, cfg.vocab_size, prompts=(4, 12), outputs=(3, 6))
    kw = dict(slots=4, max_seq=32, prompt_cap=12, interpret=True)
    cs.run_one_chip(cfg, jax.devices(), trace, **kw)
    cs.run_four_chips(cfg, jax.devices(), trace, **kw)
    print("PHASES-OK")
    """)
    assert "PHASES-OK" in out
    assert "pallas vs xla: candidate values" in out
    assert "cluster4 vs cluster1: candidate values" in out


def test_chip_smoke_refuses_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr
