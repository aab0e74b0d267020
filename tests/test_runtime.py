"""Chip-run set-up (launch/runtime.py), the device-kind peak table
(core/autotune.py) and the published-width depth cut (configs/base.py)."""
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from repro.configs import depth_cut, get_config
from repro.core.autotune import (CHIP_PEAKS, HBM_BW, PEAK_FLOPS,
                                 REHEARSED_KIND, chip_peaks)
from repro.launch.runtime import CACHE_DIR, check_interpret, require_tpu

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def test_peaks_keyed_by_device_kind():
    v5e = chip_peaks("TPU v5 lite")
    assert REHEARSED_KIND == "TPU v5 lite"
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    assert (PEAK_FLOPS, HBM_BW) == (v5e.flops, v5e.hbm_bw)
    with pytest.raises(ValueError, match="no peak figures"):
        chip_peaks("TPU v9 imaginary")
    assert set(CHIP_PEAKS) == {"TPU v5 lite"}


def test_require_tpu_refuses_cpu():
    with pytest.raises(RuntimeError, match="no TPU"):
        require_tpu()


def test_interpret_refused_on_tpu_mesh():
    def mesh(platform):
        return SimpleNamespace(
            devices=np.array([SimpleNamespace(platform=platform)]))
    check_interpret(True, mesh("cpu"))
    check_interpret(False, mesh("tpu"))
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        check_interpret(True, mesh("tpu"))


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(tmp_path, env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    want = str(CACHE_DIR)
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = ("import sys; sys.path.insert(0, %r)\n"
            "import jax\n"
            "from repro.launch.runtime import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n" % SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True,
                         timeout=300).stdout.split()
    assert out == [want, want]


def test_depth_cut_keeps_published_widths():
    full = get_config("granite-8b")
    cut = depth_cut(full, 4)
    assert cut.n_layers == 4 and cut.name == "granite-8b-L4"
    for f in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "vocab_size", "tie_embeddings", "ffn_act", "ffn_gated"):
        assert getattr(cut, f) == getattr(full, f), f
    for bad in (0, 37):
        with pytest.raises(ValueError):
            depth_cut(full, bad)
    with pytest.raises(ValueError):        # gemma2's pattern period is 2
        depth_cut(get_config("gemma2-27b"), 3)
