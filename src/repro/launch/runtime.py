"""Process set-up for a run on the chip: the TPU check and JAX's
persistent compilation cache.

A chip run has no CPU path: :func:`require_tpu` raises when JAX finds no
TPU, and Pallas interpret mode is refused on one
(:func:`check_interpret`).  CPU runs (tests, examples under
``JAX_PLATFORMS=cpu``) use the host-device meshes of
:mod:`repro.launch.mesh` and interpret mode instead.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

from repro.core.autotune import chip_peaks

# <repo>/.jax_cache — a fixed path (it is part of the cache key), listed
# in .gitignore
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache goes to the fixed,
    git-ignored :data:`CACHE_DIR` inside the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def require_tpu() -> list:
    """The process's devices, which must be TPUs with peak figures in
    ``core/autotune.CHIP_PEAKS``; raises otherwise (no CPU fallback)."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s)")
    chip_peaks(devs[0].device_kind)
    return devs


def check_interpret(interpret: bool, mesh) -> None:
    """Pallas interpret mode exists for the CPU; on a TPU it would hide
    the compiled kernels, so it raises there."""
    if interpret and mesh.devices.flat[0].platform == "tpu":
        raise ValueError("interpret=True on a TPU: Pallas interpret mode "
                         "is for CPU runs only")
