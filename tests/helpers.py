"""Test helpers: subprocess runner for multi-device tests.

The main pytest process keeps ONE CPU device (per assignment: no global
XLA_FLAGS).  Tests that need a mesh spawn a subprocess that sets
``--xla_force_host_platform_device_count=8`` before importing jax.
"""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
"""


def run_multidevice(body: str, timeout: int = 900) -> str:
    """Run ``body`` (python source) in a subprocess with 8 host devices.
    Raises on nonzero exit; returns stdout."""
    script = PRELUDE.format(src=os.path.abspath(SRC)) + textwrap.dedent(body)
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=timeout,
        env={**os.environ, "PYTHONPATH": os.path.abspath(SRC)})
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{proc.stdout[-4000:]}\n"
            f"STDERR:\n{proc.stderr[-4000:]}")
    return proc.stdout
