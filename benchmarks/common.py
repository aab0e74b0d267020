"""Benchmark utilities: timing + the 8-host-device subprocess pattern.

All benchmarks print ``name,us_per_call,derived`` CSV rows (one per paper
table/figure cell).  They run on the CPU: their wall times are relative
indicators only and never a device metric (no number here was measured on
a TPU); the derived column carries the analytic quantity the paper's table
reports (traffic bytes, speedup ratio, …).
"""
import time

import jax
import numpy as np


def time_fn(fn, *args, iters: int = 20, warmup: int = 3) -> float:
    """Median wall-time per call in µs (blocks on results)."""
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(times))


def row(name: str, us: float, derived) -> str:
    line = f"{name},{us:.1f},{derived}"
    print(line)
    return line
