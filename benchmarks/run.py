"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  CPU-only: each benchmark runs
in a child process that forces 8 emulated host devices, while this parent
never touches JAX; on a TPU host the children would each try to take the
chip, so chip runs go through ``chip_smoke.py`` instead.
"""
import os
import subprocess
import sys


MULTI = ["bench_primitives", "bench_core_module", "bench_cluster_size",
         "bench_dataflows", "bench_tpot"]


def _spawn(mod: str) -> int:
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": os.environ.get("PYTHONPATH", "src")}
    return subprocess.call([sys.executable, "-m", f"benchmarks.{mod}"],
                           env=env)


def main() -> None:
    print("name,us_per_call,derived")
    rc = 0
    for mod in MULTI:
        print(f"# --- {mod} (paper-table analogue) ---")
        rc |= _spawn(mod)
    if rc:
        raise SystemExit(rc)


if __name__ == "__main__":
    main()
