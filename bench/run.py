"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` → ``workloads``) names a configuration
(``bench/configs``) and a traffic mix (``bench/traffic``); the metrics
are read by ``bench/metrics/<name>.py``.  With ``--trace 0`` the result
carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the same window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), then ``checks``, each compared number beside its
limit; the same numbers are the last lines of standard error.  A run that
finds no TPU, or fewer chips than the cell asks for, exits non-zero and
prints no result."""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the compile cache lives at a fixed path inside the checkout unless the
# environment names one; the TPU compiler writes no logs under /tmp
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(HERE, ".cache", "jax"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import repro  # noqa: E402,F401  (the program under test; without it, no run)
from bench.harness import serve, spec  # noqa: E402
from bench.harness.peaks import peaks_for  # noqa: E402


class NoChip(RuntimeError):
    pass


def require_chips(n: int) -> dict:
    """The peaks of the chips JAX finds; raises without ``n`` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {len(devs)} {devs[0].platform} "
                     f"device(s)")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return peaks_for(devs[0].device_kind)


def result_line(cell, res: dict, trace: bool) -> dict:
    run = res["run"]
    metrics = spec.read_metrics(cell.per_layer if trace else cell.end_to_end,
                                run)
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics,
           "device": res["device"]}
    if trace and run.trace is not None:
        out["breakdown"] = {"device_ops": run.trace.top_ops,
                            "idle_gaps": run.trace.idle_gaps}
    out["checks"] = res["checks"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    try:
        peaks = require_chips(cell.chips)
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    import jax
    d = jax.devices()[0]
    serve.log(f"device: {d.platform} {d.device_kind} x{cell.chips}; cell "
              f"{cell.name} ({cell.config['name']} under "
              f"{cell.traffic['name']}); seed {args.seed}")
    res = serve.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         peaks=peaks)
    line = result_line(cell, res, bool(args.trace))
    for name, c in line["checks"].items():
        serve.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
