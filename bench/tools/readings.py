"""Readings for the limits and the rate sweep, many seeds in one process.

    python3 bench/tools/readings.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 [--control 1,2] [--rate 2.0]

For each seed it makes one run of the cell as ``bench/run.py`` does and
prints one JSON line: the compared numbers (with the fp8 control's for
the seeds under ``--control``) and the cell's end-to-end metrics.
``--rate`` overrides the mix's arrival rate (the chat sweep);
``--fault token`` serves, in every slot, the next id after the one the
head chose (the reading of that fault for the limits).  The runs
share one process so that set-up compiles once; ``setup_s`` here is not
the benchmark's."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))
import run as bench_run  # noqa: E402  (sets the cache and the paths)

from bench.harness import serve, spec  # noqa: E402


def token_altered(sched, state_in, nxt, st):
    """Every slot serves the next id instead of the one the head chose."""
    return (nxt + 1) % sched.eng.cfg.vocab_size, st


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--fault", choices=("token",), default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    peaks = bench_run.require_chips(cell.chips)
    if args.rate is not None:
        cell.traffic["arrivals"]["rate"] = args.rate
    control = {int(s) for s in args.control.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        res = serve.run_cell(cell, seed, args.seconds, False, peaks=peaks,
                             control=seed in control,
                             fault=token_altered if args.fault else None)
        run = res["run"]
        metrics = spec.read_metrics(cell.end_to_end, run)
        per = spec.read_metrics([m for m in cell.per_layer
                                 if m["source"] == "host_clock"], run)
        line = {"seed": seed, "rate": args.rate, "fault": args.fault,
                "correct": res["correct"],
                "attempted": res["attempted"], "failed": res["failed"],
                "values": res["values"],
                "metrics": {k: v["value"] for k, v in metrics.items()},
                "per_layer": {k: v["value"] for k, v in per.items()},
                "admits": run.admit_calls, "ticks": run.ticks,
                "peak": res["device"]["memory_peak_bytes"],
                "wall_s": time.perf_counter() - t}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
