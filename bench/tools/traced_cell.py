"""Run one cell traced, as ``bench/run.py --trace 1`` does, and add what
the served path's own spans, named kernels and counters say.

    python3 bench/tools/traced_cell.py --workload <cell> --seed <n> --seconds <s>

The last line of standard output is ``bench/run.py``'s result line with
six more per-layer metrics in ``metrics`` (``decode_kernel_ms``,
``decode_xla_ms``, ``decode_dispatch_ms``, ``commit_lag_ms``,
``host_syncs_per_tick``, ``admit_pad_share``; each read by
``bench/metrics/<name>.py``) and more entries in ``breakdown``:
``decode_kernels`` (device seconds per named kernel inside the window's
decode programs), ``decode_xla_by_scope`` (their other leaf ops by the
innermost named scope of ``decode_step``), ``idle_by_span`` (device idle seconds by the innermost
``serve.*`` span) and ``clock`` (the decode-step alignment: offset,
spread, pairs, decode programs left without a span, and
``trace.clock_offset``'s estimate beside it).

``serve.run_cell`` reduces its trace and deletes it before it returns;
this tool wraps the harness's ``load`` so that the same parse also feeds
``harness/serve_trace.py``, and its window so that the serve counters'
deltas and the decode step at which the trace starts are kept."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run as R  # noqa: E402  (sets the paths and the cache)
from bench.harness import serve, serve_trace, spec, trace  # noqa: E402
from repro.core import tracecount  # noqa: E402

METRICS = {"decode_kernel_ms": "ms", "decode_xla_ms": "ms",
           "decode_dispatch_ms": "ms", "commit_lag_ms": "ms",
           "host_syncs_per_tick": "count", "admit_pad_share": "%"}


def traced_run(cell, seed: int, seconds: float, peaks: dict) -> dict:
    """``serve.run_cell`` with a trace; its ``Run`` gains ``serve_counts``
    (the serve counters' deltas over the window) and ``serve_trace``
    (:class:`serve_trace.ServeReduced`)."""
    got = {}
    load, drive = serve.load, serve._Window.drive

    def both(path):
        t = time.perf_counter()
        got["td"], got["st"] = serve_trace.load(path)
        got["load_s"] = time.perf_counter() - t
        return got["td"]

    def counted(win, pending, t0, end):
        got["step0"] = win.router.replicas[0].sched.decode_calls
        before = tracecount.serve_totals()
        try:
            drive(win, pending, t0, end)
        finally:
            got["counts"] = tracecount.serve_totals() - before

    serve.load, serve._Window.drive = both, counted
    try:
        res = serve.run_cell(cell, seed, seconds, True, peaks=peaks)
    finally:
        serve.load, serve._Window.drive = load, drive
    run = res["run"]
    run.serve_counts = got.get("counts")
    if "st" in got:
        td, t = got["td"], time.perf_counter()
        run.serve_trace = serve_trace.reduce(td, got["st"],
                                             run.decode_module,
                                             got["step0"])
        serve.log(f"trace load (both reductions' inputs) {got['load_s']!r}"
                  f" s; serve reduction {time.perf_counter() - t!r} s")
        # the benchmark's own estimate, from the bench.decode spans
        plane = next(iter(td.ops))
        run.serve_trace.clock["bench_offset_s"] = trace.clock_offset(
            td.modules.get(plane, []), td.host, run.decode_module)
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    try:
        peaks = R.require_chips(cell.chips)
    except R.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    res = traced_run(cell, args.seed, args.seconds, peaks)
    run = res["run"]
    line = R.result_line(cell, res, True)
    line["metrics"].update(spec.read_metrics(
        [{"name": n, "unit": u} for n, u in METRICS.items()], run))
    e2e = spec.read_metrics(cell.end_to_end, run)
    serve.log("end-to-end metrics of the traced window: " + ", ".join(
        f"{k} {v['value']!r}" for k, v in e2e.items()))
    c, t = run.serve_counts or {}, getattr(run, "serve_trace", None)
    serve.log(f"serve counters over the window {dict(c)}; ticks "
              f"{run.ticks}, decode calls {run.decode_calls}, admit calls "
              f"{run.admit_calls}; the loop's own count of host syncs "
              f"(a probe a tick, two reads a decode, one an admit) "
              f"{run.ticks + 2 * run.decode_calls + run.admit_calls}")
    if run.admit_calls:
        mix = run.traffic
        ran = run.admit_calls * mix["slots"] * mix["prompt_cap"]
        serve.log("admit padding from the harness's own record "
                  f"{(1 - sum(run.admit_prompts) / ran) * 100!r} %")
    if t is not None:
        line.setdefault("breakdown", {}).update(
            decode_kernels=t.decode_kernels,
            decode_xla_by_scope=t.decode_other_by_scope,
            idle_by_span=t.idle_by_span, clock=t.clock)
    line["checks"] = line.pop("checks")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
