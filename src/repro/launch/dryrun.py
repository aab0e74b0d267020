import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (architecture × input shape)
cell on the production meshes, extract memory/cost/collective statistics,
and emit the roofline terms against the v5e peaks of
``core/autotune.CHIP_PEAKS``.

MUST be imported before any other jax-touching module — the two lines
above run before any other import so jax sees 512 host devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
      --shape decode_32k [--multi-pod] [--json out.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import re
import sys
import time
from typing import Dict, Optional

import jax

from repro.configs import SHAPES, get_config, list_archs, shapes_for
from repro.core.autotune import HBM_BW, ICI_BW, PEAK_FLOPS  # noqa: F401
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (build_decode_step, build_prefill_step,
                                build_train_step)
from repro.training.optimizer import OptConfig
from repro.training.train_step import TrainConfig

_COLL_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"[^=]*= \(?([a-z0-9_]+)\[([0-9,]*)\]")
_SHAPE_RE = re.compile(r"([a-z0-9_]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum output-operand bytes of every collective op in the (SPMD,
    per-device) HLO.  Keyed by op kind; 'total' included."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r".*= \(?([a-z0-9_]+)\[([0-9,]*)\][^)]*\)? "
                     r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)", line)
        if not m:
            continue
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        nbytes = _DTYPE_BYTES.get(dt, 4)
        for d in dims.split(","):
            if d:
                nbytes *= int(d)
        out[kind] = out.get(kind, 0.0) + nbytes
        out["total"] = out.get("total", 0.0) + nbytes
    return out


def collective_counts(hlo_text: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for kind in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                 "collective-permute"):
        out[kind] = len(re.findall(rf"\b{kind}\b", hlo_text))
    return out


def analyse(compiled, lowered=None) -> Dict[str, float]:
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):      # pre-0.5 JAX: one dict per device
        cost = cost[0] if cost else {}
    txt = compiled.as_text()
    coll = collective_bytes_scaled(txt)   # while-trip-count aware
    counts = collective_counts(txt)
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    cbytes = coll.get("total", 0.0)
    t_comp = flops / PEAK_FLOPS
    t_mem = bytes_acc / HBM_BW
    t_coll = cbytes / ICI_BW
    dominant = max((("compute", t_comp), ("memory", t_mem),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]
    return {
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "collective_bytes_per_device": cbytes,
        "collective_counts": counts,
        "collective_bytes_by_kind": coll,
        "t_compute_s": t_comp,
        "t_memory_s": t_mem,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "peak_device_bytes": (mem.argument_size_in_bytes
                              + mem.temp_size_in_bytes
                              + mem.output_size_in_bytes),
    }


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             opt_name: Optional[str] = None, verbose: bool = True,
             tcfg_kw: Optional[dict] = None) -> Dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape not in shapes_for(cfg):
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": "long-context cell skipped for unbounded "
                          "full-attention KV (DESIGN.md §4)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    if shape.mode == "train":
        # production defaults: FSDP (ZeRO-3) + bf16 grad accumulation;
        # 1T-class MoE additionally needs factored optimizer state to fit
        opt = opt_name or ("adafactor"
                           if cfg.param_count() > 3e11 else "adamw")
        kw = dict(fsdp=True, microbatches=4, grad_dtype="bf16")
        kw.update(tcfg_kw or {})
        tcfg = TrainConfig(opt=OptConfig(name=opt), **kw)
        fn, abstract, lay = build_train_step(cfg, mesh, tcfg, shape)
        args = abstract
    elif shape.mode == "prefill":
        fn, abstract, lay, _ = build_prefill_step(cfg, mesh, shape)
        args = abstract
    else:
        fn, abstract, lay, _ = build_decode_step(cfg, mesh, shape)
        args = abstract
    with mesh:
        lowered = jax.jit(fn).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    stats = analyse(compiled, lowered)
    # MODEL_FLOPS: 6·N·D (dense) / 6·N_active·D per step (train);
    # 2·N_active per decoded token (decode); 2·N_active·D (prefill).
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.mode != "decode"
                                   else 1)
    if shape.mode == "train":
        model_flops = 6.0 * n_active * tokens
    else:
        model_flops = 2.0 * n_active * tokens
    n_dev = 512 if multi_pod else 256
    stats.update({
        "arch": arch, "shape": shape_name, "mode": shape.mode,
        "multi_pod": multi_pod, "n_devices": n_dev,
        "heads_sub": lay.heads_sub, "cluster": lay.cluster,
        "model_flops_total": model_flops,
        "useful_flops_ratio": model_flops / max(
            stats["flops_per_device"] * n_dev, 1.0),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
    })
    if verbose:
        print(f"[{arch} × {shape_name} × "
              f"{'2x16x16' if multi_pod else '16x16'}] "
              f"heads_sub={lay.heads_sub} cluster={lay.cluster} "
              f"compile={t_compile:.1f}s")
        print(f"  flops/dev={stats['flops_per_device']:.3e} "
              f"bytes/dev={stats['bytes_per_device']:.3e} "
              f"coll/dev={stats['collective_bytes_per_device']:.3e}")
        print(f"  t_comp={stats['t_compute_s']*1e3:.3f}ms "
              f"t_mem={stats['t_memory_s']*1e3:.3f}ms "
              f"t_coll={stats['t_collective_s']*1e3:.3f}ms "
              f"dominant={stats['dominant']}")
        print(f"  peak_dev_bytes={stats['peak_device_bytes']/2**30:.2f}GiB "
              f"(args {stats['argument_bytes']/2**30:.2f} + temp "
              f"{stats['temp_bytes']/2**30:.2f}) "
              f"useful_flops_ratio={stats['useful_flops_ratio']:.3f}")
    return stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for arch in list_archs():
            cfg = get_config(arch)
            for shape in SHAPES.values():
                cells.append((arch, shape.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            try:
                results.append(run_cell(arch, shape, multi_pod=mp))
            except Exception as e:  # noqa: BLE001 — report and continue
                failures += 1
                print(f"[{arch} × {shape} × mp={mp}] FAILED: {e!r}",
                      file=sys.stderr)
                results.append({"arch": arch, "shape": shape,
                                "multi_pod": mp, "error": repr(e)})
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1, default=str)
        print(f"wrote {args.json}")
    print(f"{len(results)} cells, {failures} failures")
    return 1 if failures else 0




# ---------------------------------------------------------------------------
# Trip-count-aware collective accounting: XLA cost_analysis and a naive HLO
# text walk count a `while` body ONCE; scans over layers / KV chunks /
# microbatches hide their per-iteration collectives.  This walker assigns
# each op to its enclosing computation, recovers while trip counts from the
# canonical jax lowering (condition `compare(iter, constant(N))`), and
# multiplies through the (possibly nested) call graph.
# ---------------------------------------------------------------------------
def _hlo_computations(txt: str):
    comps, cur, name = {}, [], None
    for line in txt.splitlines():
        m = re.match(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{",
                     line)
        if m:
            name = m.group(1)
            cur = []
            comps[name] = cur
            continue
        if name is not None:
            if line.strip().startswith("}"):
                name = None
            elif line.strip():
                cur.append(line)
    return comps


def _trip_count(cond_lines) -> int:
    consts = {}
    for ln in cond_lines:
        m = re.match(r"\s*%?([\w.\-]+)\s*=\s*[a-z0-9]+\[\]\s*constant\((\d+)\)", ln)
        if m:
            consts[m.group(1)] = int(m.group(2))
    for ln in cond_lines:
        m = re.search(r"compare\(([^)]*)\)", ln)
        if m:
            for arg in m.group(1).split(","):
                arg = arg.strip().lstrip("%")
                if arg in consts:
                    return consts[arg]
    if len(consts) == 1:
        return next(iter(consts.values()))
    return 1


def collective_bytes_scaled(txt: str):
    """Collective bytes with while-trip-count multipliers applied."""
    comps = _hlo_computations(txt)
    # computation -> multiplier (product of enclosing while trip counts)
    mult = {name: 1 for name in comps}
    # find while ops: body/condition computation references
    edges = []       # (parent_comp, child_comp, factor)
    for name, lines in comps.items():
        for ln in lines:
            wm = re.search(r"while\(.*?\).*condition=%?([\w.\-]+).*"
                           r"body=%?([\w.\-]+)", ln)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                n = _trip_count(comps.get(cond, []))
                edges.append((name, body, n))
            cm = re.findall(r"(?:to_apply|calls)=%?([\w.\-]+)", ln)
            for child in cm:
                edges.append((name, child, 1))
            fm = re.search(r"fusion\(.*?\).*calls=%?([\w.\-]+)", ln)
            if fm:
                edges.append((name, fm.group(1), 1))
    # propagate multipliers (few levels; iterate to fixpoint)
    for _ in range(8):
        changed = False
        for parent, child, n in edges:
            want = mult.get(parent, 1) * n
            if child in mult and mult[child] < want:
                mult[child] = want
                changed = True
        if not changed:
            break
    out = {}
    for name, lines in comps.items():
        f = mult.get(name, 1)
        for ln in lines:
            m = re.match(r".*= \(?([a-z0-9_]+)\[([0-9,]*)\][^)]*\)? "
                         r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                         r"collective-permute)", ln.strip())
            if not m:
                continue
            dt, dims, kind = m.group(1), m.group(2), m.group(3)
            nbytes = _DTYPE_BYTES.get(dt, 4)
            for d in dims.split(","):
                if d:
                    nbytes *= int(d)
            out[kind] = out.get(kind, 0.0) + nbytes * f
            out["total"] = out.get("total", 0.0) + nbytes * f
    return out

if __name__ == "__main__":
    sys.exit(main())
