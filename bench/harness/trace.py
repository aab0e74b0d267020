"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

What is read, per TPU plane (``/device:TPU:<n>``): the ``XLA Ops`` line
(one event per operation run on the device) and the ``XLA Modules`` line
(one event per program run).  On the host plane (``/host:CPU``), the
``TraceAnnotation`` spans that the benchmark's own files open: the
window, each router tick, and each admit and decode call.

Device and host timestamps share an origin but not a clock: the device
line can sit a millisecond or two off.  The offset is estimated from the
decode calls, whose host span ends right after the device finishes the
program (the span holds ``block_until_ready``)."""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from statistics import median
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]        # (name, start_s, end_s)

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.tick", "bench.admit", "bench.decode")
_OP = re.compile(r"^%(\S+) = .*?[\]\})] ([a-z][a-z0-9_\-]*)\(")


@dataclass
class TraceData:
    """Events of one trace, in seconds from the profile's origin."""
    ops: Dict[str, List[Interval]] = field(default_factory=dict)
    modules: Dict[str, List[Interval]] = field(default_factory=dict)
    host: List[Interval] = field(default_factory=list)


def op_label(name: str) -> str:
    """``%fusion.3 = bf16[..] fusion(...)`` → ``fusion.3 (fusion)``."""
    m = _OP.match(name)
    if m:
        return f"{m.group(1)} ({m.group(2)})"
    return name[:80]


def load(path: str) -> TraceData:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> TraceData:
    td = TraceData()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                evs = [(e.name, e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9)
                       for e in line.events]
                dest = td.ops if line.name == "XLA Ops" else td.modules
                dest[plane.name] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN or e.name in HOST_SPANS:
                        td.host.append((e.name, e.start_ns * 1e-9,
                                        (e.start_ns + e.duration_ns) * 1e-9))
    td.host.sort(key=lambda iv: iv[1])
    return td


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                   float]]:
    """Merged, sorted union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def leaves(ops: Sequence[Interval]) -> List[Interval]:
    """The operations that hold no other: a ``while`` or a call whose
    interval encloses the operations of its body is left out, so that no
    time counts twice."""
    evs = sorted(ops, key=lambda iv: (iv[1], -iv[2]))
    return [ev for ev, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[1] >= ev[2]]


def clock_offset(modules: Sequence[Interval], host: Sequence[Interval],
                 module_name: str, span: str = "bench.decode") -> float:
    """Seconds to add to device times to put them on the host clock:
    the median gap between the end of each decode span and the end of the
    decode program it waited for (matched in order).  0 where the counts
    differ or nothing matches."""
    mods = [m for m in modules if m[0].startswith(module_name + "(")]
    spans = [h for h in host if h[0] == span]
    if not mods or len(mods) != len(spans):
        return 0.0
    return median(h[2] - m[2] for m, h in zip(mods, spans))


def innermost(host: Sequence[Interval], t: float) -> str:
    """Name of the innermost benchmark span open at host time ``t``."""
    best, width = "outside bench spans", float("inf")
    for name, s, e in host:
        if s <= t < e and name != WINDOW_SPAN and e - s < width:
            best, width = name.replace("bench.", ""), e - s
    return best


def module_prefix(fn_name: str) -> str:
    """Name XLA gives the program of a jitted function: ``jit_`` plus the
    function's name with every character outside [A-Za-z0-9_] as ``_``
    (``<lambda>`` → ``jit__lambda``)."""
    return "jit_" + re.sub(r"[^A-Za-z0-9_]", "_", fn_name).rstrip("_")


@dataclass
class Reduced:
    window_s: float
    busy_s: float                     # mean over chips
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    decode_device_s: float            # device time of the decode programs
    decode_programs: float            # and their count (mean over chips)


def reduce(td: TraceData, decode_module: Optional[str] = None,
           top: int = 10) -> Reduced:
    """Busy time, top ops, idle gaps by host span, and the device time
    and count of the decode programs (found by their module name, so a
    lost host span does not lose them), all inside the window span."""
    wins = [h for h in td.host if h[0] == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    _, w0, w1 = wins[0]
    if not td.ops:
        raise ValueError("no TPU plane with XLA Ops in the trace")
    busy_total = 0.0
    per_op: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    dec_s = dec_n = 0.0
    for plane, ops in td.ops.items():
        mods = td.modules.get(plane, [])
        off = (clock_offset(mods, td.host, decode_module)
               if decode_module else 0.0)
        lo, hi = w0 - off, w1 - off           # the window on device time
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for name, s, e in leaves(ops):
            if e > lo and s < hi:
                per_op[op_label(name)] += min(e, hi) - max(s, lo)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[innermost(td.host, (a + b) / 2 + off)] += b - a
        for name, s, e in mods:
            if (decode_module and name.startswith(decode_module + "(")
                    and lo <= (s + e) / 2 < hi):
                dec_s += e - s
                dec_n += 1
    n = len(td.ops)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(window_s=w1 - w0, busy_s=busy_total / n,
                   top_ops=[(k, v / n) for k, v in rank(per_op)],
                   idle_gaps=[(k, v / n) for k, v in rank(gaps)],
                   decode_device_s=dec_s / n, decode_programs=dec_n / n)
