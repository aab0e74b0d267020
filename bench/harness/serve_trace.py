"""Reduction of the served path's own spans and named kernels in a JAX
profiler trace.  It adds to ``harness/trace.py`` and changes none of the
numbers that module gives.

What is read, beside what ``trace.py`` reads:

* on the host plane, the ``serve.*`` spans that ``serving/router.py`` and
  ``serving/scheduler.py`` open, with their stats (``tick``, ``step``);
* on each TPU plane, each op's ``op_name`` path, which the trace keeps in
  the ``tf_op`` stat of the op's metadata (``ProfileData`` does not show
  metadata stats, so :func:`op_paths` reads them from the file).  A
  custom call runs the Pallas kernel named by its instruction
  (``%fused_ffn.7``) or, where ``vmap`` turned a per-slot kernel into a
  loop of calls (``%closed_call.13``), by the ``vmap(<kernel>)`` in its
  path.  Every other op is labelled with the innermost named scope of
  ``serving/engine.decode_step`` in its path (:data:`SCOPES`), or the
  ``vmap(<kernel>)`` loop it belongs to: where the XLA glue comes from.

The clock: a ``serve.decode`` span carries the scheduler's decode step,
and the ``k``-th decode program in the trace is step ``step0 + k + s``
(``step0``: the step at which the trace started; ``s``: the programs the
device trace lost before it, found as the nearest ``s`` at which the
program lies inside its span).  So a lost span costs one pair, and lost
programs do not shift the pairs after them.  The offset is the median
over the pairs of the span's end less the program's end (the span ends
once the program's tokens are on the host).
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from statistics import median, quantiles
from typing import Dict, List, Optional, Sequence, Tuple

from .trace import WINDOW_SPAN, Interval, TraceData, clip, leaves, union

KERNELS = ("fused_decode", "fused_ffn", "fused_head", "fused_mla_decode",
           "flash_decode", "rglru_scan", "rwkv6_scan")
SCOPES = ("embed", "layers", "kv_read", "kv_write", "head")
OUTSIDE = "outside serve spans"
PATH_STAT = "tf_op"           # the metadata stat that holds the op_name
SLACK_S = 0.01                # device clock error a pair may show
REACH = 64                    # most programs lost in a row that re-sync
_INST = re.compile(r"^%([\w\-]+?)(?:\.\d+)? = ")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_KERNEL = re.compile(r"(?:^|/)(?:vmap\((K)\)(?=[/:]|$)|(K)/pallas_call)"
                     .replace("K", "|".join(KERNELS)))
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r"|vmap\((?:" +
                    "|".join(KERNELS) + r")\))(?=[/:]|$)")

Span = Tuple[str, float, float, dict]     # (name, start_s, end_s, stats)


@dataclass
class ServeTraceData:
    """What :func:`from_profile` reads beyond :class:`trace.TraceData`."""
    spans: List[Span] = field(default_factory=list)
    paths: Dict[str, Dict[str, str]] = field(default_factory=dict)


def label_of(name: str, path: str) -> str:
    """What a leaf op of a decode program is: the kernel of KERNELS a
    custom call runs, or the :func:`scope_of` an XLA op, or ``other``."""
    return ((" custom-call(" in name and kernel_of(name, path))
            or scope_of(path) or "other")


def kernel_of(name: str, path: str) -> Optional[str]:
    """The Pallas kernel a custom call runs: its instruction's name, or
    the innermost ``vmap(<kernel>)`` or ``<kernel>/pallas_call`` of its
    ``op_name`` path.  None for a call to another target (XLA's own
    ``AllocateBuffer`` calls sit in the same loops)."""
    target = _TARGET.search(name)
    if target and target.group(1) != "tpu_custom_call":
        return None
    m = _INST.match(name)
    if m and m.group(1) in KERNELS:
        return m.group(1)
    hits = [a or b for a, b in _KERNEL.findall(path)]
    return hits[-1] if hits else None


def scope_of(path: str) -> Optional[str]:
    """The innermost of :data:`SCOPES` or ``vmap(<kernel>)`` in an op's
    ``op_name`` path."""
    hits = _SCOPE.findall(path)
    return hits[-1] if hits else None


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf, lo: int = 0, hi: Optional[int] = None):
    """``(field number, value)`` of the protobuf message in
    ``buf[lo:hi]``: an int for a varint, ``(start, end)`` for a
    length-delimited field; fixed-width fields are skipped."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif kind == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")


def op_paths(buf) -> Dict[str, Dict[str, str]]:
    """Each op's :data:`PATH_STAT` in the TPU planes of a serialized
    ``XSpace``, by plane and op name.  Only the planes' metadata (one
    entry per op of each program) is parsed; the events
    (``XPlane.lines``) are skipped unread."""
    out: Dict[str, Dict[str, str]] = {}
    text = lambda ab: bytes(buf[ab[0]:ab[1]]).decode("utf-8", "replace")
    entry = lambda ab: dict(_fields(buf, *ab)).get(2, (0, 0))  # map value
    for f, plane in _fields(buf):
        if f != 1:                                   # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:                               # XPlane.name
                name = text(v)
            elif g == 4:                             # event_metadata
                metas.append(entry(v))
            elif g == 5:                             # stat_metadata
                sm = dict(_fields(buf, *entry(v)))
                stat_names[sm.get(1, 0)] = text(sm.get(2, (0, 0)))
        if not name.startswith("/device:TPU:"):
            continue
        key = next((k for k, n in stat_names.items() if n == PATH_STAT),
                   None)
        paths: Dict[str, str] = {}
        for m in metas:
            op = path = ""
            for g, v in _fields(buf, *m):
                if g == 2:                           # XEventMetadata.name
                    op = text(v)
                elif g == 5:                         # XEventMetadata.stats
                    st = dict(_fields(buf, *v))
                    if st.get(1) != key:
                        continue
                    if 5 in st:                      # str_value
                        path = text(st[5])
                    elif 7 in st:                    # ref_value: interned
                        path = stat_names.get(st[7], "")
            paths[op] = path
        out[name] = paths
    return out


def from_profile(pd, paths: Dict[str, Dict[str, str]]) -> ServeTraceData:
    """The host plane's ``serve.*`` spans, with ``paths`` (:func:`op_paths`
    of the same trace)."""
    out = ServeTraceData(paths=paths)
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9,
                                      dict(e.stats)))
    out.spans.sort(key=lambda s: (s[1], -s[2]))
    return out


def load(path: str) -> Tuple[TraceData, ServeTraceData]:
    """Both reductions' inputs from one parse of ``path``."""
    from jax.profiler import ProfileData

    from .trace import from_profile as trace_from_profile
    pd = ProfileData.from_file(path)
    with open(path, "rb") as f:
        paths = op_paths(memoryview(f.read()))
    return trace_from_profile(pd), from_profile(pd, paths)


def decode_pairs(mods: Sequence[Interval], spans: Sequence[Span],
                 step0: int) -> List[Tuple[Interval, Span]]:
    """``(program, serve.decode span)`` pairs: the ``k``-th program with
    the span of step ``step0 + k + s``, ``s`` kept from the last pair and
    moved to the nearest value (within :data:`REACH`) at which the
    program lies inside its span, give or take :data:`SLACK_S`."""
    by_step = {int(sp[3]["step"]): sp for sp in spans
               if sp[0] == "serve.decode" and "step" in sp[3]}
    tries = sorted(range(-REACH, REACH + 1), key=abs)
    out, s = [], 0
    for k, m in enumerate(mods):
        for d in tries:
            sp = by_step.get(step0 + k + s + d)
            if sp and sp[1] - SLACK_S <= m[1] and m[2] <= sp[2] + SLACK_S:
                s += d
                out.append((m, sp))
                break
    return out


def labelled(spans: Sequence[Span], lo: float, hi: float
             ) -> List[Tuple[float, float, str]]:
    """``[lo, hi)`` cut into pieces, each labelled with the innermost
    ``serve.*`` span open over it (or :data:`OUTSIDE`): one sweep over
    the spans sorted by start."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []          # (name, end)
    t = lo

    def upto(x: float) -> None:
        nonlocal t
        x = min(x, hi)
        if x > t:
            out.append((t, x, stack[-1][0] if stack else OUTSIDE))
            t = x

    for name, s, e, _ in spans:
        if e <= lo or s >= hi:
            continue
        while stack and stack[-1][1] <= s:
            upto(stack[-1][1])
            stack.pop()
        upto(s)
        stack.append((name, e))
    while stack:
        upto(stack[-1][1])
        stack.pop()
    upto(hi)
    return out


def overlap_by_label(gaps: Sequence[Tuple[float, float]],
                     pieces: Sequence[Tuple[float, float, str]]
                     ) -> Dict[str, float]:
    """Seconds of the sorted, disjoint ``gaps`` under each label of the
    sorted, disjoint ``pieces`` (one merge of the two lists)."""
    out: Dict[str, float] = defaultdict(float)
    i = j = 0
    while i < len(gaps) and j < len(pieces):
        a, b = gaps[i]
        s, e, label = pieces[j]
        lo, hi = max(a, s), min(b, e)
        if hi > lo:
            out[label] += hi - lo
        if b <= e:
            i += 1
        else:
            j += 1
    return dict(out)


@dataclass
class ServeReduced:
    decode_kernels: Dict[str, float]   # device s per named kernel
    decode_other_s: float              # the decode programs' other leaves
    decode_other_by_scope: Dict[str, float]   # the same by scope_of
    decode_programs: float             # decode programs in the window
    dispatch_s: Optional[float]        # mean: decode call → program start
    commit_lag_s: Optional[float]      # mean: decode.wait end → commit end
    idle_by_span: Dict[str, float]     # device idle s by innermost span
    clock: dict                        # offset_s, spread_s, pairs, lost


def reduce(td: TraceData, st: ServeTraceData, decode_module: str,
           step0: int) -> ServeReduced:
    """Per-kernel and other leaf time inside the window's decode
    programs, decode dispatch, commit lag and device idle time by the
    innermost ``serve.*`` span, on the clock the decode pairs give."""
    wins = [h for h in td.host if h[0] == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    _, w0, w1 = wins[0]
    kern: Dict[str, float] = defaultdict(float)
    by_scope: Dict[str, float] = defaultdict(float)
    n_prog = 0.0
    idle: Dict[str, float] = defaultdict(float)
    offsets: List[float] = []
    dispatch: List[float] = []
    lost = 0
    for plane, ops in td.ops.items():
        mods = sorted((m for m in td.modules.get(plane, [])
                       if m[0].startswith(decode_module + "(")),
                      key=lambda m: m[1])
        pairs = decode_pairs(mods, st.spans, step0)
        lost += len(mods) - len(pairs)
        offs = [sp[2] - m[2] for m, sp in pairs]
        off = median(offs) if offs else 0.0
        offsets += offs
        dispatch += [m[1] + off - sp[1] for m, sp in pairs
                     if w0 <= sp[1] < w1]
        lo, hi = w0 - off, w1 - off           # the window on device time
        paths = st.paths.get(plane, {})
        known: Dict[str, str] = {}            # op name → label_of
        in_win = [m for m in mods if lo <= (m[1] + m[2]) / 2 < hi]
        n_prog += len(in_win)
        j = 0
        for ev in leaves(ops):
            mid = (ev[1] + ev[2]) / 2
            while j < len(in_win) and in_win[j][2] <= mid:
                j += 1
            if j == len(in_win) or mid < in_win[j][1]:
                continue                      # outside the decode programs
            label = known.get(ev[0])
            if label is None:
                label = known[ev[0]] = label_of(ev[0], paths.get(ev[0], ""))
            (kern if label in KERNELS else by_scope)[label] += ev[2] - ev[1]
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(a + off, b + off) for a, b in zip(edges[0::2], edges[1::2])
                if b > a]
        for label, s in overlap_by_label(
                gaps, labelled(st.spans, w0, w1)).items():
            idle[label] += s
    n = max(len(td.ops), 1)
    rank = lambda d: {k: v / n for k, v in sorted(d.items(),
                                                  key=lambda kv: -kv[1])}
    lags, wait_end = [], None
    for name, s, e, _ in st.spans:
        if not w0 <= s < w1:
            continue
        if name == "serve.tick":
            wait_end = None
        elif name == "serve.decode.wait":
            wait_end = e
        elif name == "serve.commit" and wait_end is not None:
            lags.append(e - wait_end)
    q = quantiles(offsets, n=4) if len(offsets) > 1 else [0.0, 0.0, 0.0]
    mean = lambda xs: sum(xs) / len(xs) if xs else None
    return ServeReduced(
        decode_kernels=rank(kern),
        decode_other_s=sum(by_scope.values()) / n,
        decode_other_by_scope=rank(by_scope), decode_programs=n_prog / n,
        dispatch_s=mean(dispatch), commit_lag_s=mean(lags),
        idle_by_span=rank(idle),
        clock={"offset_s": median(offsets) if offsets else 0.0,
               "spread_s": q[2] - q[0], "pairs": len(offsets),
               "lost": lost})
