"""The reduction of the served path's own spans and named kernels
(``harness/serve_trace.py``) on a small constructed ``.xplane.pb``:
the decode-step clock (with a lost span), per-kernel and other leaf time
inside the decode programs, decode dispatch, commit lag and device idle
time by the innermost ``serve.*`` span; and the new metrics' readers."""
from __future__ import annotations

import pytest

from bench.harness import serve_trace as ST
from bench.harness import spec
from bench.harness import trace as TR
from test_bench_trace import constructed as constructed_bench

MS = 1_000_000_000        # picoseconds per millisecond
TF_OP, PATH = 1, 2        # the device plane's stat keys: tf_op, and the
                          # interned op_name path it refers to
TICK, STEP = 1, 2         # the host plane's stat keys


def _events(evs):
    out = []
    for m, s, d, *stats in evs:
        st = "".join(f" stats {{ metadata_id: {k} {kind}: {v} }}"
                     for k, kind, v in stats)
        out.append(f"    events {{ metadata_id: {m} offset_ps: "
                   f"{int(s * MS)} duration_ps: {int(d * MS)}{st} }}")
    return "\n".join(out)


def _meta(kind, names, stats=None):
    stats = stats or {}
    return "\n".join(f'  {kind} {{ key: {i} value {{ id: {i} name: "{n}"'
                     f'{stats.get(i, "")} }} }}' for i, n in names.items())


def constructed(tmp_path, lose_step=None, lose_first_program=False) -> str:
    """Device clock 1 ms behind the host (1.2 ms at the second step).

    Device (ms): decode programs [10, 40], [60, 70], [80, 90]; an admit
    program [47, 56].  Leaf ops: in the first, ``closed_call`` running
    ``vmap(fused_decode)`` [10, 25] (named only by the ``tf_op`` stat of
    its metadata, an interned string, as on the chip), a fusion in the
    ``kv_read`` scope [25, 33] and ``fused_ffn.3`` [33, 40], under a
    ``while`` [10, 40];
    then ``closed_call`` [60, 64], a copy [64, 67], ``fused_head.2``
    [67, 70]; then ``closed_call`` [80, 85], a copy [85, 90].

    Host (ms): window [0, 100]; three ``serve.tick`` spans, each holding
    dispatch, (admit and its wait,) decode (steps 5, 6, 7) and its wait,
    latch, probe and commit."""
    vmap_path = "jit(dec_body)/vmap(fused_decode)/while/body/closed_call"
    kv_path = "jit(dec_body)/layers/while/body/kv_read/dynamic_slice"
    dev = {1: "%while.41 = (s32[]) while((s32[]) %t), body=%b",
           2: "%closed_call.25 = f32[8]{0} custom-call(f32[8]{0} %q)",
           3: "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop",
           4: "%fused_ffn.3 = bf16[8]{0} custom-call(bf16[8]{0} %x)",
           5: "%copy.5 = bf16[8]{0} copy(bf16[8]{0} %r)",
           6: "%fused_head.2 = f32[8]{0} custom-call(bf16[8]{0} %y)",
           7: "%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %z), kind=kLoop",
           8: "jit_dec_body(123)", 9: "jit_admit(7)"}
    ops = [(1, 10, 30), (2, 10, 15), (3, 25, 8), (4, 33, 7),
           (7, 47, 9), (2, 60, 4), (5, 64, 3), (6, 67, 3),
           (2, 80, 5), (5, 85, 5)]
    mods = [(8, 10, 30), (9, 47, 9), (8, 60, 10), (8, 80, 10)]
    if lose_first_program:             # the device trace missed step 5
        mods = mods[1:]
    names = ["bench.window", "serve.tick", "serve.dispatch", "serve.admit",
             "serve.admit.wait", "serve.decode", "serve.decode.wait",
             "serve.latch", "serve.probe", "serve.commit"]
    h = {n: i + 1 for i, n in enumerate(names)}

    def tick(i, s, d):
        return (h["serve.tick"], s, d, (TICK, "int64_value", i))

    def decode(step, s, d):
        return (h["serve.decode"], s, d, (TICK, "int64_value", step - 5),
                (STEP, "int64_value", step))

    host = [(h["bench.window"], 0, 100),
            tick(0, 2, 43), (h["serve.dispatch"], 2, 1), decode(5, 8, 33),
            (h["serve.decode.wait"], 20, 21), (h["serve.latch"], 41.5, 0.5),
            (h["serve.probe"], 42, 1), (h["serve.commit"], 43, 1.5),
            tick(1, 46, 34), (h["serve.dispatch"], 46, 0.5),
            (h["serve.admit"], 47, 11), (h["serve.admit.wait"], 50, 8),
            decode(6, 59, 12.2), (h["serve.decode.wait"], 65, 6.2),
            (h["serve.latch"], 71.5, 0.5), (h["serve.probe"], 72, 1),
            (h["serve.commit"], 73, 1),
            tick(2, 80, 15), (h["serve.dispatch"], 80, 0.5),
            decode(7, 80.5, 10.5), (h["serve.decode.wait"], 85, 6),
            (h["serve.latch"], 91, 0.5), (h["serve.probe"], 91.5, 0.5),
            (h["serve.commit"], 92, 1)]
    if lose_step is not None:
        host = [e for e in host if not (e[0] == h["serve.decode"]
                                        and e[-1][2] == lose_step)]
    txt = f'''
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{_events(ops)}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
{_events(mods)}
  }}
{_meta("event_metadata", dev,
       {2: f" stats {{ metadata_id: {TF_OP} ref_value: {PATH} }}",
        3: f' stats {{ metadata_id: {TF_OP} str_value: "{kv_path}" }}'})}
{_meta("stat_metadata", {TF_OP: "tf_op", PATH: vmap_path})}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{_events(host)}
  }}
{_meta("event_metadata", {i + 1: n for i, n in enumerate(names)})}
{_meta("stat_metadata", {TICK: "tick", STEP: "step"})}
}}
'''
    from jax.profiler import ProfileData
    path = tmp_path / "serve.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(txt))
    return str(path)


def _reduce(path):
    td, st = ST.load(path)
    return ST.reduce(td, st, "jit_dec_body", step0=5)


def test_serve_spans_and_named_kernels_reduce(tmp_path):
    r = _reduce(constructed(tmp_path))
    # offsets 1.0, 1.2, 1.0 ms: the median, and the quartiles' distance
    assert r.clock["offset_s"] == pytest.approx(1e-3)
    assert r.clock["spread_s"] == pytest.approx(0.2e-3)
    assert (r.clock["pairs"], r.clock["lost"]) == (3, 0)
    # the while is not counted over its body; the admit program is not
    # a decode program
    assert r.decode_kernels == pytest.approx(
        {"fused_decode": 0.024, "fused_ffn": 0.007, "fused_head": 0.003})
    assert list(r.decode_kernels) == ["fused_decode", "fused_ffn",
                                      "fused_head"]
    assert r.decode_other_s == pytest.approx(0.016)
    assert r.decode_other_by_scope == pytest.approx({"kv_read": 0.008,
                                                     "other": 0.008})
    assert r.decode_programs == 3
    # program start + offset − span start: 3, 2, 0.5 ms
    assert r.dispatch_s == pytest.approx(5.5e-3 / 3)
    # commit end − decode.wait end: 3.5, 2.8, 2.0 ms
    assert r.commit_lag_s == pytest.approx(8.3e-3 / 3)
    assert r.idle_by_span == pytest.approx({
        "serve.tick": 0.0158, "outside serve spans": 0.008,
        "serve.decode": 0.0055, "serve.commit": 0.0035,
        "serve.probe": 0.0025, "serve.dispatch": 0.002,
        "serve.latch": 0.0015, "serve.admit": 0.001,
        "serve.admit.wait": 0.001, "serve.decode.wait": 0.0002})
    # every idle second of the window is placed, and only once
    td, _ = ST.load(constructed(tmp_path))
    rb = TR.reduce(td, decode_module="jit_dec_body")
    assert sum(r.idle_by_span.values()) == pytest.approx(
        rb.window_s - rb.busy_s)
    # both reductions see the same decode programs
    assert rb.decode_programs == r.decode_programs
    assert rb.decode_device_s == pytest.approx(
        sum(r.decode_kernels.values()) + r.decode_other_s)


def test_a_lost_decode_span_costs_one_pair(tmp_path):
    r = _reduce(constructed(tmp_path, lose_step=6))
    assert (r.clock["pairs"], r.clock["lost"]) == (2, 1)
    assert r.clock["offset_s"] == pytest.approx(1e-3)
    assert r.decode_kernels == pytest.approx(
        {"fused_decode": 0.024, "fused_ffn": 0.007, "fused_head": 0.003})
    assert r.dispatch_s == pytest.approx(3.5e-3 / 2)
    # the lost span's wait still closes its tick's commit lag
    assert r.commit_lag_s == pytest.approx(8.3e-3 / 3)


def test_lost_programs_do_not_shift_the_pairs(tmp_path):
    """The device trace lost step 5's program: the first program in the
    trace is step 6, found by where it lies, and the pairs stay true."""
    r = _reduce(constructed(tmp_path, lose_first_program=True))
    assert (r.clock["pairs"], r.clock["lost"]) == (2, 0)
    assert r.clock["offset_s"] == pytest.approx(1.1e-3)
    assert r.dispatch_s == pytest.approx((61.1 - 59 + 81.1 - 80.5) / 2e3)
    assert r.decode_programs == 2


def test_a_trace_without_serve_spans_reads_nothing(tmp_path):
    """A program without the serve spans and kernel names (the trace of
    ``test_bench_trace``): nothing is matched, nothing is named, and
    every idle second lies outside the serve spans."""
    td, st = ST.load(constructed_bench(tmp_path))
    r = ST.reduce(td, st, "jit_dec_body", step0=0)
    assert r.decode_kernels == {}
    assert list(r.decode_other_by_scope) == ["other"]
    assert r.dispatch_s is None and r.commit_lag_s is None
    assert (r.clock["pairs"], r.clock["lost"]) == (0, 2)
    assert list(r.idle_by_span) == [ST.OUTSIDE]

    class Run:
        serve_trace, serve_counts, ticks = r, None, 10
    assert spec.read_metrics([{"name": n, "unit": "x"} for n in METRICS],
                             Run()) == {}


@pytest.mark.parametrize("name, op, path", [
    ("fused_ffn", "%fused_ffn.7 = bf16[8]{0} custom-call(bf16[8]{0} %x)",
     "jit(f)/layers/while/body/closed_call/fused_ffn/pallas_call:"),
    ("fused_decode", "%closed_call.13 = f32[8]{0} custom-call(f32[8]{0} %x)",
     "jit(f)/layers/while/body/closed_call/vmap(fused_decode)/while/body/"
     "closed_call:"),
    ("fused_mla_decode", "%closed_call.2 = f32[] custom-call()",
     "jit(f)/fused_mla_decode/pallas_call:"),
    (None, "%closed_call.3 = f32[] custom-call()",
     "jit(f)/vmap(kernel)/while/body/closed_call:"),
    (None, "%closed_call.4 = f32[] custom-call()",
     "jit(f)/fused_decode_x/pallas_call:"),
    (None, '%custom-call.36 = f32[8]{0} custom-call(), '
           'custom_call_target="AllocateBuffer"',
     "jit(f)/vmap(fused_decode)/while/body/closed_call:"),
])
def test_kernel_of(name, op, path):
    """A custom call's kernel, from its instruction name or the
    innermost kernel in its ``op_name`` path."""
    assert ST.kernel_of(op, path) == name


METRICS = ("decode_kernel_ms", "decode_xla_ms", "decode_dispatch_ms",
           "commit_lag_ms", "host_syncs_per_tick", "admit_pad_share")


def test_new_readers(tmp_path):
    from collections import Counter
    r = _reduce(constructed(tmp_path))

    class Run:
        serve_trace, ticks = r, 4
        serve_counts = Counter(host_syncs=13, admit_rows=256,
                               admit_positions=8 * 1024)
    got = spec.read_metrics([{"name": n, "unit": "x"} for n in METRICS],
                            Run())
    assert {k: v["value"] for k, v in got.items()} == pytest.approx({
        "decode_kernel_ms": 34 / 3, "decode_xla_ms": 16 / 3,
        "decode_dispatch_ms": 5.5 / 3, "commit_lag_ms": 8.3 / 3,
        "host_syncs_per_tick": 3.25, "admit_pad_share": 96.875})

    class Bare:                       # a run of the harness as it stands
        ticks = 4
    assert spec.read_metrics([{"name": n, "unit": "x"} for n in METRICS],
                             Bare()) == {}


@pytest.mark.parametrize("scope, path", [
    ("kv_read", "jit(f)/shard_map/layers/while/body/kv_read/dynamic_slice"),
    ("layers", "jit(f)/shard_map/layers/while/body/dynamic_slice"),
    ("vmap(fused_decode)", "jit(f)/layers/while/body/vmap(fused_decode)/"
                           "while/body/closed_call/dynamic_slice"),
    ("head", "jit(f)/head/sort"),
    (None, "jit(f)/embedding/gather"),
])
def test_scope_of(scope, path):
    assert ST.scope_of(path) == scope


def test_traced_cell_without_a_tpu_fails_without_a_result():
    import os
    import subprocess
    import sys

    from bench_tiny import ROOT
    p = subprocess.run(
        [sys.executable, "bench/tools/traced_cell.py", "--workload",
         "granite-chat", "--seed", "3000000001", "--seconds", "1"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
