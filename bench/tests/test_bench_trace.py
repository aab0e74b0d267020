"""The trace reduction on a small constructed ``.xplane.pb``: busy
union and idle share, top device ops, decode program time, and
idle gaps attributed to the benchmark span open during them."""
from __future__ import annotations

import pytest

from bench.harness import trace as TR

MS = 1_000_000_000        # picoseconds per millisecond


def _events(evs):
    return "\n".join(f"    events {{ metadata_id: {m} offset_ps: {int(s * MS)}"
                     f" duration_ps: {int(d * MS)} }}" for m, s, d in evs)


def _meta(names):
    return "\n".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: '
        f'"{n}" }} }}' for i, n in names.items())


def constructed(tmp_path) -> str:
    """Device clock 1 ms behind the host.  Host (ms): window [0, 100];
    tick [2, 45] holds decode [10.5, 41]; tick [46, 80] holds admit
    [47, 58] and decode [60.5, 71].  Device (ms): a ``while`` op
    [10, 40] enclosing ops A [10, 25] and B [25, 40], then C [60, 70];
    decode programs [10, 40] and [60, 70], each ending 1 ms (host)
    before its span."""
    dev_names = {1: "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), "
                    "kind=kLoop",
                 2: "%closed_call.25 = f32[8]{0} custom-call(f32[8]{0} %q)",
                 3: "%copy.5 = bf16[8]{0} copy(bf16[8]{0} %r)",
                 4: "jit_dec_body(123)",
                 5: "%while.41 = (s32[]) while((s32[]) %t), body=%b"}
    host_names = {1: "bench.window", 2: "bench.tick", 3: "bench.decode",
                  4: "bench.admit", 5: "other"}
    # host origin 0; the device line starts 1 ms earlier on the same
    # origin: device t maps to host t + 1
    txt = f'''
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
{_events([(5, 10, 30), (1, 10, 15), (2, 25, 15), (3, 60, 10)])}
  }}
  lines {{
    id: 2
    name: "XLA Modules"
    timestamp_ns: 0
{_events([(4, 10, 30), (4, 60, 10)])}
  }}
{_meta({k: v.replace('"', '') for k, v in dev_names.items()})}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{
    id: 1
    name: "python"
    timestamp_ns: 0
{_events([(1, 0, 100), (2, 2, 43), (3, 10.5, 30.5), (2, 46, 34),
          (4, 47, 11), (3, 60.5, 10.5), (5, 85, 5)])}
  }}
{_meta(host_names)}
}}
'''
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(txt))
    return str(path)


def test_constructed_trace_reduces(tmp_path):
    td = TR.load(constructed(tmp_path))
    # the device is 1 ms behind: offset 1 ms from the decode spans
    off = TR.clock_offset(td.modules["/device:TPU:0"], td.host,
                          "jit_dec_body")
    assert off == pytest.approx(1e-3)
    r = TR.reduce(td, decode_module="jit_dec_body")
    assert r.window_s == pytest.approx(0.100)
    # union of while ∪ A ∪ B ∪ C = [10, 40] ∪ [60, 70] = 40 ms
    assert r.busy_s == pytest.approx(0.040)
    assert 1 - r.busy_s / r.window_s == pytest.approx(0.6)
    ops = dict(r.top_ops)
    # the enclosing while is not counted on top of its body
    assert ops == pytest.approx({"fusion.1 (fusion)": 0.015,
                                 "closed_call.25 (custom-call)": 0.015,
                                 "copy.5 (copy)": 0.010})
    # gaps on the device window [-1, 99]: [-1, 10] in the first tick,
    # [40, 60] while the admit span is open, [70, 99] outside any tick
    assert dict(r.idle_gaps) == pytest.approx(
        {"outside bench spans": 0.029, "admit": 0.020, "tick": 0.011})
    assert [k for k, _ in r.idle_gaps] == ["outside bench spans", "admit",
                                           "tick"]
    assert r.decode_device_s == pytest.approx(0.040)
    assert r.decode_programs == 2


def test_decode_programs_survive_a_lost_host_span(tmp_path):
    """A decode span missing from the host line leaves the clock offset
    unknown (0), and the decode programs are still all counted."""
    td = TR.load(constructed(tmp_path))
    td.host = [h for h in td.host
               if not (h[0] == "bench.decode" and h[1] > 0.05)]
    r = TR.reduce(td, decode_module="jit_dec_body")
    assert r.decode_device_s == pytest.approx(0.040)
    assert r.decode_programs == 2


def test_union_and_clip():
    assert TR.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert TR.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]


def test_op_label_and_module_prefix():
    assert TR.op_label("%copy-start = (bf16[4]{0}, u32[]{:S(2)}) "
                       "copy-start(bf16[4]{0} %w)") == "copy-start (copy-start)"
    assert TR.op_label("no hlo text") == "no hlo text"
    assert TR.module_prefix("<lambda>") == "jit__lambda"
    assert TR.module_prefix("dec_body") == "jit_dec_body"


def test_no_window_span_is_an_error(tmp_path):
    td = TR.load(constructed(tmp_path))
    td.host = [h for h in td.host if h[0] != TR.WINDOW_SPAN]
    with pytest.raises(ValueError):
        TR.reduce(td)
