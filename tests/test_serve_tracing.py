"""The serve loop's own spans and counters (serving/router.py,
serving/scheduler.py, core/tracecount.py).

A one-replica router over a reduced granite model is driven through a
short arrival trace under a profiler trace (Python tracer off, so only
the ``TraceAnnotation`` spans land on the host plane).  The tests read
the ``serve.*`` spans back from the trace and check them, and the
serve-loop counters, against what the loop did: one ``serve.tick`` per
tick with its children in the order the work runs, one decode step per
``serve.decode`` in order, and the counters exact."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core import tracecount
from repro.serving.router import Router
from repro.serving.scheduler import Request

SLOTS, CAP, MAX_SEQ = 2, 8, 32
# (arrival tick, prompt length, max_new): two admits at tick 0, one
# request waiting for a free slot, one arriving later
TRACE = [(0, 5, 3), (0, 3, 4), (0, 6, 2), (4, 2, 3)]


def _spans(path):
    """``(name, start_ns, end_ns, stats)`` of the ``serve.*`` spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_test_mesh
    from repro.launch.serve import EngineOptions, build_replicas
    cfg = reduced(get_config("granite-8b"))
    (eng,) = build_replicas(cfg, make_test_mesh(data=1, model=1),
                            n_replicas=1, max_seq=MAX_SEQ,
                            batch_global=SLOTS,
                            options=EngineOptions(backend="xla"))
    router = Router([eng], prompt_cap=CAP, max_new_cap=8)
    rng = np.random.default_rng(0)
    reqs = [(t, Request(rid, rng.integers(1, cfg.vocab_size, n).tolist(),
                        new)) for rid, (t, n, new) in enumerate(TRACE)]
    # compile admit, decode and retire outside the traced run
    router.run([(0, Request(99, [1, 2, 3], 2))])
    sched = router.replicas[0].sched
    tick0, step0 = router.tick, sched.decode_calls
    tracecount.reset_serve()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tdir = str(tmp_path_factory.mktemp("serve_trace"))
    jax.profiler.start_trace(tdir, profiler_options=opts)
    router.run([(tick0 + t, r) for t, r in reqs])
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                        recursive=True)
    return {"router": router, "sched": sched, "spans": _spans(path),
            "ticks": router.tick - tick0,
            "decodes": sched.decode_calls - step0, "step0": step0,
            "admits": sum(1 for t, kind, _, _ in sched.events
                          if kind == "admit" and t >= tick0),
            "admit_ticks": len({t for t, kind, _, _ in sched.events
                                if kind == "admit" and t >= tick0}),
            "totals": tracecount.serve_totals()}


def _children(spans, parent):
    """The spans directly inside ``parent``, in order."""
    _, s0, e0, _ = parent
    inside = [s for s in spans if s is not parent and s0 <= s[1]
              and s[2] <= e0]
    return [s for s in inside
            if not any(o is not s and o[1] <= s[1] and s[2] <= o[2]
                       for o in inside)]


def test_one_tick_span_per_tick_holding_its_children_in_order(served):
    spans = served["spans"]
    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert len(ticks) == served["ticks"]
    assert [s[3]["tick"] for s in ticks] == list(
        range(ticks[0][3]["tick"], ticks[0][3]["tick"] + len(ticks)))
    admitted = 0
    for t in ticks:
        names = [c[0] for c in _children(spans, t)]
        if "serve.admit" in names:
            admitted += 1
        order = ["serve.dispatch", "serve.admit", "serve.decode",
                 "serve.latch", "serve.retire", "serve.probe",
                 "serve.commit"]
        assert names == [n for n in order if n in names], names
        for n in ("serve.dispatch", "serve.probe", "serve.commit"):
            assert names.count(n) == 1, names
    assert admitted == served["admit_ticks"]
    waits = {"serve.admit": "serve.admit.wait",
             "serve.decode": "serve.decode.wait"}
    for s in spans:
        if s[0] in waits:
            assert [c[0] for c in _children(spans, s)] == [waits[s[0]]]


def test_decode_spans_carry_rising_steps(served):
    steps = [s[3]["step"] for s in served["spans"]
             if s[0] == "serve.decode"]
    assert steps == list(range(served["step0"],
                               served["step0"] + served["decodes"]))
    assert served["decodes"] > 0


def test_admit_spans_name_their_requests(served):
    rids = [s[3]["rids"] for s in served["spans"]
            if s[0] == "serve.admit"]
    got = sorted(int(r) for x in rids for r in x.strip("[]").split(","))
    # the scheduler's own ids: 0 went to the warm-up request
    assert got == list(range(1, len(TRACE) + 1))


def test_serve_counters_are_exact(served):
    c = served["totals"]
    ticks, decodes = served["ticks"], served["decodes"]
    admits = served["admit_ticks"]
    assert decodes < ticks       # a tick with no slot busy still probes
    # per tick the probe's cache_lens; per decode the next tokens and
    # the latch's cache_lens; per admit the first tokens
    syncs = ticks + 2 * decodes + admits
    assert c["host_syncs"] == syncs
    assert c["host_sync_bytes"] == 4 * SLOTS * syncs
    assert c["admit_positions"] == admits * SLOTS * CAP
    assert c["admit_rows"] == sum(n for _, n, _ in TRACE)
    assert served["admits"] == len(TRACE)
