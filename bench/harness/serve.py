"""One run of one cell: build the served path, warm it up, drive it open
loop for the window, then compare what it served with the reference.

The served path is the program's own: ``serving.router.Router`` (one
replica) over ``SlotScheduler`` over the engine's admit (prefill) and
``decode_step``, built by ``launch.serve.build_engine_full`` with the
Pallas backend and the serve-layout prepack.  The benchmark wraps the
engine's admit and decode calls only to open its spans, to wait for the
device inside them (the scheduler reads their results at once anyway)
and to keep each decode step's candidate logits for the comparison."""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from . import check, traffic as T
from .trace import Reduced, load, module_prefix, reduce
from .widths import Widths

WARMUP_ID = 1 << 40          # request ids of set-up's warm-up requests


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


@dataclass
class Run:
    """What the window produced; the metric readers read this."""
    cell: str
    config: dict
    traffic: dict
    widths: Widths
    peaks: dict
    setup_s: float = 0.0
    window_s: float = 0.0
    ticks: int = 0
    tick_host_s: float = 0.0          # host clock over all window ticks
    device_wait_s: float = 0.0        # of which spent waiting on device
    decode_calls: int = 0
    decode_lives: List[np.ndarray] = field(default_factory=list)
    admit_calls: int = 0
    admit_s: List[float] = field(default_factory=list)
    admit_prompts: List[int] = field(default_factory=list)
    itl: List[float] = field(default_factory=list)
    ttft: List[float] = field(default_factory=list)
    tokens: int = 0
    trace: Optional[Reduced] = None
    decode_module: str = ""


class _Calls:
    """Wraps the engine's admit, decode and retire: spans, device wait,
    and the per-step candidate stash.  ``recording`` is on during the
    window."""

    def __init__(self, eng, run: Run):
        self.eng, self.run = eng, run
        self.sched = None
        self.recording = False
        self.cands: Dict[int, object] = {}      # scheduler tick → cand_v
        self.fault: Optional[Callable] = None

    def admit(self, params, state, toks, lens, samp=None):
        with jax.profiler.TraceAnnotation("bench.admit"):
            t0 = time.perf_counter()
            first, st = self.eng.admit_fn(params, state, toks, lens, samp)
            t1 = time.perf_counter()
            jax.block_until_ready(first)
            t2 = time.perf_counter()
        if self.recording:
            self.run.admit_calls += 1
            self.run.admit_s.append(t2 - t0)
            self.run.device_wait_s += t2 - t1
            self.run.admit_prompts += [int(n) for n in lens if n > 0]
        return first, st

    def retire(self, state, mask):
        # the retire copies the whole state; waiting for it here keeps the
        # next tick's admit from being enqueued beside it, which would
        # hold a third copy of the K/V cache
        t0 = time.perf_counter()
        st = jax.block_until_ready(self.eng.retire_fn(state, mask))
        if self.recording:
            self.run.device_wait_s += time.perf_counter() - t0
        return st

    def decode(self, params, state, tokens):
        if self.recording:
            lives = self.sched.expected_cache_lens()
            self.run.decode_lives.append(lives[lives >= 0])
        with jax.profiler.TraceAnnotation("bench.decode"):
            t1 = time.perf_counter()
            nxt, st = self.eng.decode_fn(params, state, tokens)
            if self.fault is not None:
                nxt, st = self.fault(self.sched, state, nxt, st)
            t2 = time.perf_counter()
            jax.block_until_ready(nxt)
            t3 = time.perf_counter()
        self.cands[self.sched.tick] = st["cand_v"]
        if self.recording:
            self.run.decode_calls += 1
            self.run.device_wait_s += t3 - t2
        return nxt, st


def _log_memory(devices) -> None:
    """Device memory and the largest live arrays, by shape."""
    from collections import Counter
    log(f"memory: {[d.memory_stats() for d in devices]}")
    by = Counter()
    for a in jax.live_arrays():
        by[(tuple(a.shape), str(a.dtype))] += a.nbytes
    log(f"live arrays: {sum(by.values())} bytes; largest "
        f"{by.most_common(12)}")


def _process_start() -> float:
    """Epoch seconds at which this process started (from /proc), or now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(ln.split()[1]) for ln in f
                         if ln.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return time.time()


PROCESS_START = _process_start()


def program_config(model: dict):
    """The program's registered architecture at the file's sizes."""
    from repro.configs import get_config
    base = get_config(model["arch"])
    return dataclasses.replace(
        base, name=model["name"], n_layers=model["num_hidden_layers"],
        d_model=model["hidden_size"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        ffn_act=model["hidden_act"], ffn_gated=model["gated_ffn"],
        tie_embeddings=model["tie_word_embeddings"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]))


def _finished(router, calls: _Calls, planned_by_id: Dict[int, T.Planned]
              ) -> List[dict]:
    """Finished requests with their served tokens and, per decode step,
    the fused head's candidate row of their slot."""
    sched = router.replicas[0].sched
    local = [rid for _, kind, rid, _ in router.events if kind == "dispatch"]
    out = []
    for lr, rid in enumerate(local):
        e = router.journal[rid]
        if not e.done or rid >= WARMUP_ID:
            continue
        res = sched.results[lr]
        rows = [calls.cands.get(res.admit_tick + j - 1)
                for j in range(1, len(e.tokens))]
        out.append({"rid": rid, "prompt": planned_by_id[rid].prompt,
                    "tokens": list(e.tokens), "slot": res.slot,
                    "rows": rows})
    return out


class _Window:
    """The open-loop window: each request joins at the first tick at or
    after its due time; every token is stamped when the router commits it
    to the journal."""

    def __init__(self, router, run: Run, due: List[T.Planned]):
        self.router, self.run, self.due = router, run, due
        self.seen: Dict[int, int] = {}       # tokens stamped, per request
        self.last: Dict[int, float] = {}     # time of the last stamp
        self.first: Dict[int, float] = {}    # time of the first token
        self.late: Dict[int, float] = {}     # submit time − due time
        self.active: set = set()
        self.sent = 0                        # window arrivals submitted
        self.longest: List[tuple] = []       # (seconds, tick, admits so far)

    def track(self, rid: int, served: int, t: float) -> None:
        """A request already served ``served`` tokens before ``t``."""
        self.seen[rid], self.first[rid] = served, t
        self.active.add(rid)

    def drive(self, pending: List, t0: float, end: float) -> None:
        from repro.serving.scheduler import Request
        run, due = self.run, self.due
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            while self.sent < len(due) and t0 + due[self.sent].due <= now:
                p = due[self.sent]
                pending.append(Request(p.rid, p.prompt, p.max_new))
                self.late[p.rid] = now - t0 - p.due
                self.sent += 1
            if not pending and self.router.idle():
                nxt = (t0 + due[self.sent].due if self.sent < len(due)
                       else end)
                time.sleep(max(0.0, min(nxt, end) - now))
                continue
            self.active.update(r.rid for r in pending)
            with jax.profiler.TraceAnnotation("bench.tick"):
                self.router.step(pending)
            pending = []
            t = time.perf_counter()
            run.ticks += 1
            run.tick_host_s += t - now
            self.longest = sorted(self.longest + [(t - now, run.ticks,
                                                   run.admit_calls)])[-3:]
            self._stamp(t)

    def _stamp(self, t: float) -> None:
        run = self.run
        for rid in list(self.active):
            e = self.router.journal[rid]
            n, had = len(e.tokens), self.seen.get(rid, 0)
            if n > had:
                if rid not in self.first:
                    self.first[rid] = t
                elif rid in self.last:
                    run.itl.append(t - self.last[rid])
                # tokens committed together reach the client together
                run.itl.extend([0.0] * (n - had - 1))
                run.tokens += n - had
                self.seen[rid], self.last[rid] = n, t
            if e.done or e.failed:
                self.active.discard(rid)

    def drain(self, seconds: float) -> None:
        """After the close, step on (no new arrivals) until every request
        due in the window has finished, for at most ``seconds``."""
        ids = [p.rid for p in self.due[:self.sent]]
        j = self.router.journal
        stop = time.perf_counter() + seconds
        while (any(not (j[r].done or j[r].failed) for r in ids)
               and time.perf_counter() < stop):
            self.router.step()
            t = time.perf_counter()
            for rid in ids:
                if rid not in self.first and j[rid].tokens:
                    self.first[rid] = t


def _build(model: dict, mix: dict, w: Widths, seed: int, devices,
           interpret: bool):
    """The served path with the seeded weights: a one-replica router over
    the engine, its admit/decode/retire wrapped by :class:`_Calls`."""
    from repro.launch.mesh import device_mesh
    from repro.launch.serve import EngineOptions, build_engine_full

    from . import model as M

    cfg = program_config(model)
    eng = build_engine_full(
        cfg, device_mesh(devices), max_seq=mix["max_seq"],
        batch_global=mix["slots"],
        options=EngineOptions(backend="pallas", prepack="on",
                              interpret=interpret, stash_candidates=True,
                              **mix.get("engine", {})))
    # the program initialises its own weights; the benchmark's, from the
    # seed, take their place before anything is served
    shard = jax.tree.map(lambda a: a.sharding, eng.params["train"])
    eng.params["train"] = eng.params["serve"] = None
    gc.collect()
    train = M.program_params(w, seed, cfg, eng.lay, shard)
    eng.params["train"] = train
    eng.params["serve"] = eng.repack_fn(train)
    del train
    return eng


def _serve(eng, cap: int, max_new_cap: int, run: Run,
           fault: Optional[Callable]):
    from repro.serving.router import Router
    router = Router([eng._replace(admit_fn=None, decode_fn=None)],
                    prompt_cap=cap, max_new_cap=max_new_cap)
    # the scheduler starts from the handle's initial state and keeps its
    # own; the handles would keep the initial one alive beside it, a
    # second whole K/V cache, so they go without it
    calls = _Calls(eng._replace(state=None), run)
    calls.fault = fault
    rep = router.replicas[0]
    rep.eng = rep.sched.eng = eng._replace(
        state=None, admit_fn=calls.admit, decode_fn=calls.decode,
        retire_fn=calls.retire)
    calls.sched = rep.sched
    return router, calls


def _warm_up(router, vocab: int, cap: int, seed: int,
             inflight: List[T.Planned], warm_new: int) -> None:
    """Compile and run admit, decode and retire, in the orders the window
    calls them, on warm-up requests; then admit the requests already in
    flight when the window opens."""
    from repro.serving.scheduler import Request
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))

    def warm(k, new):
        return Request(WARMUP_ID + k,
                       rng.integers(0, vocab, size=cap).tolist(), new)
    # tick 0: admit; a one-token request retires; decode.  Tick 1: decode.
    # Tick 2: admit after a decode, decode, and both finish: retire.  So
    # each step has taken the state of each step that can precede it
    for arrivals in ([warm(0, 1), warm(1, 4)], [], [warm(2, 2)]):
        router.step(arrivals)
    while not router.idle():
        router.step()
    arrivals = [Request(p.rid, p.prompt, p.max_new) for p in inflight]
    for _ in range(warm_new - 1 if inflight else 0):
        router.step(arrivals)
        arrivals = []
    jax.block_until_ready(router.replicas[0].sched.state)


def _candidates(finished: List[dict], slots: int) -> None:
    """Fetch each sampled request's candidate rows to the host."""
    rows = jax.device_get([r["rows"] for r in finished])
    for r, hr in zip(finished, rows):
        r["cands"] = (np.stack([np.asarray(x).reshape(-1, slots,
                                                      x.shape[-1])[0][
            r["slot"]] for x in hr]) if hr else None)
        del r["rows"]


def _report(run: Run, win: _Window, n_compiles: int) -> None:
    late = list(win.late.values())
    log(f"set-up {run.setup_s!r} s (from process start); window "
        f"{run.window_s!r} s; compilations inside the window {n_compiles}; "
        f"ticks {run.ticks}; decode calls {run.decode_calls}; admit calls "
        f"{run.admit_calls}; tokens {run.tokens}")
    if late:
        log(f"open-loop generator lateness: mean {float(np.mean(late))!r}"
            f" s, max {float(np.max(late))!r} s over {len(late)} requests")
    log(f"longest ticks (s, tick, admits so far): {win.longest}")
    if run.admit_s:
        host = (run.tick_host_s - run.device_wait_s) / max(run.ticks, 1)
        log(f"admit host ms: mean {float(np.mean(run.admit_s)) * 1e3!r}, "
            f"max {float(np.max(run.admit_s)) * 1e3!r}; host ms per tick "
            f"{host * 1e3!r}")
    if run.ttft:
        log(f"ttft_p90_ms {float(np.percentile(run.ttft, 90)) * 1e3!r} over "
            f"{len(run.ttft)} requests")
    if run.itl:
        log(f"itl_p99_ms {float(np.percentile(run.itl, 99)) * 1e3!r}; "
            f"itl_p95_ms {float(np.percentile(run.itl, 95)) * 1e3!r}")


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             interpret: bool = False, fault: Optional[Callable] = None,
             peaks: Optional[dict] = None, control: bool = False) -> dict:
    """One run; returns the result line's fields (without ``metrics``),
    the compared ``values`` and the :class:`Run` under ``"run"``."""
    from repro.serving.scheduler import Request

    model, mix = cell.config, cell.traffic
    w = Widths.from_config(model)
    run = Run(cell=cell.name, config=model, traffic=mix, widths=w,
              peaks=peaks or {})
    devices = jax.devices()[:cell.chips]
    slots, cap = mix["slots"], mix["prompt_cap"]
    planned = T.plan(mix, seed, seconds, model["vocab_size"])
    warm_new = mix.get("warmup_ticks", 2) + 1
    group = {g: [p for p in planned if p.group == g]
             for g in ("in_flight", "backlog", "arrivals")}
    for p in group["in_flight"]:
        p.max_new += warm_new        # tokens served during set-up
    by_id = {p.rid: p for p in planned}

    eng = _build(model, mix, w, seed, devices, interpret)
    decode_name = eng.decode_fn.__name__
    router, calls = _serve(eng, cap, max(
        [p.max_new for p in planned] + [warm_new, 4]), run, fault)
    del eng
    gc.collect()
    _warm_up(router, model["vocab_size"], cap, seed, group["in_flight"],
             warm_new)
    _log_memory(devices)
    run.decode_module = module_prefix(decode_name)

    compiles: List[str] = []

    def listener(name, secs, **kw):
        if name.startswith("/jax/core/compile/"):
            compiles.append(f"{name} {kw.get('fun_name', '')}")
    jax.monitoring.register_event_duration_secs_listener(listener)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if tdir is not None:
        jax.profiler.start_trace(tdir)

    # -- the window ---------------------------------------------------------
    win = _Window(router, run, sorted(group["arrivals"], key=lambda p: p.due))
    run.setup_s = time.time() - PROCESS_START
    t0 = time.perf_counter()
    pending = [Request(p.rid, p.prompt, p.max_new) for p in group["backlog"]]
    for p in group["backlog"]:
        win.late[p.rid] = 0.0
        win.active.add(p.rid)
    for p in group["in_flight"]:    # served since set-up: no first token
        win.track(p.rid, len(router.journal[p.rid].tokens), t0)
    calls.recording = True
    broke = None
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            win.drive(pending, t0, t0 + seconds)
    except Exception as e:       # the served path failed: no result holds
        broke = f"{type(e).__name__}: {e}"
        log(f"the served path failed inside the window: {broke}")
        _log_memory(devices)
    run.window_s = time.perf_counter() - t0
    calls.recording = False
    n_compiles = len(compiles)
    jax.monitoring.unregister_event_duration_listener(listener)
    if tdir is not None:
        jax.profiler.stop_trace()
    drain = float(mix.get("drain_s", 0))
    if drain > 0 and broke is None:
        # a latency mix: requests due in the window keep their first
        # token's wait, and one never served is infinitely late (a
        # saturated mix leaves its queue behind)
        win.drain(drain)
        run.ttft = [win.first[p.rid] - (t0 + p.due) if p.rid in win.first
                    else float("inf") for p in win.due[:win.sent]]
    _report(run, win, n_compiles)
    if n_compiles:
        log(f"compiled inside the window: {compiles[:n_compiles]}")
    dev = devices[0]
    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                       for s in stats)}
    failed = sum(1 for p in planned if p.rid in router.journal
                 and router.journal[p.rid].failed)
    failed += sum(1 for v in run.ttft if v == float("inf"))
    attempted = len(group["in_flight"]) + len(group["backlog"]) + win.sent

    # -- the comparison, once the program's state is freed ------------------
    picked = check.sample(_finished(router, calls, by_id), seed,
                          mix["check"]["requests"],
                          mix["check"]["max_tokens"])
    _candidates(picked, slots)
    del router, calls, win
    gc.collect()
    t_ref = time.perf_counter()
    values = (check.compare(w, model, seed, picked, control=control)
              if picked else {})
    log(f"reference {time.perf_counter() - t_ref!r} s; compared "
        f"{values.get('served_tokens')} served tokens and "
        f"{values.get('decode_steps')} decode steps of {len(picked)} "
        f"requests {[r['rid'] for r in picked]}")
    limits = model["limits"]
    correct = bool(picked) and n_compiles == 0 and broke is None and \
        check.verdict(values, limits)
    if control and picked:
        values["control_correct"] = check.control_verdict(values, limits)
        log(f"control (fp8 reference in the program's place): correct "
            f"{values['control_correct']}")
    if tdir is not None:
        from glob import glob
        files = glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)
        run.trace = reduce(load(files[0]), decode_module=run.decode_module)
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    checks = {k: {"value": values.get(k, float("nan")), "limit": v}
              for k, v in limits.items()}
    return {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "device": device, "checks": checks,
            "values": values, "run": run}
