"""Slot-based continuous batching over the ragged decode engine.

The decode step is RAGGED (per-slot ``cache_lens`` — serving/engine.py,
DESIGN.md §6), so the batch no longer advances in lockstep: this module
runs the request-level loop on top of it.  The engine's ``B`` batch rows
become **slots** with a lifecycle::

    FREE (cache_lens = −1)
      └─ admit ──▶ ACTIVE   targeted prefill-insert at the slot's offset
                            (EngineHandle.admit_fn; one jitted call admits
                            every request picked this tick, and emits each
                            request's FIRST token)
    ACTIVE ── decode ──▶    one ragged decode step per tick advances ALL
                            active slots (per-slot RoPE position, append
                            slot, live-span cull; free slots do zero
                            attend-step work — state["work_blocks"])
      └─ retire ──▶ FREE    on EOS or max_new (EngineHandle.retire_fn);
                            the slot is immediately re-admittable

Scheduling policy (deterministic, mirrored by the pure-Python reference
simulator in tests/test_scheduler.py): arrivals enqueue FIFO; each tick
admits queue-head requests into the lowest-numbered free slots, retires
any one-token requests, runs one decode step for the active slots, then
retires finished ones.

The driver is host-side Python issuing three jitted programs (admit /
decode / retire) — the decode hot loop itself stays ONE fused dispatch
per token, exactly the paper's fusion story; continuous batching only
changes which slots carry live work.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import tracecount
from repro.launch.serve import EngineHandle
from repro.serving.sampling import (GREEDY, SamplingParams,
                                    fill_sampling_row, host_sampling_rows,
                                    validate_sampling)


def fetch(x) -> np.ndarray:
    """Blocking device→host read of ``x``.  Every such read of the serve
    loop (scheduler and router) goes through here, so each is counted:
    ``host_syncs`` and ``host_sync_bytes`` (core/tracecount.py)."""
    a = np.asarray(jax.device_get(x))
    tracecount.record_serve("host_syncs")
    tracecount.record_serve("host_sync_bytes", a.nbytes)
    return a


@dataclass
class Request:
    """One generation request.  ``prompt``: token ids (≤ the scheduler's
    ``prompt_cap``); ``max_new``: tokens to generate (counting the one
    sampled by the prefill insert).

    ``replay``: journaled tokens to RECONSTRUCT before generating live
    (fleet recovery — serving/router.py).  The slot admits ``prompt``
    normally, then force-feeds the replay tokens as decode inputs in
    order, re-building the exact device state of the original stream
    (same jitted programs, same inputs, same order ⇒ same floats —
    DESIGN.md §9).  The engine's re-emitted tokens are cross-checked
    against the journal (``replay_mismatch``); the journaled value is
    authoritative for both the result stream and the next decode input.
    ``max_new`` counts the replayed tokens, so a resumed request keeps
    its original budget.

    ``sampling``: per-request :class:`SamplingParams`
    (serving/sampling.py) — temperature / top-k / top-p / seed, default
    greedy.  The params ride the admit call into the slot's device
    state leaves and every emission of this request uses them; the PRNG
    stream is positional (seed × emit offset), so a replayed request
    re-derives its original sampled stream bit-exactly."""
    rid: int
    prompt: Sequence[int]
    max_new: int
    replay: Sequence[int] = ()
    sampling: SamplingParams = GREEDY


class SchedulerHooks:
    """Extension points for perturbing a live scheduler — the ONLY
    sanctioned way the fault-injection harness (serving/faults.py)
    touches a running engine: the hooks are threaded through the
    admit/decode call sites, never monkeypatched, so every injected
    fault is visible in the call graph.  The base class is a no-op;
    a scheduler built with ``hooks=None`` behaves identically.
    """

    def pre_step(self, sched: "SlotScheduler") -> None:
        """Start of every tick; may raise (e.g. faults.ReplicaKilled)."""

    def admit_args(self, sched: "SlotScheduler", toks: np.ndarray,
                   lens: np.ndarray):
        """Rewrite the (tokens, lengths) the DEVICE admit call sees —
        host bookkeeping keeps the original request (dropped admits)."""
        return toks, lens

    def post_admit(self, sched: "SlotScheduler") -> None:
        """After the tick's admit call (duplicate-admit injection)."""

    def decode_args(self, sched: "SlotScheduler", params, state, tokens):
        """Rewrite what the device decode call consumes (KV/length
        corruption, weight poisoning)."""
        return params, state, tokens

    def decode_blackholed(self, sched: "SlotScheduler") -> bool:
        """True ⇒ the decode call never returns (network blackhole):
        the scheduler's host loop sees a stale echo of its own inputs
        while device state freezes."""
        return False


@dataclass
class _Slot:
    rid: Optional[int] = None
    remaining: int = 0          # tokens still to emit
    last_tok: int = 0
    prompt_len: int = 0         # admitted prompt length (journal model)
    emitted: int = 0            # tokens emitted so far (incl. replayed)
    replay: List[int] = field(default_factory=list)
    replay_mismatch: int = 0    # engine token ≠ journaled token count

    @property
    def free(self) -> bool:
        return self.rid is None


@dataclass
class RequestResult:
    rid: int
    tokens: List[int] = field(default_factory=list)
    slot: int = -1
    admit_tick: int = -1
    finish_tick: int = -1
    # effective per-request sampling params (what the device actually
    # used — audit trail for sampled streams)
    sampling: SamplingParams = GREEDY


class SlotScheduler:
    """Continuous-batching driver over an :class:`EngineHandle`.

    Build the engine with ``build_engine_full(..., track_work=True)`` to
    get the per-slot attend-step counters the tests assert on.  Requires
    an attention-only decoder (the targeted prefill-insert pads prompts
    to ``prompt_cap`` — recurrent scans would fold the padding into
    their state) and the batch replicated over the data axes.
    """

    def __init__(self, engine: EngineHandle, *, prompt_cap: int,
                 eos_id: Optional[int] = None,
                 hooks: Optional[SchedulerHooks] = None,
                 integrity_latch: bool = False):
        cfg = engine.cfg
        assert cfg.frontend is None and cfg.encoder is None, \
            "SlotScheduler supports decoder-only text models"
        # capacity-based MoE dispatch couples batch rows (experts drop by
        # PER-BATCH capacity), so a request's tokens would depend on its
        # slot neighbors — breaking the slot-independence contract the
        # scheduler (and its tests) guarantee
        assert cfg.moe is None, \
            "SlotScheduler requires dense-FFN models: MoE capacity " \
            "routing makes tokens depend on co-resident slots"
        assert engine.scfg.batch_local == engine.batch_global, \
            "SlotScheduler needs the batch replicated over data axes"
        self.eng = engine
        self.prompt_cap = int(prompt_cap)
        self.eos_id = eos_id
        self.hooks = hooks
        self.n_slots = engine.batch_global
        self.slots = [_Slot() for _ in range(self.n_slots)]
        self.queue: List[Request] = []
        self.results: Dict[int, RequestResult] = {}
        self.events: List[Tuple[int, str, int, int]] = []   # (tick, kind,
        self.occupancy: List[float] = []                    #  rid, slot)
        self.tick = 0
        self.decode_calls = 0
        # Pre-retire integrity latch (router probes, DESIGN.md §9).
        # Retiring a slot resets its cache length and finite sentinel —
        # which would DESTROY the evidence of a fault whose victim
        # finishes on the fault tick, letting a corrupt final token
        # commit.  With the latch on, violations are snapshotted to the
        # host between the decode and the retire that would erase them.
        self.integrity_latch = integrity_latch
        self.latched: List[str] = []
        self._replay_mismatch_retired = 0
        # all slots start FREE (cache_lens = −1)
        self.state = engine.retire_fn(engine.state,
                                      np.ones((self.n_slots,), np.int32))

    # -- host views of the device state ----------------------------------
    def cache_lens(self) -> np.ndarray:
        """Per-slot cache lengths (−1 = free); identical across shards."""
        return fetch(self.state["cache_lens"]).reshape(-1, self.n_slots)[0]

    def work_blocks(self) -> np.ndarray:
        """Per-slot attend-step counters, summed over the (dp, model)
        device grid — each cluster rank counts its own rank-local blocks
        (core/tracecount.live_attend_blocks)."""
        if "work_blocks" not in self.state:
            raise ValueError("build the engine with track_work=True")
        return fetch(self.state["work_blocks"]).reshape(
            -1, self.n_slots).sum(axis=0)

    # -- host model of the device cache lengths ---------------------------
    def expected_cache_lens(self) -> np.ndarray:
        """What ``cache_lens`` MUST read if the device executed exactly
        the admits/decodes this host issued: an active slot's cache
        holds its prompt plus one entry per decode input so far
        (``prompt_len + emitted − 1`` — the admit insert itself emits
        the first token without consuming a cache entry); free slots
        sit at −1.  The router's journal cross-check compares this
        against the device vector every tick: a dropped or duplicated
        admit, a blackholed (frozen) replica, or a corrupted length all
        surface as a mismatch (DESIGN.md §9)."""
        out = np.full((self.n_slots,), -1, np.int64)
        for b, s in enumerate(self.slots):
            if not s.free:
                out[b] = s.prompt_len + s.emitted - 1
        return out

    def replay_mismatches(self) -> int:
        """Total journal/engine token disagreements across recovery
        replays, live and retired (zero under the supported fault
        model)."""
        return self._replay_mismatch_retired + sum(
            s.replay_mismatch for s in self.slots)

    def _latch_integrity(self) -> None:
        """Snapshot per-slot integrity violations BEFORE the post-decode
        retire can reset them (see ``integrity_latch``).  All reads are
        [shards, B] host pulls — the same cost as one router probe."""
        with TraceAnnotation("serve.latch"):
            st = self.state
            if "nonfinite" in st:
                if (fetch(st["nonfinite"]) > 0).any():
                    self.latched.append("detect_nonfinite")
            lens = fetch(st["cache_lens"]).reshape(-1, self.n_slots)
            if ((lens < -1).any()
                    or (lens > self.eng.scfg.max_seq).any()
                    or (lens != lens[0]).any()):
                self.latched.append("detect_lens_bounds")
            if (lens[0] != self.expected_cache_lens()).any():
                self.latched.append("detect_journal_stale")

    # -- request intake ---------------------------------------------------
    def submit(self, req: Request) -> None:
        # length 0 means "slot untouched" to the prefill insert, so an
        # empty prompt would desync host bookkeeping from device state
        plen = len(req.prompt)
        if plen == 0:
            raise ValueError(
                f"request {req.rid}: empty prompt — the targeted prefill "
                "insert treats length 0 as 'leave this slot untouched', "
                "so an admitted request needs at least 1 prompt token")
        if plen > self.prompt_cap:
            raise ValueError(
                f"request {req.rid}: prompt length {plen} exceeds this "
                f"scheduler's prompt_cap={self.prompt_cap}")
        if plen > self.eng.scfg.max_seq:
            raise ValueError(
                f"request {req.rid}: prompt length {plen} exceeds the "
                f"engine's cache capacity max_seq={self.eng.scfg.max_seq}")
        if req.max_new < 1:
            raise ValueError(
                f"request {req.rid}: max_new must be ≥ 1 "
                f"(got {req.max_new})")
        if len(req.replay) >= req.max_new:
            raise ValueError(
                f"request {req.rid}: replay carries {len(req.replay)} "
                f"tokens but max_new={req.max_new} — a resumed request "
                "must have live tokens left to generate")
        validate_sampling(req.rid, req.sampling)
        if req.rid in self.results:
            raise ValueError(f"request {req.rid}: duplicate request id")
        self.queue.append(req)
        self.results[req.rid] = RequestResult(rid=req.rid,
                                              sampling=req.sampling)

    # -- lifecycle pieces -------------------------------------------------
    def _admit(self) -> None:
        free = [i for i, s in enumerate(self.slots) if s.free]
        admitted: List[Tuple[int, Request]] = []
        while self.queue and free:
            admitted.append((free.pop(0), self.queue.pop(0)))
        if not admitted:
            return
        with TraceAnnotation("serve.admit", tick=self.tick,
                             rids=[req.rid for _, req in admitted]):
            toks = np.zeros((self.n_slots, self.prompt_cap), np.int32)
            lens = np.zeros((self.n_slots,), np.int32)
            samp = host_sampling_rows(self.n_slots)
            for b, req in admitted:
                toks[b, :len(req.prompt)] = np.asarray(req.prompt, np.int32)
                lens[b] = len(req.prompt)
                fill_sampling_row(samp, b, req.sampling)
            tracecount.record_serve("admit_rows", int(lens.sum()))
            tracecount.record_serve("admit_positions", toks.size)
            if self.hooks is not None:
                toks, lens = self.hooks.admit_args(self, toks, lens)
            first, self.state = self.eng.admit_fn(
                self.eng.params["train"], self.state, toks, lens, samp)
            with TraceAnnotation("serve.admit.wait"):
                first = fetch(first).reshape(-1)
            for b, req in admitted:
                self.slots[b] = _Slot(rid=req.rid, remaining=req.max_new,
                                      prompt_len=len(req.prompt),
                                      replay=list(req.replay))
                res = self.results[req.rid]
                res.slot, res.admit_tick = b, self.tick
                self.events.append((self.tick, "admit", req.rid, b))
                self._emit(b, int(first[b]))
            if self.hooks is not None:
                self.hooks.post_admit(self)

    def _emit(self, b: int, tok: int) -> None:
        s = self.slots[b]
        if s.replay:
            # recovery replay: the journal is authoritative — the
            # engine's re-emitted token must MATCH it (same weights,
            # same inputs); count any divergence as a detection signal
            # rather than corrupting the stream
            want = s.replay.pop(0)
            if tok != want:
                s.replay_mismatch += 1
            tok = want
        s.last_tok = tok
        s.remaining -= 1
        s.emitted += 1
        self.results[s.rid].tokens.append(tok)

    def _retire_finished(self) -> None:
        fin = [b for b, s in enumerate(self.slots) if not s.free
               and (s.remaining <= 0
                    or (self.eos_id is not None
                        and s.last_tok == self.eos_id))]
        if not fin:
            return
        with TraceAnnotation("serve.retire"):
            mask = np.zeros((self.n_slots,), np.int32)
            for b in fin:
                mask[b] = 1
                rid = self.slots[b].rid
                self.results[rid].finish_tick = self.tick
                self.events.append((self.tick, "finish", rid, b))
                self._replay_mismatch_retired += self.slots[b].replay_mismatch
                self.slots[b] = _Slot()
            self.state = self.eng.retire_fn(self.state, mask)

    # -- one scheduler tick ----------------------------------------------
    def step(self) -> None:
        if self.hooks is not None:
            self.hooks.pre_step(self)
        self._admit()
        if self.integrity_latch and any(
                not s.free and (s.remaining <= 0
                                or (self.eos_id is not None
                                    and s.last_tok == self.eos_id))
                for s in self.slots):
            # a request admitted THIS tick finishes before the decode
            # stage — latch now or the retire below erases the evidence
            # of a dropped/corrupted admit
            self._latch_integrity()
        self._retire_finished()          # one-token / instant-EOS admits
        active = [b for b, s in enumerate(self.slots) if not s.free]
        if active:
            tok_in = np.asarray([s.last_tok for s in self.slots], np.int32)
            if self.hooks is not None and self.hooks.decode_blackholed(self):
                # the decode call never returns: the host loop proceeds
                # on a stale echo of its own inputs while device state
                # freezes — the router's expected-lens cross-check trips
                # at its next probe (DESIGN.md §9)
                nxt = tok_in
            else:
                params, st, ti = self.eng.params["serve"], self.state, tok_in
                if self.hooks is not None:
                    params, st, ti = self.hooks.decode_args(
                        self, params, st, ti)
                with TraceAnnotation("serve.decode", tick=self.tick,
                                     step=self.decode_calls):
                    nxt, self.state = self.eng.decode_fn(params, st, ti)
                    self.decode_calls += 1
                    with TraceAnnotation("serve.decode.wait"):
                        nxt = fetch(nxt).reshape(-1)
            for b in active:
                self._emit(b, int(nxt[b]))
            if self.integrity_latch:
                self._latch_integrity()
            self._retire_finished()
        self.occupancy.append(len(active) / self.n_slots)
        self.tick += 1

    def idle(self) -> bool:
        return not self.queue and all(s.free for s in self.slots)

    def run(self, max_ticks: int = 10_000) -> Dict[int, RequestResult]:
        while not self.idle() and self.tick < max_ticks:
            self.step()
        assert self.idle(), f"scheduler did not drain in {max_ticks} ticks"
        return self.results


def replay_trace(sched: SlotScheduler,
                 trace: Sequence[Tuple[int, Request]],
                 max_ticks: int = 10_000) -> Dict[int, RequestResult]:
    """Drive ``sched`` from an arrival trace: ``(arrival_tick, Request)``
    pairs.  Requests join the queue at the START of their arrival tick;
    the scheduler then runs until drained."""
    pending = sorted(trace, key=lambda ar: ar[0])
    i = 0
    while (i < len(pending) or not sched.idle()) and sched.tick < max_ticks:
        while i < len(pending) and pending[i][0] <= sched.tick:
            sched.submit(pending[i][1])
            i += 1
        sched.step()
    assert sched.idle(), "trace did not drain"
    return sched.results
