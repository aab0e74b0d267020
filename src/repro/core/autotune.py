"""Cluster-size / dataflow selection (paper §4.1 Fig. 11 + App. B).

The paper's conclusion: *"the optimal cluster size varies across workloads
… cluster size should be tuned accordingly"* (they measure 4 best for
32–64 heads, 2 for 128 heads on H100).  On H100 the trade-off is DSMEM
latency/bandwidth vs active SMs; on TPU the analogous trade-off is:

* larger N ⇒ more chips cooperate on one head ⇒ shorter per-chip KV scan
  (good: decode is KV-bandwidth-bound) but more ICI rounds (log2 N) and
  more gather/reduce traffic (paper's traffic model, linear-to-N·log N);
* larger N also shrinks the head-group axis H = model_axis / N ⇒ fewer
  heads resident per chip ⇒ more weight replication for GQA KV weights.

We pick N by minimizing an analytical per-token latency model built from
the paper's traffic formulas plus v5e roofline constants.  This is the
same *structure* as the paper's Appendix-B analysis, with DSMEM constants
replaced by ICI/HBM constants from :data:`CHIP_PEAKS` — the figures of
the rehearsed target, a TPU v5e (:data:`REHEARSED_KIND`).
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import asdict, dataclass
from typing import Dict, List, Optional, Tuple

from repro.configs.base import ATTN_GLOBAL, ATTN_LOCAL, ModelConfig
from repro.core import dataflow as df
from repro.core import primitives as prim

@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one TPU generation."""
    flops: float                 # bf16 FLOP/s
    hbm_bw: float                # HBM bytes/s
    ici_bw: float                # inter-chip bytes/s per link
    source: str


# Peaks keyed by ``jax.Device.device_kind``.  A kind that is not here is
# an error (:func:`chip_peaks`), never a silent default.
CHIP_PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9,
        ici_bw=50e9,             # 1,600 Gbit/s over 4 links
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip '
               'interconnect'),
}
# The chip the CPU runs rehearse: tests/test_tpu_compile.py compiles for
# a described v5e, and the analytical plans below use its figures.
REHEARSED_KIND = "TPU v5 lite"


def chip_peaks(kind: str) -> ChipPeaks:
    """The :data:`CHIP_PEAKS` entry for a ``device_kind``; raises for a
    kind without published figures here."""
    try:
        return CHIP_PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no peak figures for device kind {kind!r}; add them to "
            f"CHIP_PEAKS with their source") from None


_V5E = chip_peaks(REHEARSED_KIND)
PEAK_FLOPS = _V5E.flops      # bf16 FLOP/s per chip
HBM_BW = _V5E.hbm_bw         # bytes/s per chip
ICI_BW = _V5E.ici_bw         # bytes/s per link
ICI_LAT = 1e-6               # seconds per hop (round latency floor)
GRID_STEP_OVH = 1e-6         # per-Pallas-grid-step fixed overhead (s)
VMEM_BUDGET = 8 * 2**20      # bytes for double-buffered KV blocks


@dataclass(frozen=True)
class TunePoint:
    cluster_size: int
    dataflow: str               # "split_token" | "split_head" | "mla"
    est_seconds: float
    terms: Dict[str, float]


def _attn_decode_time(cfg: ModelConfig, seq_len: int, batch: int,
                      model_axis: int, n: int, flow: str) -> Tuple[float, Dict[str, float]]:
    """Per-layer decode-step latency estimate for cluster size n."""
    hd = cfg.resolved_head_dim
    d = cfg.d_model
    heads_axis = model_axis // n
    q_local = max(1, cfg.n_heads // heads_axis)
    kv_local = max(1, cfg.n_kv_heads // heads_axis)
    bpe = 2  # bf16

    if cfg.mla is not None and flow == "mla":
        l_rank = cfg.mla.kv_lora_rank
        kv_bytes = batch * seq_len * (l_rank + cfg.mla.rope_head_dim) * bpe
        traffic = df.traffic_mla(hd, l_rank, cfg.n_heads * hd, n,
                                 bytes_per_el=bpe, batch=batch) * q_local
        flops = 2 * batch * q_local * seq_len * (l_rank + cfg.mla.rope_head_dim) * 2
    elif flow == "split_head":
        kv_bytes = batch * seq_len * kv_local * hd * 2 * bpe  # full S per rank
        traffic = df.traffic_split_head(seq_len, d, n, batch=batch) * q_local
        flops = 2 * batch * q_local * seq_len * hd * 2 / n
    else:  # split_token
        kv_bytes = batch * seq_len * kv_local * hd * 2 * bpe / n  # S split
        traffic = df.traffic_split_token(hd, d, n, bytes_per_el=bpe,
                                         batch=batch) * q_local
        flops = 2 * batch * q_local * seq_len * hd * 2 / n

    # weight bytes per chip for the fused block (QKV + O slices)
    w_bytes = (d * (q_local + 2 * kv_local) * hd / (1 if flow == "split_head" else n)
               + q_local * hd * d / n) * bpe
    t_mem = (kv_bytes + w_bytes) / HBM_BW
    t_comp = flops / PEAK_FLOPS
    t_ici = traffic / (n * ICI_BW) + math.log2(max(n, 2)) * ICI_LAT * (0 if n == 1 else 1)
    total = max(t_mem, t_comp) + t_ici
    return total, {"mem": t_mem, "comp": t_comp, "ici": t_ici,
                   "traffic_bytes": traffic}


def tune_cluster(cfg: ModelConfig, *, seq_len: int, batch: int,
                 model_axis: int = 16,
                 flows: Optional[List[str]] = None) -> TunePoint:
    """Pick (cluster_size, dataflow) minimizing the analytical latency.

    Mirrors the paper's tuning conclusion: larger N helps long sequences
    (KV split) until ICI rounds dominate; SplitHead only competes at short
    S; MLA uses its own fused dataflow.
    """
    if flows is None:
        flows = ["mla"] if cfg.mla is not None else ["split_token", "split_head"]
    best: Optional[TunePoint] = None
    n = 1
    while n <= model_axis:
        heads_axis = model_axis // n
        if cfg.n_heads % heads_axis == 0 or heads_axis <= cfg.n_heads:
            for flow in flows:
                t, terms = _attn_decode_time(cfg, seq_len, batch,
                                             model_axis, n, flow)
                pt = TunePoint(n, flow, t, terms)
                if best is None or t < best.est_seconds:
                    best = pt
        n *= 2
    assert best is not None
    return best


def sweep(cfg: ModelConfig, *, seq_len: int, batch: int,
          model_axis: int = 16) -> List[TunePoint]:
    """Full (N × dataflow) sweep — used by the Fig. 11 benchmark."""
    flows = ["mla"] if cfg.mla is not None else ["split_token", "split_head"]
    pts = []
    n = 1
    while n <= model_axis:
        for flow in flows:
            t, terms = _attn_decode_time(cfg, seq_len, batch, model_axis, n, flow)
            pts.append(TunePoint(n, flow, t, terms))
        n *= 2
    return pts


# ===========================================================================
# Serving plan: (cluster, dataflow, backend, block_s) per seq-length bucket,
# with a persisted table so repeated launches skip the search.
# ===========================================================================
@dataclass(frozen=True)
class ServePlan:
    cluster_size: int
    dataflow: str                # "split_token" | "mla"
    backend: str                 # "xla" | "pallas"
    block_s: int                 # KV block granularity (both backends)
    # serve-layout weight prepack (serving/prepack.py): weights are
    # re-laid out once at load time so the decode step performs zero
    # weight-segment ICI gathers and zero dynamic-slice weight slicing
    prepack: bool
    # d_ff tile of the fused-FFN block-tail megakernel (kernels/fused_ffn,
    # DESIGN.md §7); fitted down to a divisor of F_loc at the call site.
    # Pre-fused-FFN table entries lack this field and self-heal by
    # re-tuning (same schema-drift path as the prepack field).
    block_f: int
    # vocab tile of the fused LM-head/sampling kernel (kernels/fused_head,
    # DESIGN.md §7); fitted down to a divisor of V_loc at the call site.
    # Pre-fused-head table entries lack this field and self-heal by
    # re-tuning through the same TypeError path.
    block_v: int
    est_seconds: float


def seq_bucket(seq_len: int) -> int:
    """Power-of-two sequence-length bucket (≥ 256) — plans are tuned and
    persisted per bucket, not per exact length.  Ragged serving buckets
    on the expected MAX LIVE length, not the allocated capacity
    (``build_engine_full(plan_seq_len=…)`` — continuous batching
    allocates slack slots whose spans never reach ``max_seq``, and
    block_s/cluster should follow the spans the kernels actually
    stream; DESIGN.md §6)."""
    b = 256
    while b < seq_len:
        b *= 2
    return b


_BLOCK_CANDIDATES = (128, 256, 512, 1024, 2048)


def pick_block_s(cfg: ModelConfig, seq_len: int, cluster_size: int,
                 batch: int = 1) -> int:
    """KV block size for the decode inner loop.

    Per-rank live span is ``seq_len / N``; each block pays a fixed grid-
    step overhead plus its HBM bytes, so the model prefers the largest
    block whose double-buffered K+V tiles fit the VMEM budget and that
    doesn't exceed the span (smaller blocks only add overhead).
    """
    hd = cfg.resolved_head_dim
    if cfg.mla is not None:
        row = (cfg.mla.kv_lora_rank + cfg.mla.rope_head_dim) * 2 * batch
    else:
        row = max(1, cfg.n_kv_heads) * hd * 2 * 2 * batch    # K+V rows, bf16
    span = max(1, seq_len // max(cluster_size, 1))
    best = _BLOCK_CANDIDATES[0]
    for b in _BLOCK_CANDIDATES:
        if b * row * 2 > VMEM_BUDGET:      # ×2: double-buffered pipeline
            break
        best = b
        if b >= span:
            break
    # wide-KV configs: even the smallest candidate can blow the budget —
    # halve until the double-buffered tiles fit (floor 8)
    while best > 8 and best * row * 2 > VMEM_BUDGET:
        best //= 2
    return best


_BLOCK_F_CANDIDATES = (256, 512, 1024, 2048, 4096)


def pick_block_f(cfg: ModelConfig) -> int:
    """d_ff tile for the fused-FFN megakernel (kernels/fused_ffn).

    Each grid step streams an up tile [D, bf], an optional gate tile
    [D, bf] and a down-row tile [bf, D]; prefer the largest tile whose
    double-buffered weights fit the VMEM budget (fewer grid steps ⇒
    less fixed per-step overhead; the [B, D] activation scratch is
    batch-small and deliberately outside the model).  The call site
    fits the pick down to a divisor of the local ``d_ff`` shard
    (``_fit_block_s``).
    """
    d = cfg.d_model
    bpe = 2
    tiles = 3 if cfg.ffn_gated else 2      # up (+gate) cols + down rows
    best = _BLOCK_F_CANDIDATES[0]
    for b in _BLOCK_F_CANDIDATES:
        if b * d * tiles * bpe * 2 > VMEM_BUDGET:   # ×2: double-buffered
            break
        best = b
    while best > 8 and best * d * tiles * bpe * 2 > VMEM_BUDGET:
        best //= 2
    return best


_BLOCK_V_CANDIDATES = (512, 1024, 2048, 4096)


def pick_block_v(cfg: ModelConfig, *, batch: int = 1, k: int = 8) -> int:
    """Vocab tile for the fused LM-head/sampling kernel (kernels/fused_head).

    Each grid step streams one ``[bv, D]`` tile of the (possibly tied)
    embedding table in the model dtype; prefer the largest tile whose
    double-buffered weight stream fits the VMEM budget (fewer grid
    steps ⇒ less fixed per-step overhead).  The residency model also
    charges the ``[B, D]`` normed-input scratch (f32) and the
    ``[B, k]`` running top-k partials (f32 value + int32 index — the
    k-wide streaming selection the sampled tail folds per tile); both
    are batch-small but no longer negligible at large B × k, so they
    join the budget instead of living outside it.  The call site fits
    the pick down to a divisor of the local vocab shard
    (``_fit_block_s``)."""
    d = cfg.d_model
    bpe = 2
    fixed = batch * d * 4 + batch * k * 8    # h scratch + (val, idx) topk
    best = _BLOCK_V_CANDIDATES[0]
    for b in _BLOCK_V_CANDIDATES:
        if b * d * bpe * 2 + fixed > VMEM_BUDGET:   # ×2: double-buffered
            break
        best = b
    while best > 8 and best * d * bpe * 2 + fixed > VMEM_BUDGET:
        best //= 2
    return best


def _backend_for(cfg: ModelConfig, backend: str) -> str:
    """Resolve ``"auto"``: attention layers take the fused Pallas kernels
    (no intermediate materialization, length-clamped HBM traffic);
    attention-free architectures keep the XLA dataflow (the fusion scope
    the paper targets does not apply — DESIGN.md §4)."""
    if backend != "auto":
        return backend
    return "xla" if cfg.is_attention_free else "pallas"


def _prepack_for(backend_resolved: str, prepack) -> bool:
    """Resolve the prepack knob: ``"auto"`` (default) enables the serve
    layout whenever the Pallas backend is in play — the fully fused
    ``partial_o`` path requires it; explicit on/off is honored for both
    backends (the XLA serve layout still hoists the rank slices).
    Unknown strings raise instead of silently disabling the fast path."""
    if prepack in ("auto", None):
        return backend_resolved == "pallas"
    if isinstance(prepack, str):
        if prepack in ("on", "true", "1"):
            return True
        if prepack in ("off", "false", "0"):
            return False
        raise ValueError(f"prepack must be auto/on/off, got {prepack!r}")
    return bool(prepack)


def weight_gather_bytes_per_step(cfg: ModelConfig, *, model_axis: int,
                                 cluster_size: int, backend: str,
                                 prepack: bool,
                                 bytes_per_el: int = 2) -> float:
    """Modeled per-token ICI bytes spent on *weight-segment* gathers.

    The Level-2 Pallas path hoists Alg. 3/4's activation gathers to the
    step-invariant weight segments (DESIGN.md §2); without prepack these
    re-run every decode step.  The XLA path gathers activations instead
    (O(B·heads·hd), not counted here), and the prepacked serve layout
    gathers once at load — both read 0.  Tracked in BENCH_tpot.json so
    the perf trajectory is auditable across PRs.
    """
    if backend != "pallas" or prepack:
        return 0.0
    n = cluster_size
    if n <= 1:
        return 0.0
    hs = max(1, model_axis // n)
    d = cfg.d_model
    total = 0.0
    for kind in cfg.layer_kinds:
        if kind not in (ATTN_GLOBAL, ATTN_LOCAL):
            continue
        q_loc = max(1, cfg.n_heads // hs)
        if cfg.mla is not None:
            m = cfg.mla
            seg = (d * q_loc * (m.nope_head_dim + m.rope_head_dim) / n
                   + d * (m.kv_lora_rank + m.rope_head_dim) / n
                   + q_loc * m.nope_head_dim * m.kv_lora_rank / n)
        else:
            kv_loc = max(1, cfg.n_kv_heads // hs)
            hd = cfg.resolved_head_dim
            seg = d * (q_loc + 2 * kv_loc) * (hd / n)
            if cfg.qkv_bias:       # bq/bk/bv segments gather too
                seg += (q_loc + 2 * kv_loc) * (hd / n)
        total += prim.traffic_gather(seg * bytes_per_el, n)
    return total


def _n_dense_ffn_layers(cfg: ModelConfig) -> int:
    """Attention layers whose dense FFN the fused block tail covers
    (MoE layers keep the XLA expert dispatch; enc-dec interleaves
    cross-attention — DESIGN.md §7)."""
    if cfg.moe is not None or cfg.encoder is not None:
        return 0
    return sum(1 for k in cfg.layer_kinds if k in (ATTN_GLOBAL, ATTN_LOCAL))


def _fused_ffn_reduce_active(model_axis: int, backend: str,
                             prepack: bool) -> bool:
    """Mirror of the runtime dispatch in ``engine._fused_ffn_tail``: the
    fused tree ClusterReduce runs only on the prepacked Pallas path AND
    only for power-of-two model axes (the tree schedule's validity
    condition); otherwise the layer pays the ``psum_model`` all-reduce."""
    return (backend == "pallas" and prepack
            and model_axis > 1 and not (model_axis & (model_axis - 1)))


def ffn_psum_bytes_per_step(cfg: ModelConfig, *, model_axis: int,
                            batch: int, backend: str, prepack: bool,
                            bytes_per_el: int = 2) -> float:
    """Modeled per-step ICI bytes of the per-layer FFN activation
    all-reduce (``ctx.psum_model`` on the ``[B, D]`` down-projection
    partials; XLA's bandwidth-optimal schedule moves ``2·(N−1)·size``
    over the fabric).  The fused full-block path replaces it with ONE
    fused tree ClusterReduce per layer — this column reads 0 there and
    :func:`ffn_cluster_reduce_bytes_per_step` carries the replacement's
    traffic, so the trade stays auditable in BENCH_tpot.json.  Non-pow2
    model axes keep the psum even when prepacked (the runtime fallback
    in ``engine._fused_ffn_tail``)."""
    if model_axis <= 1 or _fused_ffn_reduce_active(model_axis, backend,
                                                   prepack):
        return 0.0
    size = batch * cfg.d_model * bytes_per_el
    return _n_dense_ffn_layers(cfg) * 2.0 * (model_axis - 1) * size


def ffn_cluster_reduce_bytes_per_step(cfg: ModelConfig, *, model_axis: int,
                                      batch: int, backend: str,
                                      prepack: bool,
                                      bytes_per_el: int = 2) -> float:
    """Modeled per-step ICI bytes of the fused ClusterReduce that
    replaces the FFN ``psum_model`` on the full-block path (the paper's
    tree schedule: ``size · log2 N · N``)."""
    if not _fused_ffn_reduce_active(model_axis, backend, prepack):
        return 0.0
    size = batch * cfg.d_model * bytes_per_el
    return (_n_dense_ffn_layers(cfg)
            * prim.traffic_reduce(size, model_axis))


def _fused_head_active(backend: str, prepack: bool) -> bool:
    """Mirror of the runtime dispatch in ``engine.decode_step``: the
    fused LM-head/sampling tail runs whenever the serve tree carries the
    head bundle — the prepacked Pallas path (``prepack.bundle_head``).
    Assumes ``build_engine_full``'s default ``fuse_head=True``; an
    ablation engine built with ``fuse_head=False`` runs the loose tail
    and pays the logits bytes this model would report as 0."""
    return backend == "pallas" and prepack


def head_hbm_logits_bytes_per_step(cfg: ModelConfig, *, model_axis: int,
                                   batch: int, backend: str, prepack: bool,
                                   bytes_per_el: int = 4) -> float:
    """Modeled per-chip HBM bytes of the ``[B, V_loc]`` logits tensor
    the unfused LM-head tail materializes every decode step — the
    single largest activation the step writes, and the one the fused
    head kernel deletes (greedy only ever needed the per-slot (max,
    argmax)).  Reads 0 on the fused path; ``bytes_per_el`` defaults to
    4 (``lm_head_logits`` pins f32 logits).  Tracked per variant in
    BENCH_tpot.json and gated against the committed baseline by
    ``scripts/check_bench.py``."""
    if _fused_head_active(backend, prepack):
        return 0.0
    v_loc = (cfg.vocab_size + model_axis - 1) // model_axis
    return float(batch * v_loc * bytes_per_el)


def head_ici_bytes_per_step(cfg: ModelConfig, *, model_axis: int,
                            batch: int, backend: str, prepack: bool,
                            bytes_per_el: int = 4, k: int = 8) -> float:
    """Modeled per-step ICI bytes of the k-wide (value, index) candidate
    tree reduce over the vocab shards (paper tree schedule; k f32
    values + k int32 indices per slot — ``k`` is the fused tail's
    candidate width ``sampling.CAND_K``; k=1 recovers the PR-5 greedy
    pair).  Identical on the fused and unfused tails by construction —
    the fused head changes WHERE the partials come from (streaming VMEM
    tiles vs an HBM logits tensor), not the collective — so a
    regression in this column means the reduce schedule or the
    candidate width itself changed."""
    if model_axis <= 1:
        return 0.0
    pair = batch * k * bytes_per_el * 2      # k × (f32 value, int32 index)
    return prim.traffic_reduce(float(pair), model_axis)


def tune_serving(cfg: ModelConfig, *, seq_len: int, batch: int,
                 model_axis: int = 16, backend: str = "auto",
                 prepack="auto",
                 table_path: Optional[str] = None) -> ServePlan:
    """Pick the full serving plan for a (config, bucket) cell.

    Consults/updates the persisted JSON table at ``table_path`` (or
    ``$REPRO_AUTOTUNE_TABLE``) keyed by
    ``name|model_axis|batch|seq_bucket|backend|prepack`` — with prepack
    RESOLVED to its boolean, so ``prepack="auto"`` and an explicit
    ``"on"`` that resolve identically share one cell — so repeated
    launches pay zero search cost.  Entries whose schema has drifted
    (e.g. a pre-prepack table) self-heal by re-tuning.
    """
    bucket = seq_bucket(seq_len)
    backend_resolved = _backend_for(cfg, backend)
    pp = _prepack_for(backend_resolved, prepack)
    key = (f"{cfg.name}|ms{model_axis}|b{batch}|s{bucket}|{backend}"
           f"|pp{int(pp)}")
    path = table_path or os.environ.get("REPRO_AUTOTUNE_TABLE")
    table = load_table(path)
    if key in table:
        try:
            return ServePlan(**table[key])
        except TypeError:          # schema drift / hand-edited entry
            pass                   # fall through and re-tune (self-heals)
    best = tune_cluster(cfg, seq_len=bucket, batch=batch,
                        model_axis=model_axis)
    plan = ServePlan(
        cluster_size=best.cluster_size,
        dataflow=best.dataflow if best.dataflow != "split_head"
        else "split_token",            # split_head is bench-only
        backend=backend_resolved,
        block_s=pick_block_s(cfg, bucket, best.cluster_size, batch),
        prepack=pp,
        block_f=pick_block_f(cfg),
        block_v=pick_block_v(cfg, batch=batch),
        est_seconds=best.est_seconds,
    )
    table[key] = asdict(plan)
    save_table(path, table)
    return plan


def load_table(path: Optional[str]) -> Dict[str, dict]:
    if not path or not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def save_table(path: Optional[str], table: Dict[str, dict]) -> None:
    if not path:
        return
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
