"""Configuration system for the ClusterFusion-TPU framework.

Every architecture is described by a :class:`ModelConfig`; every workload
shape by a :class:`ShapeConfig`.  The registry maps ``--arch`` ids to config
factories, and every config has a ``reduced()`` variant used by CPU smoke
tests (full configs are only ever lowered via ShapeDtypeStructs in the
dry-run, never allocated).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# Block kinds
# ---------------------------------------------------------------------------
ATTN_GLOBAL = "attn_global"        # full causal attention
ATTN_LOCAL = "attn_local"          # sliding-window causal attention
RECURRENT = "recurrent"            # RG-LRU (Griffin) block
RWKV6 = "rwkv6"                    # RWKV-6 time-mix block
BLOCK_KINDS = (ATTN_GLOBAL, ATTN_LOCAL, RECURRENT, RWKV6)


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts FFN configuration."""

    num_experts: int
    top_k: int
    # d_ff of each expert (the dense d_ff field is ignored for MoE layers
    # unless dense_ff_residual is set, in which case it sizes the dense path).
    expert_d_ff: int
    # Snowflake-Arctic style: a dense FFN residual in parallel with the MoE.
    dense_ff_residual: bool = False
    dense_residual_d_ff: int = 0
    # Router options
    router_softcap: float = 0.0
    capacity_factor: float = 1.25


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek Multi-head Latent Attention configuration (paper Alg. 4)."""

    kv_lora_rank: int = 512
    q_lora_rank: int = 0            # 0 => full-rank Q projection
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128


@dataclass(frozen=True)
class FrontendConfig:
    """Stub modality frontend: supplies precomputed embeddings.

    ``input_specs`` yields (num_frames_or_patches, feature_dim) bf16
    embeddings instead of raw audio/pixels — per the assignment contract.
    """

    kind: str                      # "audio" | "vision"
    num_positions: int             # frames / patches fed to the backbone
    feature_dim: int               # frontend output dim (projected to d_model)


@dataclass(frozen=True)
class EncoderConfig:
    """Encoder stack for enc-dec models (seamless-m4t)."""

    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | hybrid | ssm | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 => d_model // n_heads
    # Block pattern, repeated to cover n_layers (remainder truncated from the
    # pattern head).  Dense transformers: (ATTN_GLOBAL,).  Gemma-2:
    # (ATTN_LOCAL, ATTN_GLOBAL).  Griffin: (RECURRENT, RECURRENT, ATTN_LOCAL).
    block_pattern: Tuple[str, ...] = (ATTN_GLOBAL,)
    sliding_window: int = 4096     # for ATTN_LOCAL blocks
    # Attention options
    qkv_bias: bool = False         # Qwen-2 style
    logit_softcap: float = 0.0     # Gemma-2 final-logit softcap
    attn_softcap: float = 0.0      # Gemma-2 attention softcap
    rope_theta: float = 10000.0
    # Recurrent (RG-LRU) options
    rglru_d_state: int = 0         # 0 => d_model; Griffin uses d_model
    conv1d_width: int = 4
    # RWKV-6 options
    rwkv_head_dim: int = 64
    # FFN
    ffn_act: str = "silu"          # silu | gelu | gelu_tanh
    ffn_gated: bool = True
    # Extensions
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    frontend: Optional[FrontendConfig] = None
    encoder: Optional[EncoderConfig] = None
    tie_embeddings: bool = False
    use_post_norm: bool = False    # Gemma-2 sandwich norm
    norm_eps: float = 1e-6
    # citation string: [source; verified-tier]
    source: str = ""

    # -- derived -----------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        pat = self.block_pattern
        reps = math.ceil(self.n_layers / len(pat))
        return tuple((pat * reps)[: self.n_layers])

    @property
    def is_attention_free(self) -> bool:
        return all(k in (RECURRENT, RWKV6) for k in self.layer_kinds)

    @property
    def has_full_attention(self) -> bool:
        return any(k == ATTN_GLOBAL for k in self.layer_kinds)

    @property
    def max_decode_context(self) -> int:
        """Largest KV context any single layer must hold at decode time.

        Attention-free / local-attention layers bound their own context.
        """
        ctx = 0
        for k in self.layer_kinds:
            if k == ATTN_GLOBAL:
                return -1  # unbounded (grows with sequence)
            if k == ATTN_LOCAL:
                ctx = max(ctx, self.sliding_window)
        return ctx

    def param_count(self) -> int:
        """Analytical parameter count (used for MODEL_FLOPS = 6·N·D)."""
        d, hd = self.d_model, self.resolved_head_dim
        nq, nkv = self.n_heads, self.n_kv_heads
        total = self.vocab_size * d  # embedding
        if not self.tie_embeddings:
            total += self.vocab_size * d  # lm head
        for kind in self.layer_kinds:
            total += 2 * d  # two RMSNorm scales
            if kind in (ATTN_GLOBAL, ATTN_LOCAL):
                if self.mla is not None:
                    m = self.mla
                    qdim = nq * (m.nope_head_dim + m.rope_head_dim)
                    total += d * qdim                       # W_Q (full rank)
                    total += d * (m.kv_lora_rank + m.rope_head_dim)  # W_DKV
                    total += m.kv_lora_rank * nq * (m.nope_head_dim + m.v_head_dim)
                    total += nq * m.v_head_dim * d          # W_O
                else:
                    total += d * (nq * hd) + 2 * d * (nkv * hd)  # QKV
                    total += (nq * hd) * d                   # O
                    if self.qkv_bias:
                        total += (nq + 2 * nkv) * hd
            elif kind == RECURRENT:
                ds = self.rglru_d_state or d
                total += 2 * d * ds          # input/gate linear
                total += ds * self.conv1d_width
                total += 2 * ds              # RG-LRU a/gate params
                total += 2 * ds * ds // max(1, ds // ds)  # recurrent gates (approx)
                total += ds * d              # out proj
            elif kind == RWKV6:
                total += 4 * d * d           # r,k,v,g projections
                total += d * d               # output proj
                total += 6 * d               # time-mix/decacy params (approx)
            # FFN
            if self.moe is not None and kind != RECURRENT:
                m = self.moe
                per_expert = (3 if self.ffn_gated else 2) * d * m.expert_d_ff
                total += m.num_experts * per_expert
                total += d * m.num_experts   # router
                if m.dense_ff_residual:
                    total += (3 if self.ffn_gated else 2) * d * m.dense_residual_d_ff
            else:
                total += (3 if self.ffn_gated else 2) * d * self.d_ff
        if self.encoder is not None:
            e = self.encoder
            ehd = d // e.n_heads
            per = 2 * d + d * (e.n_heads * ehd) + 2 * d * (e.n_kv_heads * ehd) \
                + (e.n_heads * ehd) * d + (3 if self.ffn_gated else 2) * d * e.d_ff
            total += e.n_layers * per
            # decoder cross-attention (one per decoder layer)
            total += self.n_layers * (d * (nq * hd) + 2 * d * (nkv * hd) + (nq * hd) * d + d)
        if self.frontend is not None:
            total += self.frontend.feature_dim * d  # projection into backbone
        return total

    def active_param_count(self) -> int:
        """Active parameters per token (MoE: only top-k experts count)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        per_expert = (3 if self.ffn_gated else 2) * self.d_model * m.expert_d_ff
        inactive = (m.num_experts - m.top_k) * per_expert * sum(
            1 for k in self.layer_kinds if k != RECURRENT
        )
        return self.param_count() - inactive


# ---------------------------------------------------------------------------
# Workload shapes
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    mode: str                      # "train" | "prefill" | "decode"

    @property
    def is_decode(self) -> bool:
        return self.mode == "decode"


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def shapes_for(cfg: ModelConfig) -> List[ShapeConfig]:
    """The shape cells that apply to an architecture.

    ``long_500k`` requires sub-quadratic context handling: run only when no
    layer keeps an unbounded global-attention KV cache (SSM / hybrid /
    local-attention archs).  Skips are recorded in DESIGN.md §4.
    """
    out = []
    for s in SHAPES.values():
        if s.name == "long_500k" and cfg.max_decode_context < 0:
            continue  # pure/partial full-attention arch: unbounded KV at 500k
        out.append(s)
    return out


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, Callable[[], ModelConfig]] = {}


def register(name: str):
    def deco(fn: Callable[[], ModelConfig]):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        # import side-effect registration
        from repro import configs as _c  # noqa: F401
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def list_archs() -> List[str]:
    from repro import configs as _c  # noqa: F401
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Reduced configs for CPU smoke tests
# ---------------------------------------------------------------------------
def depth_cut(cfg: ModelConfig, n_layers: int) -> ModelConfig:
    """``cfg`` at its published widths with only the depth cut to
    ``n_layers`` — a whole number of block-pattern periods, so every layer
    kind the model has is kept.  The name records the cut."""
    period = len(cfg.block_pattern)
    if n_layers <= 0 or n_layers % period or n_layers > cfg.n_layers:
        raise ValueError(f"{cfg.name}: cannot cut {cfg.n_layers} layers to "
                         f"{n_layers} (pattern period {period})")
    return dataclasses.replace(cfg, name=f"{cfg.name}-L{n_layers}",
                               n_layers=n_layers)


def reduced(cfg: ModelConfig, *, d_model: int = 128, n_layers: int = 0,
            vocab: int = 512) -> ModelConfig:
    """Shrink a config to smoke-test size, preserving its structure.

    Keeps the family, block pattern, GQA ratio, MoE top-k / dense-residual
    topology, MLA/frontend/encoder presence — just with tiny dims.
    """
    pat = cfg.block_pattern
    nl = n_layers or max(len(pat), 2)
    # keep the q:kv ratio
    n_heads = 4
    n_kv = max(1, n_heads // max(1, cfg.q_per_kv))
    head_dim = max(8, d_model // n_heads)
    kw = dict(
        name=cfg.name + "-smoke",
        family=cfg.family,
        n_layers=nl,
        d_model=d_model,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        head_dim=head_dim,
        d_ff=d_model * 3,
        vocab_size=vocab,
        block_pattern=pat,
        sliding_window=min(cfg.sliding_window, 64),
        qkv_bias=cfg.qkv_bias,
        logit_softcap=cfg.logit_softcap,
        attn_softcap=cfg.attn_softcap,
        ffn_act=cfg.ffn_act,
        ffn_gated=cfg.ffn_gated,
        tie_embeddings=cfg.tie_embeddings,
        rglru_d_state=0,
        conv1d_width=cfg.conv1d_width,
        rwkv_head_dim=16,
        source=cfg.source,
    )
    if cfg.moe is not None:
        # capacity_factor = E ⇒ no token ever drops: capacity dropping is
        # data-layout dependent (per-shard cumsum order), which would break
        # the sharded-vs-oracle equivalence smoke tests.  Dropping semantics
        # get their own dedicated unit test.
        kw["moe"] = MoEConfig(
            num_experts=8, top_k=min(2, cfg.moe.top_k),
            expert_d_ff=d_model * 2,
            dense_ff_residual=cfg.moe.dense_ff_residual,
            dense_residual_d_ff=d_model if cfg.moe.dense_ff_residual else 0,
            capacity_factor=8.0,
        )
    if cfg.mla is not None:
        kw["mla"] = MLAConfig(kv_lora_rank=32, rope_head_dim=8,
                              nope_head_dim=16, v_head_dim=16)
    if cfg.frontend is not None:
        kw["frontend"] = FrontendConfig(cfg.frontend.kind, 16, 64)
    if cfg.encoder is not None:
        kw["encoder"] = EncoderConfig(n_layers=2, n_heads=4, n_kv_heads=4,
                                      d_ff=d_model * 3)
    return ModelConfig(**kw)
