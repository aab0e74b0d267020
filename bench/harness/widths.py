"""Work counts from a configuration's published widths.

Everything here is plain arithmetic on the configuration file's numbers,
so it reads the same work whatever implements the model.  ``step_mfu``
counts model FLOPs of the tokens actually served (real prompt tokens and
generated tokens, never padding); ``decode_roofline`` counts the least
bytes one decode step must move: every weight once, the head once, and
the K/V cache at each active slot's live length."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable


@dataclass(frozen=True)
class Widths:
    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool
    tied: bool
    weight_bytes: int = 2          # bf16 weights
    kv_bytes: int = 2              # bf16 K/V cache

    @classmethod
    def from_config(cls, c: dict) -> "Widths":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                   vocab=c["vocab_size"], gated=c["gated_ffn"],
                   tied=c["tie_word_embeddings"])

    # -- parameters -------------------------------------------------------
    @property
    def attn_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return d * hd * (2 * self.n_heads + 2 * self.n_kv_heads)

    @property
    def ffn_params(self) -> int:
        return (3 if self.gated else 2) * self.d_model * self.d_ff

    @property
    def layer_params(self) -> int:
        """Matmul weights plus the two norm scales of one layer."""
        return self.attn_params + self.ffn_params + 2 * self.d_model

    @property
    def head_params(self) -> int:
        """The LM head: the tied embedding table or the untied head."""
        return self.vocab * self.d_model

    @property
    def used_params(self) -> int:
        """Parameters a token's forward pass multiplies with: every layer
        and the head (the embedding lookup is a gather, and a tied table
        is counted once, as the head)."""
        return self.layers * self.layer_params + self.head_params

    @property
    def weight_bytes_total(self) -> int:
        """Bytes of every weight a decode step must read once."""
        return (self.layers * self.layer_params + self.head_params
                + self.d_model) * self.weight_bytes

    # -- FLOPs --------------------------------------------------------------
    def attn_flops(self, live: int) -> int:
        """Scores and weighted values of one token over ``live`` cached
        positions, all layers: 2·2·heads·head_dim·live per layer."""
        return 4 * self.n_heads * self.head_dim * live * self.layers

    @property
    def layer_matmul_flops(self) -> int:
        return 2 * self.layers * (self.attn_params + self.ffn_params)

    def prompt_flops(self, prompt_len: int) -> int:
        """A prompt of ``prompt_len`` real tokens: every position through
        every layer at its causal length, and the head once (the first
        generated token's logits)."""
        n = prompt_len
        return (n * self.layer_matmul_flops
                + self.attn_flops(n * (n + 1) // 2)
                + 2 * self.head_params)

    def decode_flops(self, live: int) -> int:
        """One generated token whose input sits at a cache of ``live``
        positions (it attends ``live + 1`` with itself)."""
        return (self.layer_matmul_flops + self.attn_flops(live + 1)
                + 2 * self.head_params)

    # -- bytes --------------------------------------------------------------
    def kv_bytes_per_position(self) -> int:
        return 2 * self.n_kv_heads * self.head_dim * self.kv_bytes \
            * self.layers

    def decode_step_bytes(self, lives: Iterable[int]) -> int:
        """Least bytes of one decode step over active slots whose caches
        hold ``lives`` positions: each weight once, the K/V read at each
        live length and the new token's K/V written."""
        kv = sum(int(n) + 1 for n in lives) * self.kv_bytes_per_position()
        return self.weight_bytes_total + kv

    def decode_step_flops(self, lives: Iterable[int]) -> int:
        return sum(self.decode_flops(int(n)) for n in lives)
