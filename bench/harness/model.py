"""Seeded weights and the plain reference of the dense GQA decoder.

The benchmark makes the weights, not the program: :func:`layer_weights`
and :func:`table_weights` draw every leaf from ``--seed`` in bfloat16, the
type they are served in.  :func:`program_params` hands them to the
program in its loading layout (one jitted call on the device); the
reference regenerates them layer by layer from the same seed, so it takes
nothing the program has made.

The reference follows the configuration's equations in float32 at
``highest`` matmul precision, with no kernel, cache or batching:

    x = E[tokens] (· sqrt(d_model) where the table is tied)
    per layer:  h = rms(x)·(1 + ln1);  q, k, v = h·Wq, h·Wk, h·Wv
                rotary (rotate-half, theta) on q and k; causal softmax of
                q·k / sqrt(head_dim), query head i reading kv head
                i // (heads / kv_heads); x += (attn)·Wo
                h = rms(x)·(1 + ln2); x += act(h·Wg)·(h·Wi)·Wo_ffn (gated)
                                      or act(h·Wi)·Wo_ffn (not gated)
    logits = rms(x)·(1 + final_norm) · headᵀ

The control (:func:`forward_hidden` with ``fp8=True``) is the same
reference with every weight matrix rounded to float8 (e4m3), one scale
per output channel: the step below the configuration's bfloat16 that
would tempt a later change."""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .widths import Widths

NORM_SCALE = 0.1     # norm scales are drawn N(0, 0.1): they are exercised
CAND_K = 8           # candidates the fused head keeps per slot


def root_key(seed: int):
    """A key from any non-negative seed, 64-bit ones included."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _normal(key, shape, scale, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def layer_weights(w: Widths, key, layer) -> Dict[str, jax.Array]:
    """One layer's weights; ``layer`` may be traced."""
    k = jax.random.fold_in(key, layer)
    ks = [jax.random.fold_in(k, j) for j in range(9)]
    d, hd, f = w.d_model, w.head_dim, w.d_ff
    s_in = 1.0 / math.sqrt(d)
    out = {
        "ln1": _normal(ks[0], (d,), NORM_SCALE, jnp.float32),
        "ln2": _normal(ks[1], (d,), NORM_SCALE, jnp.float32),
        "wq": _normal(ks[2], (d, w.n_heads, hd), s_in),
        "wk": _normal(ks[3], (d, w.n_kv_heads, hd), s_in),
        "wv": _normal(ks[4], (d, w.n_kv_heads, hd), s_in),
        "wo": _normal(ks[5], (w.n_heads * hd, d),
                      1.0 / math.sqrt(w.n_heads * hd)),
        "w_in": _normal(ks[6], (d, f), s_in),
        "w_out": _normal(ks[7], (f, d), 1.0 / math.sqrt(f)),
    }
    if w.gated:
        out["w_gate"] = _normal(ks[8], (d, f), s_in)
    return out


def table_weights(w: Widths, key) -> Dict[str, jax.Array]:
    """Embedding table, final norm scale and (untied) head."""
    k = jax.random.fold_in(key, 1 << 20)
    out = {"embed": _normal(jax.random.fold_in(k, 0),
                            (w.vocab, w.d_model), 0.02),
           "final_norm": _normal(jax.random.fold_in(k, 1), (w.d_model,),
                                 NORM_SCALE, jnp.float32)}
    if not w.tied:
        out["lm_head"] = _normal(jax.random.fold_in(k, 2),
                                 (w.vocab, w.d_model),
                                 1.0 / math.sqrt(w.d_model))
    return out


_layer_weights = jax.jit(layer_weights, static_argnums=0)
_table_weights = jax.jit(table_weights, static_argnums=0)


def program_params(w: Widths, seed: int, cfg, lay, shardings):
    """The seeded weights in the program's loading layout (its logical
    tree through ``to_device_major``), made on the device in one jitted
    call with the program's own shardings."""
    from repro.models.attention import AttnParams
    from repro.models.layers import FFNParams
    from repro.models.transformer import to_device_major

    def make(key):
        lw = jax.vmap(lambda i: layer_weights(w, key, i))(
            jnp.arange(w.layers))
        tw = table_weights(w, key)
        blk = {"ln1": lw["ln1"], "ln2": lw["ln2"],
               "attn": AttnParams(wq=lw["wq"], wk=lw["wk"], wv=lw["wv"],
                                  wo=lw["wo"]),
               "ffn": FFNParams(w_in=lw["w_in"], w_out=lw["w_out"],
                                w_gate=lw.get("w_gate"))}
        logical = {"embed": tw["embed"], "final_norm": tw["final_norm"],
                   "blocks": [blk], "tail": []}
        if "lm_head" in tw:
            logical["lm_head"] = tw["lm_head"]
        return to_device_major(cfg, lay, logical)

    # the key is an argument, not a constant: one program for every seed
    return jax.jit(make, out_shardings=shardings)(root_key(seed))


# ---------------------------------------------------------------------------
# Reference
# ---------------------------------------------------------------------------
FP8_MAX = 448.0      # largest finite float8_e4m3fn


def _fp8(x: jax.Array, axis) -> jax.Array:
    """float8 (e4m3) rounding, one scale per slice reduced over ``axis``
    (the input dimensions) mapping its largest magnitude to the largest
    finite fp8 value; returned dequantized in float32."""
    x = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _act(name):
    return {"silu": jax.nn.silu,
            "relu2": lambda v: jnp.square(jax.nn.relu(v)),
            "gelu": jax.nn.gelu}[name]


@partial(jax.jit, static_argnames=("w", "act", "theta", "eps", "fp8"))
def _layer(x, lw, *, w: Widths, act: str, theta: float, eps: float,
           fp8: bool):
    with jax.default_matmul_precision("highest"):
        f32 = (lambda a, ax: _fp8(a, ax)) if fp8 else \
            (lambda a, ax: a.astype(jnp.float32))
        n, S, d = x.shape
        hd, nh, nkv = w.head_dim, w.n_heads, w.n_kv_heads
        h = _rms(x, lw["ln1"], eps)
        q = jnp.einsum("nsd,dqh->nsqh", h, f32(lw["wq"], 0))
        k = jnp.einsum("nsd,dkh->nskh", h, f32(lw["wk"], 0))
        v = jnp.einsum("nsd,dkh->nskh", h, f32(lw["wv"], 0))
        half = hd // 2
        freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
        c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

        def rope(t):
            t1, t2 = t[..., :half], t[..., half:]
            return jnp.concatenate([t1 * c - t2 * s, t2 * c + t1 * s], -1)
        q, k = rope(q), rope(k)
        q = q.reshape(n, S, nkv, nh // nkv, hd)
        sc = jnp.einsum("nqkgh,npkh->nkgqp", q, k) / math.sqrt(hd)
        causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        sc = jnp.where(causal, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("nkgqp,npkh->nqkgh", p, v).reshape(n, S, nh * hd)
        x = x + o @ f32(lw["wo"], 0)
        h = _rms(x, lw["ln2"], eps)
        up = h @ f32(lw["w_in"], 0)
        if "w_gate" in lw:
            up = _act(act)(h @ f32(lw["w_gate"], 0)) * up
        else:
            up = _act(act)(up)
        return x + up @ f32(lw["w_out"], 0)


def _embed(table, tokens, tied: bool, d: int, fp8: bool):
    t = _fp8(table, 1) if fp8 else table.astype(jnp.float32)
    x = jnp.take(t, tokens, axis=0)
    return x * math.sqrt(d) if tied else x


@partial(jax.jit, static_argnames=("fp8",))
def _head_stats(hid, table, tok, *, fp8: bool):
    """Per position: the top-``CAND_K`` logits (descending), the argmax,
    and the logit of ``tok``."""
    with jax.default_matmul_precision("highest"):
        t = _fp8(table, 1) if fp8 else table.astype(jnp.float32)
        logits = hid @ t.T
        top_v, top_i = jax.lax.top_k(logits, CAND_K)
        at = jnp.take_along_axis(logits, tok[:, None], axis=1)[:, 0]
        return top_v, top_i[:, 0], at


@jax.jit
def _logit_at(hid, table, ids):
    with jax.default_matmul_precision("highest"):
        rows = jnp.take(table, ids, axis=0).astype(jnp.float32)
        return jnp.sum(hid * rows, axis=-1)


def forward_hidden(w: Widths, model: dict, seed: int, tokens: np.ndarray,
                   fp8: bool = False) -> jax.Array:
    """Final-normed hidden states ``[n, S, d]`` of padded sequences
    ``tokens [n, S]``, computed layer by layer from the seed."""
    key = root_key(seed)
    tw = _table_weights(w, key)
    x = _embed(tw["embed"], jnp.asarray(tokens, jnp.int32), w.tied,
               w.d_model, fp8)
    for layer in range(w.layers):
        x = _layer(x, _layer_weights(w, key, layer), w=w,
                   act=model["hidden_act"],
                   theta=float(model["rope_theta"]),
                   eps=float(model["rms_norm_eps"]), fp8=fp8)
    return _rms(x, tw["final_norm"], float(model["rms_norm_eps"])), tw


def head_table(w: Widths, tw) -> jax.Array:
    return tw["embed"] if w.tied else tw["lm_head"]


def position_stats(w: Widths, hid, tw, rows, pos, tok, fp8: bool = False,
                   chunk: int = 256) -> Tuple[np.ndarray, ...]:
    """Head statistics at positions ``(rows[i], pos[i])`` of ``hid`` with
    the served token ``tok[i]``: (top values [m, K], argmax [m], logit of
    tok [m], hidden rows [m, d])."""
    table = head_table(w, tw)
    h = hid[jnp.asarray(rows), jnp.asarray(pos)]
    tv, ti, at = [], [], []
    for a in range(0, h.shape[0], chunk):
        v, i, t = _head_stats(h[a:a + chunk], table,
                              jnp.asarray(tok[a:a + chunk], jnp.int32),
                              fp8=fp8)
        tv.append(np.asarray(v))
        ti.append(np.asarray(i))
        at.append(np.asarray(t))
    return np.concatenate(tv), np.concatenate(ti), np.concatenate(at), h


def logit_at(w: Widths, tw, h, ids) -> np.ndarray:
    return np.asarray(_logit_at(h, head_table(w, tw),
                                jnp.asarray(ids, jnp.int32)))
