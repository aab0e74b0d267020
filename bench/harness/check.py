"""The comparison that decides ``correct``: served tokens and the fused
head's candidate logits against the plain reference (harness/model.py).

Two numbers per run, each with the limit the configuration file states:

* ``logit_gap``: over every served token of the sampled requests, the
  widest gap by which the token's reference logit lies below the
  reference's best logit at that position (0 when every served token is
  the reference's argmax).  Valid for greedy requests, which all are.
* ``cand_err``: over every decode step of those requests, the largest
  absolute difference between the fused head's sorted top-``K`` logits
  (the engine's ``cand_v`` stash) and the reference's top-``K``.

With ``control=True`` the fp8 reference is also run on the same prompts
and tokens and gives ``control_gap`` (the reference gap of the token the
fp8 model puts first) and ``control_cand_err`` (its top-``K`` against
the reference's).  :func:`control_verdict` judges those two numbers by
the same limits, as if the fp8 model had served the run."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import model as M
from .widths import Widths


def sample(finished: List[dict], seed: int, n: int, max_tokens: int
           ) -> List[dict]:
    """The requests to compare, drawn from the seed: the one with the most
    served tokens, then others in a seeded order while the served tokens
    stay under ``max_tokens`` (at least the first is always taken)."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r["tokens"]), r["rid"]))
    rest = order[1:]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    rest = [rest[i] for i in rng.permutation(len(rest))]
    out, total = [order[0]], len(order[0]["tokens"])
    for r in rest:
        if len(out) >= n:
            break
        if total + len(r["tokens"]) <= max_tokens:
            out.append(r)
            total += len(r["tokens"])
    return out


def compare(w: Widths, model: dict, seed: int, reqs: List[dict],
            control: bool = False, bucket: int = 512) -> Dict[str, float]:
    """``reqs``: dicts with ``prompt``, ``tokens`` (served) and ``cands``
    (``[len(tokens) - 1, K]`` candidate rows of the decode steps, or
    None)."""
    seqs = [list(r["prompt"]) + list(r["tokens"][:-1]) for r in reqs]
    S = max(len(s) for s in seqs)
    S = -(-S // bucket) * bucket
    toks = np.zeros((len(seqs), S), np.int32)
    rows, pos, tok, dec = [], [], [], []
    for i, (r, s) in enumerate(zip(reqs, seqs)):
        toks[i, :len(s)] = s
        p = len(r["prompt"])
        for j, t in enumerate(r["tokens"]):
            rows.append(i)
            pos.append(p - 1 + j)
            tok.append(t)
            dec.append(j >= 1)
    rows, pos, tok = np.array(rows), np.array(pos), np.array(tok)
    dec = np.array(dec)
    hid, tw = M.forward_hidden(w, model, seed, toks)
    top_v, _, at, h = M.position_stats(w, hid, tw, rows, pos, tok)
    del hid
    out = {"served_tokens": float(len(tok)),
           "decode_steps": float(dec.sum()),
           "logit_gap": float(np.max(top_v[:, 0] - at))}
    cands = [r["cands"] for r in reqs if r.get("cands") is not None]
    if cands:
        got = np.concatenate(cands, axis=0)
        out["cand_err"] = float(np.max(np.abs(got - top_v[dec])))
    if control:
        hid_c, tw_c = M.forward_hidden(w, model, seed, toks, fp8=True)
        cv, ci, _, _ = M.position_stats(w, hid_c, tw_c, rows, pos, tok,
                                        fp8=True)
        del hid_c
        ref_at = M.logit_at(w, tw, h, ci)
        out["control_gap"] = float(np.max(top_v[:, 0] - ref_at))
        out["control_cand_err"] = float(np.max(np.abs(cv[dec]
                                                      - top_v[dec])))
    return out


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True when every limited number is present, finite and within its
    limit."""
    ok = True
    for name, lim in limits.items():
        v = values.get(name)
        if v is None or not np.isfinite(v) or v > lim:
            ok = False
    return ok


CONTROL_NAMES = {"logit_gap": "control_gap", "cand_err": "control_cand_err"}


def control_verdict(values: Dict[str, float], limits: Dict[str, float]
                    ) -> bool:
    """:func:`verdict` on the control's numbers in the program's place."""
    return verdict({name: values.get(c) for name, c in CONTROL_NAMES.items()
                    if c in values}, limits)
