"""One general generator for every traffic mix file in ``bench/traffic``.

A mix states three groups of requests, each with a prompt-length and an
output-length distribution:

* ``in_flight``: requests already decoding when the window opens (a
  saturated server's steady state); set-up admits them.
* ``backlog``: requests already queued when the window opens.
* ``arrivals``: open-loop arrivals during the window at ``rate`` req/s;
  ``process`` "poisson" draws exponential gaps.

Every seed gets the same multiset of sizes and gaps, in another order:
each distribution is read at ``count`` fixed quantiles, and the seed only
permutes them.  So two seeds do the same work, and a seed changes which
request gets which size and when."""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Planned:
    """One request as the generator plans it: ``due`` is seconds after the
    window opens (negative: it was due before the window)."""
    rid: int
    group: str
    due: float
    prompt: List[int]
    max_new: int


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` values of ``dist`` at the quantiles (i + 0.5) / n, clipped to
    [min, max] and rounded to whole tokens."""
    if n <= 0:
        return np.zeros((0,), np.int64)
    u = (np.arange(n) + 0.5) / n
    kind = dist["kind"]
    if kind == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown distribution kind {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", math.inf)
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def gaps(process: str, rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps (seconds) at ``rate`` per second."""
    u = (np.arange(n) + 0.5) / n
    if process == "poisson":
        return -np.log1p(-u) / rate
    raise ValueError(f"unknown arrival process {process!r}")


def _rng(seed: int, salt: int) -> np.random.Generator:
    # seeds may exceed 32 bits; SeedSequence takes any non-negative int
    return np.random.default_rng(np.random.SeedSequence([int(seed), salt]))


def plan(mix: dict, seed: int, seconds: float, vocab: int) -> List[Planned]:
    """The requests of one run: in-flight, backlog, then window arrivals
    (those due before ``seconds``), in due order within each group."""
    out: List[Planned] = []
    groups = [("in_flight", mix.get("in_flight")),
              ("backlog", mix.get("backlog")),
              ("arrivals", mix.get("arrivals"))]
    tok_rng = _rng(seed, 1)
    for salt, (name, g) in enumerate(groups, start=10):
        if not g:
            continue
        if name == "arrivals":
            n = int(math.ceil(g["rate"] * seconds))
            dues = np.cumsum(_rng(seed, salt).permutation(
                gaps(g["process"], g["rate"], n)))
            # the gaps' multiset has a fixed sum; stretch it so that the
            # last of the n arrivals is due half a gap before the close,
            # so every seed sends the same n requests in the window
            dues = dues * (seconds * (n - 0.5) / n / float(dues[-1]))
        else:
            n = int(g["count"])
            dues = np.full((n,), -1.0 if name == "in_flight" else 0.0)
        rng = _rng(seed, salt + 100)
        prompts = rng.permutation(quantiles(g["prompt"], n))
        outputs = rng.permutation(quantiles(g["output"], n))
        for i in range(n):
            toks = tok_rng.integers(0, vocab, size=int(prompts[i]))
            out.append(Planned(rid=len(out), group=name, due=float(dues[i]),
                               prompt=toks.astype(np.int64).tolist(),
                               max_new=int(outputs[i])))
    return out
