"""The one traffic generator: a workload file's parameters -> requests.

A workload file (``chipbench/workloads/<cell>.json``) states the
distributions; this module draws from them.  Every seed gets the same
multiset of sizes and gaps in another order: each distribution is
turned into a *deck* of ``deck`` values at the quantile midpoints
``(i + 0.5) / deck``, and every consecutive block of ``deck`` requests
takes one seed-shuffled copy of the deck.  So the work in a window does
not depend on the seed, only the order does.

Distributions (``{"dist": ...}``):

* ``lognormal``: ``median``, ``sigma``; clipped to ``[min, max]``,
  then rounded up to a multiple of ``round``;
* ``uniform``: integers in ``[min, max]``, rounded up to ``round``.

Arrivals: ``"loop": "closed"`` keeps ``slots`` requests in flight from
a backlog (no arrival times); ``"loop": "open"`` sends at ``rate``
requests per second with exponential gaps (stratified the same way).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Req:
    idx: int
    prompt: list            # token ids
    max_new: int
    due: float = 0.0        # seconds after the window opens (open loop)


def _round_up(x: float, step: int) -> int:
    return int(math.ceil(x / step) * step)


def deck(dist: dict, n: int) -> list:
    """``n`` values of ``dist`` at its quantile midpoints, ascending."""
    step = int(dist.get("round", 1))
    lo, hi = dist["min"], dist["max"]
    qs = [(i + 0.5) / n for i in range(n)]
    if dist["dist"] == "lognormal":
        z = NormalDist()
        raw = [dist["median"] * math.exp(dist["sigma"] * z.inv_cdf(q))
               for q in qs]
    elif dist["dist"] == "uniform":
        raw = [lo + q * (hi - lo) for q in qs]
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return [min(_round_up(min(max(v, lo), hi), step), hi) for v in raw]


def exp_gaps(rate: float, n: int) -> list:
    """``n`` exponential inter-arrival gaps (mean 1/rate) at quantile
    midpoints."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def _shuffled_blocks(values: list, count: int, rng) -> list:
    out: list = []
    while len(out) < count:
        out.extend(values[j] for j in rng.permutation(len(values)))
    return out[:count]


def count_for(wl: dict, seconds: float) -> int:
    """How many requests a window of ``seconds`` can use, with margin."""
    if wl["loop"] == "closed":
        return wl["slots"] + wl["backlog"]
    return int(math.ceil(wl["rate"] * seconds * 1.25)) + wl["deck"]


def make_requests(wl: dict, seed: int, seconds: float, vocab: int) -> list:
    """The requests of one run: sizes, due times and prompt tokens.

    Closed loop: the first ``slots`` requests fill the slots during
    set-up.  Their ``max_new`` is what is left of a request met at a
    random point of its decode: a deck spread evenly over
    ``1..output max``, so finishes and admissions are spread through
    the window from its start, as in a steady state, and every seed
    starts with the same tokens left to serve.
    """
    rng = np.random.default_rng(seed)
    n = count_for(wl, seconds)
    d = wl["deck"]
    prompts = _shuffled_blocks(deck(wl["prompt"], d), n, rng)
    outs = _shuffled_blocks(deck(wl["output"], d), n, rng)
    dues = [0.0] * n
    if wl["loop"] == "open":
        gaps = _shuffled_blocks(exp_gaps(wl["rate"], d), n, rng)
        t = 0.0
        for i, g in enumerate(gaps):
            t += g
            dues[i] = t
    else:
        s = wl["slots"]
        top = wl["output"]["max"]
        left = [int(math.ceil((i + 0.5) / s * top)) for i in range(s)]
        for i, j in enumerate(rng.permutation(s)):
            outs[i] = left[j]
    reqs = []
    for i in range(n):
        toks = rng.integers(1, vocab, prompts[i]).tolist()
        reqs.append(Req(i, toks, int(outs[i]), dues[i]))
    return reqs


def shapes(wl: dict) -> dict:
    """Every prompt length and output length the cell can send."""
    d = wl["deck"]
    return {"prompt": sorted(set(deck(wl["prompt"], d))),
            "output": sorted(set(deck(wl["output"], d)))}
