"""The one traffic generator: requests and arrivals from a mix file and a seed.

A mix file (``bench/traffic/<mix>.json``) gives a loop kind and two
length distributions.  Lengths are drawn from fixed quantile grids of
those distributions, so every seed serves the same multiset of sizes in
another order; the seed picks the order, the prompt tokens and (for the
open loop) the arrival times.  That keeps the work of a run fixed while
its order varies.

* ``rate``  - an open loop: ``round(rate_per_s * seconds)`` arrivals,
  each uniform in the window (a Poisson process given its count), timed
  from when it was due.
* ``batch`` - a backlog: requests are always waiting.  The first
  ``max_batch`` take residual lengths (a uniform share of a drawn
  prompt, and the grid's output nearest below a uniform share of a
  drawn output), so the slots hold requests of mixed age instead of one
  synchronized generation.  The mix's ``open`` says when
  the window opens: once they are ``admitted``, or once all of them are
  ``prefilled`` (where the steady state decodes far more than it
  prefills).

Output lengths, residual ones too, come from a grid of ``output_points``
values because the engine's harvest compiles one small program per
finished-stream length: the set-up warms each of them (see
``run.warm_up``), the same programs for every seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

SEED_MIX = 0x5EED   # separates the sub-streams drawn from one --seed


@dataclass(frozen=True)
class Request:
    index: int          # position in the run's sequence
    due: float          # seconds after the window opens (rate); 0 (batch)
    prompt_len: int
    output_len: int


def quantile_grid(dist: dict, k: int) -> list[int]:
    """``k`` mid-quantiles of a clipped lognormal, rounded to tokens."""
    nd = NormalDist()
    mu = math.log(dist["median"])
    out = []
    for i in range(k):
        x = math.exp(mu + dist["sigma"] * nd.inv_cdf((i + 0.5) / k))
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**64, SEED_MIX, stream])


def output_lengths(mix: dict) -> list[int]:
    """The output lengths of the mix's grid."""
    return sorted(set(quantile_grid(mix["output"], mix["output_points"])))


def requests(mix: dict, seed: int, seconds: float,
             max_batch: int) -> list[Request]:
    """The run's requests in the order they are offered."""
    if mix["loop"] == "rate":
        n = max(1, round(mix["rate_per_s"] * seconds))
    else:
        n = mix["requests"]
    prompts = quantile_grid(mix["prompt"], n)
    grid = quantile_grid(mix["output"], mix["output_points"])
    outputs = [grid[i % len(grid)] for i in range(n)]
    prompts = [prompts[i] for i in _rng(seed, 1).permutation(n)]
    outputs = [outputs[i] for i in _rng(seed, 2).permutation(n)]
    if mix["loop"] == "rate":
        due = np.sort(_rng(seed, 3).uniform(0.0, seconds, n)).tolist()
        return [Request(i, due[i], prompts[i], outputs[i]) for i in range(n)]
    res = _rng(seed, 4)
    out = []
    for i in range(n):
        p, o = prompts[i], outputs[i]
        if i < max_batch:
            p = max(1, math.ceil(res.uniform() * p))
            share = res.uniform() * o
            o = max([g for g in grid if g <= share], default=min(grid))
        out.append(Request(i, 0.0, p, o))
    return out


def prompt_tokens(req: Request, seed: int, vocab: int) -> list[int]:
    """The prompt of one request: uniform token ids in [1, vocab)."""
    rng = np.random.default_rng([seed % 2**64, SEED_MIX, 5, req.index])
    return rng.integers(1, vocab, size=req.prompt_len).tolist()


def max_total_len(mix: dict) -> int:
    """The longest prompt plus the longest output the mix can draw."""
    return mix["prompt"]["max"] + mix["output"]["max"]
