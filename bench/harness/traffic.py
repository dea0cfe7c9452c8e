"""One generator for every traffic mix; a mix is a JSON file of parameters.

Every seed serves the same set of requests in its own order: prompt
lengths, output lengths and gaps between arrivals are taken at evenly
spaced quantiles of their distributions, and the run's seed shuffles each
of the three lists and draws every token id (and, elsewhere, the
weights). So two seeds ask for the same amount of work, in another
order and with other contents, and no seed replays another's schedule.

A mix (``bench/traffic/<name>.json``)::

  {"loop": "open", "rate_per_s": 2.0,           # Poisson arrivals
   "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.7,
              "min": 32, "max": 768},
   "output": {"dist": "uniform", "min": 16, "max": 256},
   "engine": {"n_slots": 4, "max_len": 2048}}    # optional overrides

or ``"loop": "closed", "clients": 4, "requests": 32`` for callers that
each wait for their reply: the same ``requests`` lengths are served over
and over, each pass in a new order with new token ids. Requests are
greedy.

``"strata": k`` (optional) deals the prompt and the output lengths out in
rounds of ``k``: the sorted lengths are cut into ``k`` bands of equal
count, and each round takes one length of every band, in the seed's
order. Then every ``k`` requests in a row ask for about the same work,
and so does any window that holds a few rounds, whatever the seed.
Without it each list is one shuffle of the whole set.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

# passes over a closed loop's set of lengths (more than a window finishes)
CLOSED_PASSES = 8


@dataclass
class Request:
    index: int
    prompt: np.ndarray  # int32 token ids
    max_tokens: int
    due: float  # seconds after the window opens (open loop); 0 for closed


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``spec``."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] + 1 - spec["min"])
    elif spec["dist"] == "fixed":
        v = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo, hi = spec.get("min", 1), spec.get("max", math.inf)
    return np.clip(np.floor(v), lo, hi).astype(int)


def _deal(rng, lengths: np.ndarray, strata: int) -> np.ndarray:
    """``lengths`` in the seed's order, in rounds of ``strata`` (one
    length of each band of the sorted lengths per round)."""
    if strata <= 1:
        return rng.permutation(lengths)
    n, s = len(lengths), np.sort(lengths)
    bands = [rng.permutation(s[b * n // strata:(b + 1) * n // strata])
             for b in range(strata)]
    rounds = [rng.permutation([b[r] for b in bands if r < len(b)])
              for r in range(max(len(b) for b in bands))]
    return np.concatenate(rounds).astype(lengths.dtype)


def request_count(mix: dict, seconds: float) -> int:
    """How many requests a run of ``seconds`` draws: in an open loop as
    many as fall due inside the window, whatever the seed's order."""
    if mix["loop"] == "open":
        return max(1, math.floor(mix["rate_per_s"] * seconds))
    return mix["requests"] * CLOSED_PASSES


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    n = request_count(mix, seconds)
    rng = np.random.default_rng([int(seed), 0x5C4ED])
    k = mix.get("strata", 1)
    if mix["loop"] == "closed":
        per = mix["requests"]
        prompts = np.concatenate([_deal(
            rng, _quantiles(mix["prompt"], per), k)
            for _ in range(CLOSED_PASSES)])
        outputs = np.concatenate([_deal(
            rng, _quantiles(mix["output"], per), k)
            for _ in range(CLOSED_PASSES)])
    else:
        prompts = _deal(rng, _quantiles(mix["prompt"], n), k)
        outputs = _deal(rng, _quantiles(mix["output"], n), k)
    if mix["loop"] == "open":
        # exponential gaps at evenly spaced quantiles, shuffled, rescaled
        # so the n arrivals span n / rate seconds: all due in the window
        u = (np.arange(n) + 0.5) / n
        gaps = rng.permutation(-np.log1p(-u))
        gaps *= (n / mix["rate_per_s"]) / gaps.sum()
        due = np.cumsum(gaps) - gaps[0]
    elif mix["loop"] == "closed":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    ids = np.random.default_rng(seed)
    return [Request(i, ids.integers(0, vocab, int(p)).astype(np.int32),
                    int(o), float(t))
            for i, (p, o, t) in enumerate(zip(prompts, outputs, due))]

