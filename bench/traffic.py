"""The one traffic generator: every mix is a data file it reads.

A mix (``bench/traffic/<name>.json``) gives the loop, the length
distributions and, for an open loop, the arrival process::

    {"loop": "open", "rate_per_s": 0.4, "period_s": 51,
     "gaps": {"dist": "gamma", "shape": 0.25},
     "prompt_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                       "min": 16, "max": 512},
     "output_tokens": {"dist": "uniform", "min": 8, "max": 256},
     "layout_seed": 1}

    {"loop": "closed", "clients": 8, "requests": 64, ...}

The sizes and the arrival gaps sit at evenly spaced quantiles of their
distributions, in an order fixed by the file's ``layout_seed``, so every
run seed gets the same set of them: the run's seed picks where the
cyclic sequence starts (a rotation, so bursts stay
whole) and the token ids.  An open loop's gaps are scaled so that one
period of ``rate_per_s * period_s`` requests spans exactly ``period_s``;
a run longer than a period repeats the sequence.
"""
from __future__ import annotations

import dataclasses
import itertools
from statistics import NormalDist
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass
class Spec:
    """One request as the generator lays it out."""
    index: int          # position in the run's sequence
    due_s: float        # open loop: when it is due, from the window start
    prompt_len: int
    output_len: int


def _lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of the distribution, so
    the set follows it without sampling noise, in an order drawn from
    ``rng``; clipped to ``[min, max]``."""
    lo, hi = int(dist["min"]), int(dist["max"])
    q = (np.arange(n) + 0.5) / n
    if dist["dist"] == "uniform":
        v = np.floor(lo + q * (hi - lo + 1))
    elif dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        v = np.rint(np.exp(np.log(dist["median"]) + dist["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return rng.permutation(np.clip(v, lo, hi).astype(np.int64))


def _gaps(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` gaps at evenly spaced quantiles of the gap distribution
    (read off a large draw), in an order drawn from ``rng``, scaled to
    span ``period_s``."""
    g = mix["gaps"]
    if g["dist"] == "gamma":          # CV = 1 / sqrt(shape)
        big = rng.gamma(g["shape"], 1.0, size=1 << 20)
    elif g["dist"] == "exponential":  # Poisson arrivals
        big = rng.exponential(1.0, size=1 << 20)
    else:
        raise ValueError(f"unknown gap distribution {g['dist']!r}")
    v = rng.permutation(np.quantile(big, (np.arange(n) + 0.5) / n))
    return v * (mix["period_s"] / v.sum())


def layout_size(mix: dict) -> int:
    if mix["loop"] == "open":
        return max(1, int(round(mix["rate_per_s"] * mix["period_s"])))
    return int(mix["requests"])


def layout(mix: dict):
    """``(prompt_lens, output_lens, gaps)`` of one period, from the mix's
    own ``layout_seed``: the same for every run seed."""
    n = layout_size(mix)
    rng = np.random.default_rng(int(mix["layout_seed"]))
    prompts = _lengths(mix["prompt_tokens"], n, rng)
    outputs = _lengths(mix["output_tokens"], n, rng)
    gaps = _gaps(mix, n, rng) if mix["loop"] == "open" else np.zeros(n)
    return prompts, outputs, gaps


def stream(mix: dict, seed: int) -> Iterator[Spec]:
    """The run's requests for ``seed``, in order, without end."""
    prompts, outputs, gaps = layout(mix)
    n = len(prompts)
    start = int(np.random.default_rng([int(seed), 7]).integers(n))
    t = 0.0
    for i in itertools.count():
        j = (start + i) % n
        yield Spec(i, t, int(prompts[j]), int(outputs[j]))
        t += float(gaps[j])


def sequence(mix: dict, seed: int, count: int) -> List[Spec]:
    """The first ``count`` requests of the run for ``seed``."""
    return list(itertools.islice(stream(mix, seed), count))


def due_before(mix: dict, seed: int, horizon_s: float) -> List[Spec]:
    """Open loop: every request due before ``horizon_s``."""
    per = layout_size(mix)
    count = per * (int(horizon_s // mix["period_s"]) + 2)
    return [s for s in sequence(mix, seed, count)
            if s.due_s < horizon_s * (1 - 1e-12)]


def prompt_tokens(seed: int, index: int, length: int,
                  vocab: int) -> List[int]:
    """Token ids of request ``index``: uniform over ``[1, vocab)``."""
    rng = np.random.default_rng([int(seed), 11, index])
    return rng.integers(1, vocab, size=length).tolist()
