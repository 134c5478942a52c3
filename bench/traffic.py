"""The one traffic generator: a mix file of parameters in, jobs out.

Lengths are a fixed multiset for every seed: values at the
``(i + 0.5) / n`` quantiles of a lognormal (``median``, ``sigma``),
rounded and clipped to ``[min, max]``.  A closed loop takes its jobs in
order from a pool dealt in blocks of ``block`` jobs, each block the same
``block`` quantiles in its own order, so every run serves nearly the
same multiset however many jobs its window reaches.  The seed only
permutes them and draws the prompt token ids, so two seeds send the
same work in another order.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Job:
    idx: int
    prompt: np.ndarray              # int32 token ids
    max_new: int


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for any whole-number seed (negative or past 64 bits)."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def quantile_lengths(dist: Dict, n: int) -> np.ndarray:
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    lens = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(lens), dist["min"], dist["max"]).astype(np.int64)


def dealt(dist: Dict, n: int, block: int,
          rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths: blocks of the ``block`` quantiles, each permuted."""
    q = quantile_lengths(dist, block)
    reps = -(-n // block)
    return np.concatenate([rng.permutation(q) for _ in range(reps)])[:n]


def make_jobs(traffic: Dict, vocab: int, seed: int) -> List[Job]:
    """The closed loop's pool of ``pool`` jobs."""
    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    n = int(traffic["pool"])
    rng = rng_for(seed)
    block = int(traffic["block"])
    plens = dealt(traffic["prompt_tokens"], n, block, rng)
    olens = dealt(traffic["output_tokens"], n, block, rng)
    return [Job(i, rng.integers(0, vocab, int(plens[i]), dtype=np.int32),
                int(olens[i])) for i in range(n)]


def warm_prompt_lengths(traffic: Dict) -> List[int]:
    """Prompt lengths that reach every power-of-two prefill bucket the
    mix can produce: its bounds and the powers of two between them."""
    lo, hi = traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"]
    lens = {lo, hi}
    p = 1
    while p <= hi:
        if p >= lo:
            lens.add(p)
        p *= 2
    return sorted(lens)
