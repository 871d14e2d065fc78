"""The one generator of serving traffic: reads a traffic file's numbers.

Lengths come from a fixed grid of quantiles of the stated distribution,
so every seed serves the same multiset of prompt and output lengths and
the same set of gaps between arrivals; the seed draws the order, the
pairing of prompt and output lengths, and the token ids.  The order is
stratified: each block of ``block`` consecutive requests holds one
length from each of ``block`` equal strata of the grid, so any stretch
of the queue that a window serves has the same mix whatever the seed.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def length_grid(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of a lognormal with
    the spec's median and sigma, rounded and clipped to [min, max]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(raw), int(spec["min"]), int(spec["max"])).astype(np.int64)


def arrival_gaps(spec: Dict, n: int) -> np.ndarray:
    """Gaps of a Poisson process at ``rate_per_s``: quantiles of the
    exponential distribution (a fixed set; the seed orders them)."""
    rate = float(spec["rate_per_s"])
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def stratified(values: np.ndarray, block: int, rng) -> np.ndarray:
    """``values`` reordered so that every ``block`` consecutive entries
    take one from each stratum of the sorted values, in a random order."""
    n = len(values) - len(values) % block
    strata = np.sort(values)[:n].reshape(block, n // block)
    strata = np.stack([rng.permutation(row) for row in strata])
    blocks = np.stack([rng.permutation(strata[:, j]) for j in range(n // block)])
    return blocks.reshape(-1)


def requests(traffic: Dict, vocab: int, seed: int) -> List[Dict]:
    """[{rid, prompt (int32 array), max_new_tokens, due_s}] in arrival
    order; ``due_s`` counts from the window's start."""
    n = int(traffic["requests"])
    rng = np.random.default_rng([seed, 101])
    block = int(traffic.get("block", 64))
    n -= n % block
    prompts = stratified(length_grid(traffic["prompt_len"], n), block, rng)
    outputs = stratified(length_grid(traffic["output_len"], n), block, rng)
    arrival = traffic["arrival"]
    if arrival["kind"] == "backlog":
        due = np.zeros(n)
    elif arrival["kind"] == "poisson":
        due = np.cumsum(stratified(arrival_gaps(arrival, n), block, rng))
    else:
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    out = []
    for i in range(n):
        out.append({
            "rid": i,
            "prompt": rng.integers(0, vocab, int(prompts[i]), dtype=np.int64).astype(np.int32),
            "max_new_tokens": int(outputs[i]),
            "due_s": float(due[i]),
        })
    return out


def percentile(values, q: float) -> float:
    """The q-th percentile (linear between closest ranks)."""
    if not len(values):
        return math.nan
    return float(np.percentile(np.asarray(values, np.float64), q))
