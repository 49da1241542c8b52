"""Seeded request generators that read a traffic file's parameters.

Every seed gets the same work in the same order: lengths and gaps are
the quantiles of their distributions at fixed points, in blocks of
``block`` requests, each block shuffled by a fixed permutation of its
stream, and the seed draws only the token ids.  Near capacity the order
of long and short requests alone moves a latency tail by a factor of
three (the chat cell on a TPU v5e), so a seed that reordered the work
would change the work.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, Iterator, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    due_s: float                # offset from the window's start (open loop)
    prompt: np.ndarray          # int32 token ids
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths at the quantiles of a lognormal with the given
    median and sigma, clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    return -np.log1p(-_quantiles(n)) / rate


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def _blocks(traffic: Dict[str, Any], stream: int) -> Iterator[tuple]:
    """Endless (gap, prompt_len, max_new) triples, block by block."""
    k = int(traffic["block"])
    prompts = lognormal_lengths(traffic["prompt"], k)
    outputs = lognormal_lengths(traffic["output"], k)
    gaps = (exponential_gaps(traffic["rate_per_s"], k)
            if "rate_per_s" in traffic else np.zeros(k))
    rng = np.random.default_rng(np.random.SeedSequence([stream]))
    while True:
        order = [rng.permutation(k) for _ in range(3)]
        for i in range(k):
            yield (float(gaps[order[0][i]]), int(prompts[order[1][i]]),
                   int(outputs[order[2][i]]))


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=n, dtype=np.int32)


def open_loop(traffic: Dict[str, Any], seed: int, seconds: float,
              vocab: int) -> List[Req]:
    """Poisson-like arrivals at ``rate_per_s``: every request due in
    [0, seconds), the first at 0."""
    out: List[Req] = []
    t = 0.0
    tok = _rng(seed, 1 << 20)
    for gap, plen, olen in _blocks(traffic, 0):
        if t >= seconds:
            return out
        out.append(Req(t, _tokens(tok, plen, vocab), olen))
        t += gap


def closed_loop(traffic: Dict[str, Any], seed: int, vocab: int
                ) -> List[Iterator[Req]]:
    """One endless request stream per client, each sent when the
    client's previous request completes."""
    def client(c: int) -> Iterator[Req]:
        tok = _rng(seed, (1 << 20) + c)
        for _, plen, olen in _blocks(traffic, c + 1):
            yield Req(0.0, _tokens(tok, plen, vocab), olen)
    return [client(c) for c in range(int(traffic["clients"]))]


def prompt_range(traffic: Dict[str, Any]) -> tuple:
    return int(traffic["prompt"]["min"]), int(traffic["prompt"]["max"])
