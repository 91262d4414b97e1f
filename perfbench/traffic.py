"""The one traffic generator: sizes from a traffic file, token ids from the
seed.

The sequence of (prompt length, output length) pairs depends only on the
traffic file, so every ``--seed`` serves the same work in the same order and
differs only in token ids and weights.  A closed loop of one client serves
only the first few requests of a window, so the order makes every stretch of
the list stand for the whole mix: prompt lengths come in rounds that hold
each bucket once (each round shuffled by ``order_seed``), and the i-th output
length is the stated distribution's quantile at the i-th point of the van
der Corput sequence (1/2, 1/4, 3/4, 1/8, 5/8, ...), which spreads any prefix
of the list evenly over the distribution.
"""
from __future__ import annotations

import math
import statistics
from typing import Any, Dict, List, Tuple

import numpy as np


def van_der_corput(i: int) -> float:
    """The i-th point (i >= 1) of the base-2 van der Corput sequence."""
    x, d = 0.0, 0.5
    while i:
        x += d * (i & 1)
        i >>= 1
        d /= 2
    return x


def sizes(traffic: Dict[str, Any]) -> List[Tuple[int, int]]:
    """[(prompt_len, max_new_tokens)] in serving order."""
    n = int(traffic["requests"])
    rng = np.random.default_rng(int(traffic["order_seed"]))
    buckets = [int(b) for b in traffic["prompt_tokens"]]
    prompts: List[int] = []
    while len(prompts) < n:
        prompts += [buckets[i] for i in rng.permutation(len(buckets))]
    out = traffic["output_tokens"]
    if "fixed" in out:
        outs = [int(out["fixed"])] * n
    else:
        nd = statistics.NormalDist()
        med, sig = float(out["lognormal_median"]), float(out["lognormal_sigma"])
        outs = [min(int(out["max"]), max(int(out["min"]), round(
            med * math.exp(sig * nd.inv_cdf(van_der_corput(i + 1))))))
            for i in range(n)]
    return list(zip(prompts[:n], outs))


def token_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generators per use (0: window prompts, 1: warm-up)."""
    return np.random.default_rng([int(seed), stream])


def prompts(seed: int, stream: int, lengths: List[int],
            vocab: int) -> List[np.ndarray]:
    """Token ids uniform over the vocabulary, one int32 row per length."""
    rng = token_rng(seed, stream)
    return [rng.integers(0, vocab, size=(1, p), dtype=np.int32)
            for p in lengths]
