"""One generator for every traffic mix: a data file of parameters in, a
request schedule out.

A traffic file (``traffic/<name>.json``) names an arrival process and the
length distributions. Every seed gets the same multiset of sizes in another
order, with its own token ids, so two seeds differ in order and content, not
in the amount of work: the lengths are the distributions' quantiles at evenly
spaced probabilities, permuted by the seed.

Arrival processes:

- ``sessions``: ``sessions`` long contexts (``prompt``) built during set-up,
  each then decoding up to ``output`` tokens through the window, with no
  admission inside it.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    rid: int
    prompt: np.ndarray      # int32 token ids
    max_new: int


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` integer lengths at the quantiles (i + 0.5) / n of ``dist``."""
    p = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in p])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "fixed":
        x = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def schedule(traffic: dict, seed: int, vocab: int) -> List[Req]:
    """The requests of one run."""
    rng = np.random.default_rng(seed)
    kind = traffic["arrivals"]["process"]
    if kind != "sessions":
        raise ValueError(f"unknown arrival process {kind!r}")
    n = int(traffic["arrivals"]["sessions"])
    prompts = rng.permutation(quantiles(traffic["prompt"], n))
    outputs = rng.permutation(quantiles(traffic["output"], n))
    return [Req(rid=i, prompt=rng.integers(0, vocab, int(prompts[i]),
                                           dtype=np.int32),
                max_new=int(outputs[i]))
            for i in range(n)]


def limits(traffic: dict) -> tuple:
    """(longest prompt, longest output) the engine has to be sized for."""
    return int(traffic["prompt"]["max"]), int(traffic["output"]["max"])
