"""Whether what the timed path served is correct.

Once the window has closed, a sample of the requests it served is drawn from
the seed, the request with the longest sequence always in it, until it holds
``min_tokens`` served tokens or more. The plain float32 reference is run
once over each sampled prompt followed by its served tokens, and every
served token is judged by its gap: the reference's best logit at that
position minus the reference's logit of the served token. Greedy decoding
from a correct program serves the reference's best token up to rounding, so
the gap stays near zero and grows only at near ties; a token altered or
computed from the wrong context lies far below the best.

Numbers compared, each beside its limit, as far as the cell's
``limits/<cell>.json`` names them:

- ``max_logit_gap``: the widest gap, at most its limit;
- ``mean_logit_gap``: the mean gap over every judged token, at most its
  limit (it separates a lower-precision run whose widest gap does not);
- ``tokens_compared``: served tokens judged, at least its limit.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List

import numpy as np

from chipbench import arch


@dataclasses.dataclass
class Verdict:
    correct: bool
    numbers: Dict[str, dict]        # name -> {"value", "limit", "rule"}

    def lines(self) -> List[str]:
        return [f"check {name}: {d['value']} (limit {d['limit']}, "
                f"{d['rule']})" for name, d in self.numbers.items()]


def load_reference(bench_dir: str, name: str):
    return arch.load_file(os.path.join(bench_dir, "configs", name + ".py"),
                          f"reference_{name}")


def sample(served: Dict[int, tuple], seed: int, min_tokens: int,
           max_positions: int) -> List[int]:
    """Request ids to compare: the longest sequence first, then others in
    an order drawn from ``seed``, until ``min_tokens`` served tokens are in
    or the next would pass ``max_positions`` reference positions.
    ``served``: rid -> (prompt, served tokens)."""
    rids = sorted(served)
    if not rids:
        return []
    size = {r: len(served[r][0]) + len(served[r][1]) for r in rids}
    longest = max(rids, key=lambda r: (size[r], r))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    order = [longest] + [r for r in rng.permutation(rids) if r != longest]
    picked, tokens, positions = [], 0, 0
    for r in order:
        if picked and positions + size[r] > max_positions:
            continue
        picked.append(int(r))
        tokens += len(served[r][1])
        positions += size[r]
        if tokens >= min_tokens:
            break
    return picked


def padded_size(pairs) -> tuple:
    """(sequence length, rows) every sampled request is padded to, so that
    the reference compiles one shape per run: the longest of each."""
    if not pairs:
        return (0, 0)
    return (max(len(p) + len(t) for p, t in pairs),
            max(len(t) for _, t in pairs))


def gaps_for(reference, raw, cfgj, prompt, tokens, size=(0, 0)):
    """Per served token: the reference's best logit minus the logit of the
    token that was served. ``size``: see :func:`padded_size`."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(tokens[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(seq))
    lg = reference.logits(raw, cfgj, seq, rows, size=size)
    tok = np.asarray(tokens, np.int64)
    return lg.max(axis=-1) - lg[np.arange(len(tok)), tok]


def control_gaps(reference, raw, cfgj, prompt, tokens, act_dtype,
                 size=(0, 0)):
    """The reference computed at ``act_dtype`` put in the program's place:
    at each position of the same prompt and tokens, the float32 gap of the
    token the lower precision puts first."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(tokens[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(seq))
    ref = reference.logits(raw, cfgj, seq, rows, size=size)
    low = reference.logits(raw, cfgj, seq, rows, act_dtype, size=size)
    pick = low.argmax(axis=-1)
    return ref.max(axis=-1) - ref[np.arange(len(pick)), pick]


def judge(gaps: np.ndarray, limits: dict) -> Verdict:
    n = int(gaps.size)
    values = {
        "max_logit_gap": float(gaps.max()) if n else float("inf"),
        "mean_logit_gap": float(gaps.mean()) if n else float("inf"),
        "tokens_compared": n,
    }
    numbers, correct = {}, bool(np.isfinite(gaps).all())
    for name, value in values.items():
        if name not in limits:
            continue
        limit = limits[name]["limit"]
        at_least = name == "tokens_compared"
        numbers[name] = {"value": value, "limit": limit,
                         "rule": "at least" if at_least else "at most"}
        correct &= value >= limit if at_least else value <= limit
    return Verdict(correct, numbers)
