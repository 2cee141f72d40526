"""Stratified draws: a distribution's quantile grid, which a seed permutes.

Every seed then offers the same multiset of sizes; only which request gets
which differs. Independent draws move the offered load itself by several
percent from seed to seed (PERF.md, PR 22's refusal)."""

from __future__ import annotations

import math
import statistics
from dataclasses import dataclass, field

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile(spec: dict, q: float) -> int:
    """The q-quantile (0 < q < 1) of a distribution given as data."""
    kind = spec["dist"]
    if kind == "lognormal":
        x = math.exp(math.log(spec["median"])
                     + spec["sigma"] * _NORMAL.inv_cdf(q))
    elif kind == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"])
    elif kind == "zipf":          # ranks 0..n-1, weight 1 / (rank+1)^s
        w = [1.0 / (r + 1) ** spec["s"] for r in range(spec["n"])]
        acc, total = 0.0, sum(w)
        for rank, wi in enumerate(w):
            acc += wi / total
            if q <= acc:
                return rank
        return spec["n"] - 1
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    x = min(max(x, spec.get("min", x)), spec.get("max", x))
    return x if spec.get("real") else int(round(x))


def grid(spec: dict, n: int) -> list:
    """n points of the quantile grid, ascending."""
    return [quantile(spec, (j + 0.5) / n) for j in range(n)]


def permuted(rng: np.random.Generator, values: list) -> list:
    return [values[i] for i in rng.permutation(len(values))]


def rng_for(seed: int, *stream) -> np.random.Generator:
    """Independent streams from one --seed (any whole number up to a
    little over 2**31)."""
    return np.random.default_rng([int(seed), *stream])


@dataclass
class Req:
    """One request of a serving schedule, and what the run records of it."""
    rid: int
    prompt: np.ndarray              # int32 token ids
    max_new_tokens: int
    due: float | None = None        # seconds from schedule start (open loop)
    session: int = -1
    turn: int = 0
    # filled by the runner
    submit_t: float | None = None
    token_times: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    failed: bool = False
    done: bool = False
    handle: object = None
