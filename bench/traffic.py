"""One general generator for every traffic mix: ``traffic/<name>.json``.

A mix is data: how requests arrive and how long their prompts and outputs
are.  The sizes and gaps are the quantiles ``(i + 0.5) / n`` of each
stated distribution, put in an order drawn once from a fixed stream, so
every seed replays the same schedule -- the same lengths at the same
times -- and does the same work; the seed draws the token ids (and, in the
harness, the weights).  A run serves tens of requests, too few for the
order of a few long ones to average out between seeds.

Arrivals (``arrivals.kind``):
  ``poisson``  open loop at ``rate_per_s``: ``round(rate * seconds)``
               requests whose gaps are the exponential's quantiles, scaled
               so that they arrive inside ``[0, seconds)``;
  ``backlog``  ``requests`` requests, all due at 0.

Lengths (``prompt`` and ``output``): ``lognormal`` (``median``, ``sigma``,
clipped to ``[min, max]``) or ``uniform`` (``min``..``max``).
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclass
class Draw:
    """One request as the generator makes it; the harness turns it into
    the program's ``Request``."""
    uid: int
    prompt: np.ndarray      # (T,) int32
    max_new: int
    arrival: float          # seconds after the window opens


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named stream of one seed (any size of
    whole number)."""
    return np.random.default_rng([seed % 2 ** 63, seed // 2 ** 63,
                                  sum(map(ord, stream))])


def quantiles(n: int):
    return [(i + 0.5) / n for i in range(n)]


def lengths(spec: dict, n: int) -> List[int]:
    """The ``n`` stratified lengths of one length distribution, sorted."""
    kind = spec["dist"]
    if kind == "lognormal":
        mu, sig = math.log(spec["median"]), spec["sigma"]
        vals = [math.exp(mu + sig * _NORMAL.inv_cdf(q)) for q in quantiles(n)]
    elif kind == "uniform":
        lo, hi = spec["min"], spec["max"]
        vals = [lo + q * (hi - lo) for q in quantiles(n)]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return [int(min(max(round(v), spec["min"]), spec["max"])) for v in vals]


def n_requests(traffic: dict, seconds: float) -> int:
    arr = traffic["arrivals"]
    if arr["kind"] == "poisson":
        return max(1, round(arr["rate_per_s"] * seconds))
    if arr["kind"] == "backlog":
        return int(arr["requests"])
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def arrivals(traffic: dict, n: int, seconds: float,
             rng: np.random.Generator) -> List[float]:
    arr = traffic["arrivals"]
    if arr["kind"] != "poisson":
        return [0.0] * n
    gaps = np.array([-math.log(1.0 - q) for q in quantiles(n)])
    rng.shuffle(gaps)
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return list(t * (seconds / gaps.sum()))


SCHEDULE_SEED = 0


def generate(traffic: dict, vocab: int, seed: int, seconds: float
             ) -> List[Draw]:
    n = n_requests(traffic, seconds)
    rng = rng_for(SCHEDULE_SEED, "schedule")
    prompts = lengths(traffic["prompt"], n)
    rng.shuffle(prompts)
    outputs = lengths(traffic["output"], n)
    rng.shuffle(outputs)
    due = arrivals(traffic, n, seconds, rng)
    tok_rng = rng_for(seed, "tokens")
    return [Draw(uid=i, prompt=tok_rng.integers(0, vocab, size=t,
                                                dtype=np.int32),
                 max_new=int(m), arrival=float(a))
            for i, (t, m, a) in enumerate(zip(prompts, outputs, due))]
