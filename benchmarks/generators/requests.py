"""Serving traffic: requests with drawn lengths on an arrival schedule.

One generator for every mix; a mix is a file of parameters
(``benchmarks/traffic/<mix>.json``):

* ``prompt_tokens`` / ``answer_tokens``: a length distribution
  (``lognormal`` with ``median``, ``sigma``, clipped to ``min``..``max``,
  or ``fixed`` with ``value``);
* ``arrivals``: ``{"kind": "all_at_zero", "count": n}`` (a closed backlog,
  every request due at once) or ``{"kind": "poisson", "rate_per_s": r}``
  (an open loop at a fixed rate through the lead-in and the window);
* ``lead_in_s``: seconds of the same load before the window, not counted.

Every seed gets the same set of lengths and the same set of gaps between
arrivals, in another order: the set is the distribution's quantiles at
``(i + 0.5) / n``, so its median, tail and sum never move with the seed.
The order is spread (``spread``): the sorted set is cut into ``STRATA``
equal strata and every run of ``STRATA`` consecutive requests holds one
value of each, so a window that sees fifty requests of a thousand sees
the whole distribution whatever the seed (a plain shuffle made two seeds'
tokens per second differ by 13% on the chip, PR 23). ``order_seed``, where
the mix gives one, fixes that order for every run: the mix is then a
replayed trace, the same requests due at the same times, and ``--seed``
draws the token ids (and the weights) only; a spread order from ``--seed``
still left 8% between three seeds, because which long prompt meets which
neighbours decides how the slots fill (PR 23). Without ``order_seed`` the
order follows ``--seed``. Token ids are uniform over the vocabulary.
Answers have an exact length (``max_new_tokens``, no EOS).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


STRATA = 16


@dataclass
class Request:
    arrival_s: float          # from the start of the lead-in
    prompt: List[int]
    max_new_tokens: int


def lengths(spec: dict, n: int) -> np.ndarray:
    """The fixed set of ``n`` lengths of a distribution, ascending."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def gaps(rate_per_s: float, n: int) -> np.ndarray:
    """The fixed set of ``n`` exponential gaps at that rate, ascending,
    scaled so that they sum to ``n / rate`` exactly."""
    q = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return q * (n / rate_per_s) / q.sum()


def spread(ascending: np.ndarray, rng, strata: int = STRATA) -> np.ndarray:
    """The values in an order in which every ``strata`` consecutive ones
    hold one of each stratum (a contiguous slice of the sorted set), the
    strata in a random order within the run and each stratum's values in a
    random order across runs."""
    groups = [rng.permutation(g) for g in np.array_split(ascending, strata)]
    out = []
    for r in range(max(len(g) for g in groups)):
        out.extend(groups[k][r] for k in rng.permutation(strata)
                   if r < len(groups[k]))
    return np.asarray(out, dtype=ascending.dtype)


def generate(params: dict, seed: int, vocab_size: int, seconds: float
             ) -> dict:
    ids_rng = np.random.default_rng(seed)
    rng = np.random.default_rng(params.get("order_seed", seed))
    lead_in = float(params.get("lead_in_s", 0.0))
    arr = params["arrivals"]
    if arr["kind"] == "all_at_zero":
        n = int(arr["count"])
        arrivals = np.zeros(n)
    elif arr["kind"] == "poisson":
        n = max(1, int(round(arr["rate_per_s"] * (lead_in + seconds))))
        # the first request is due at 0; the other n - 1 gaps, the same
        # set for every seed, follow in the seed's order
        g = gaps(arr["rate_per_s"], n)
        arrivals = np.concatenate([[0.0], np.cumsum(spread(g[1:], rng))])
    else:
        raise ValueError(f"unknown arrivals kind {arr['kind']!r}")
    prompts = spread(lengths(params["prompt_tokens"], n), rng)
    answers = spread(lengths(params["answer_tokens"], n), rng)
    ids = ids_rng.integers(0, vocab_size, int(prompts.sum()), dtype=np.int64)
    cuts = np.concatenate([[0], np.cumsum(prompts)])
    requests = [Request(float(arrivals[i]),
                        ids[cuts[i]:cuts[i + 1]].tolist(), int(answers[i]))
                for i in range(n)]
    return {"requests": requests, "lead_in_s": lead_in,
            "open_loop": arr["kind"] == "poisson"}
