"""The one traffic generator: every mix is a data file of parameters.

Sizes and gaps are stratified draws: the ``n`` values of a distribution are
its quantiles at ``(i + 0.5) / n``, paired and put in order by a shuffle
that is the mix's own. Every seed therefore offers the same requests at the
same times, and ``--seed`` picks what they say: the token ids of every
prompt and the sampling seeds. A run's time to first token depends on how
the long prompts of its window bunch together; with a 30-second window of
about 50 requests, orders drawn from the seed moved the 90th percentile by
40% between seeds, against 2% between two runs of one seed.

Parameters of a mix (``traffic/<name>.json``):

* ``loop``: ``"open"`` (arrivals on a schedule, independent users) or
  ``"closed"`` (``clients`` callers, each sending its next request when the
  last one finished; ``"slots"`` means one per engine slot).
* ``arrivals`` (open): ``"poisson"``; the rate is ``rate_rps``, or ``load``
  times the configuration's ``knee_rps[knee]``.
* ``prompt``, ``output``: ``{"dist": "lognormal", "median", "sigma", "min",
  "max"}`` or ``{"dist": "uniform", "min", "max"}``, in tokens.
* ``max_total``: cap on prompt + output tokens (the output is cut to fit).
* ``sampling``: ``temperature``, ``top_p``, ``top_k``; ``greedy_every``: every
  k-th request is greedy whatever the sampling (the correctness check
  compares greedy tokens).
* ``preroll_s``: seconds of arrivals before the window opens.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

__all__ = ["Spec", "quantiles", "draw_specs", "open_schedule", "rate_of"]


@dataclasses.dataclass(frozen=True)
class Spec:
    """One request as generated: when it is due (seconds from the start of
    the schedule; closed loop: None), its prompt and its output budget."""

    idx: int
    due: float
    prompt: tuple
    max_new: int
    temperature: float
    top_p: float
    top_k: int
    greedy: bool


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of ``dist``, as whole tokens."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = dist["min"] + u * (dist["max"] + 1 - dist["min"])
        v = np.floor(v)
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def rate_of(traffic: Dict, config: Dict) -> float:
    if "rate_rps" in traffic:
        return float(traffic["rate_rps"])
    return float(traffic["load"]) * float(config["knee_rps"][traffic["knee"]])


def draw_specs(traffic: Dict, n: int, seed: int, vocab: int, max_seq: int,
               dues=None) -> List[Spec]:
    """``n`` requests from ``traffic``: sizes in the mix's own order, token
    ids from ``seed``."""
    order = np.random.default_rng(0)
    outs = quantiles(traffic["output"], n)[order.permutation(n)]
    prompts = quantiles(traffic["prompt"], n)[order.permutation(n)]
    rng = np.random.default_rng(seed)
    cap = min(int(traffic.get("max_total", max_seq)), max_seq - 1)
    samp = traffic.get("sampling", {})
    every = int(traffic.get("greedy_every", 1))
    specs = []
    for i in range(n):
        p = int(min(prompts[i], cap - 1))
        m = int(max(1, min(outs[i], cap - p)))
        greedy = samp.get("temperature", 0.0) == 0.0 or i % every == 0
        specs.append(Spec(
            idx=i, due=None if dues is None else float(dues[i]),
            prompt=tuple(int(t) for t in rng.integers(1, vocab, size=p)),
            max_new=m,
            temperature=0.0 if greedy else float(samp["temperature"]),
            top_p=1.0 if greedy else float(samp.get("top_p", 1.0)),
            top_k=0 if greedy else int(samp.get("top_k", 0)),
            greedy=greedy))
    return specs


def open_schedule(traffic: Dict, rate: float, span_s: float, seed: int,
                  vocab: int, max_seq: int) -> List[Spec]:
    """Poisson arrivals at ``rate`` over ``span_s`` seconds: as many
    requests as the rate gives in that span, their exponential gaps
    stratified and put in the mix's own order, scaled so the last one is
    due at the span's end."""
    if traffic.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    n = max(1, int(math.ceil(rate * span_s)))
    u = (np.arange(n) + 0.5) / n
    gaps = np.random.default_rng(1).permutation(-np.log1p(-u) / rate)
    dues = np.cumsum(gaps)
    dues *= span_s / dues[-1]
    return draw_specs(traffic, n, seed, vocab, max_seq, dues=dues)
