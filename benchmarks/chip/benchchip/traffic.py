"""The one traffic generator: a mix is a JSON file of parameters under
``traffic/``, and every seed draws the same multiset of sizes and gaps in
another order.

A mix file holds::

    {"loop": "backlog", "prompt": {...}, "output": {...}}
    {"loop": "open", "arrival": {"dist": "gamma", "cv": 2.0},
     "prompt": {...}, "output": {...}}

A window of ``seconds`` holds ``rate_per_s * seconds`` requests. An open
loop spreads their due times over the window; a backlog has them all due
when the window opens (at least one per decode slot), so the window is a
fixed amount of work that lasts about ``seconds`` at the rate the cell was
sized for. ``rate_per_s`` is the cell's (``load`` in
``cells/<workload>.json``, merged over the mix), since the rate that a
system sustains differs from one configuration to the next.

A length distribution is ``{"dist": "uniform", "min", "max"}`` or
``{"dist": "lognormal", "median", "sigma", "min", "max"}``, optionally with
``"multiple"``: a prompt length is rounded up to it (each distinct prompt
length is one prefill program, so prompts come in buckets). ``{"dist":
"mixture", "parts": [{"weight": w, <distribution>}, ...]}`` draws the share
``w`` of the window's values from each part (long and short prompts mixed).

``"prefix": {"tokens": n, "count": k}`` starts every prompt with one of
``k`` shared prefixes of ``n`` tokens, drawn from the seed and dealt out in
turn (a system prompt that many requests share); prompts stay at least
``n`` long.

Values are stratified: the ``i``-th of ``n`` draws is the distribution's
quantile at ``(i + 0.5) / n``, so two seeds give a window the same sizes and
gaps. An open loop's seed permutes them. A backlog's sizes come in one fixed
order: admitted first come first served, its makespan depends on the order
of its lengths (by up to 16% between seeds on the chip), so the seed draws
only the prompts' tokens and every run does the same work. The spread
between runs is then the system's, not the sampler's.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

#: the key of a backlog's one order of sizes
BACKLOG_ORDER = [0, 7]


def _quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    kind = dist["dist"]
    if kind == "mixture":
        # the share ``weight`` of the quantiles goes to each part in turn
        w = np.array([p["weight"] for p in dist["parts"]], float)
        edges = np.concatenate([[0.0], np.cumsum(w / w.sum())])
        part = np.minimum(np.searchsorted(edges, u, side="right") - 1,
                          len(w) - 1)
        out = np.empty(len(u), int)
        for j, p in enumerate(dist["parts"]):
            sel = part == j
            out[sel] = _quantile(p, (u[sel] - edges[j])
                                 / (edges[j + 1] - edges[j]))
        return out
    lo, hi = dist["min"], dist["max"]
    m = dist.get("multiple", 1)
    if kind == "uniform":
        # uniform over the admissible values: the multiples of ``m`` in
        # [lo, hi] (every integer when m == 1)
        vals = np.arange(-(-lo // m) * m, hi + 1, m)
        return vals[np.minimum((u * len(vals)).astype(int), len(vals) - 1)]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        raw = dist["median"] * np.exp(dist["sigma"] * z)
        out = np.ceil(np.clip(raw, lo, hi) / m) * m
        return np.clip(out, -(-lo // m) * m, hi).astype(int)
    raise ValueError(f"unknown length distribution {kind!r}")


def buckets(dist: dict) -> List[int]:
    """Every value a length distribution can produce (the prefill shapes
    to warm up)."""
    if dist["dist"] == "mixture":
        return sorted(set().union(*(buckets(p) for p in dist["parts"])))
    m = dist.get("multiple", 1)
    return list(range(-(-dist["min"] // m) * m, dist["max"] + 1, m))


def stratified(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` values at the quantiles ``(i + 0.5) / n``, in ``rng``'s order."""
    u = (np.arange(n) + 0.5) / n
    return rng.permutation(_quantile(dist, u))


def gaps(arrival: dict, rate: float, n: int,
         rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps of mean ``1 / rate``: Gamma with the given
    coefficient of variation (shape ``1 / cv**2``; cv 1 is Poisson),
    stratified, rescaled so that they sum to exactly ``n / rate``, in
    ``rng``'s order."""
    if arrival["dist"] != "gamma":
        raise ValueError(f"unknown arrival distribution {arrival['dist']!r}")
    from scipy.special import gammaincinv

    k = 1.0 / arrival["cv"] ** 2
    u = (np.arange(n) + 0.5) / n
    g = gammaincinv(k, u)
    g = g * (n / rate) / g.sum()
    return rng.permutation(g)


@dataclasses.dataclass
class Request:
    """One request: when it is due (seconds after the window opens) and
    what it asks for."""

    rid: int
    due: float
    prompt: np.ndarray
    gen: int


class Traffic:
    """Requests of one mix, drawn from ``seed``. ``slots`` is the cell's
    decode slot count (a backlog fills each slot at least once)."""

    def __init__(self, mix: dict, *, vocab: int, seed: int, slots: int):
        self.mix = mix
        self.vocab = vocab
        self.slots = slots
        self.rng = np.random.default_rng([seed, 0])
        pre = mix.get("prefix")
        if pre is not None and min(buckets(mix["prompt"])) < pre["tokens"]:
            raise ValueError(f"prompts shorter than the {pre['tokens']}-token "
                             "shared prefix")

    def prompt_buckets(self) -> List[int]:
        return buckets(self.mix["prompt"])

    def max_prompt(self) -> int:
        return max(buckets(self.mix["prompt"]))

    def max_gen(self) -> int:
        return max(buckets(self.mix["output"]))

    def _make(self, rid, due, plen, gen, head) -> Request:
        toks = self.rng.integers(0, self.vocab, int(plen)).astype(np.int32)
        if head is not None:
            toks[:head.size] = head
        return Request(rid, due, toks, int(gen))

    def _heads(self, n: int, order: np.random.Generator) -> list:
        """The shared prefix each of ``n`` prompts starts with (None
        without a ``prefix`` in the mix)."""
        pre = self.mix.get("prefix")
        if pre is None:
            return [None] * n
        heads = [self.rng.integers(0, self.vocab, pre["tokens"])
                 .astype(np.int32) for _ in range(pre["count"])]
        return [heads[k] for k in order.permutation(np.arange(n)
                                                    % pre["count"])]

    def schedule(self, seconds: float) -> List[Request]:
        """Every request due in a window of ``seconds``, by due time."""
        rate = self.mix["rate_per_s"]
        order = self.rng
        if self.mix["loop"] == "backlog":
            n = max(self.slots, round(rate * seconds))
            due = np.zeros(n)
            order = np.random.default_rng(BACKLOG_ORDER)
        elif self.mix["loop"] == "open":
            n = max(1, int(math.floor(rate * seconds)))
            g = gaps(self.mix["arrival"], rate, n, self.rng)
            due = np.cumsum(g) - g[0] * 0.5   # first arrival inside the window
        else:
            raise ValueError(f"unknown loop {self.mix['loop']!r}")
        plens = stratified(self.mix["prompt"], n, order)
        gens = stratified(self.mix["output"], n, order)
        heads = self._heads(n, order)
        return [self._make(i, float(t), p, q, h)
                for i, (t, p, q, h) in enumerate(zip(due, plens, gens,
                                                     heads))]
