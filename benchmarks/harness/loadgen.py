"""One general load generator, driven by a traffic file's parameters.

A traffic mix is data (``benchmarks/traffic/<name>.json``); this module is
the only code that turns one into requests and arrival times, so a later PR
adds a mix by adding a file. Everything is drawn from ``--seed`` with
``numpy.random.RandomState``: the same seed gives the same stream of
requests and the same arrival schedule.

Serve traffic keys::

    "arrival":    {"process": "closed", "clients": N}            closed loop
                  {"process": "poisson", "rate_per_s": r}        open loop
                  {"process": "gamma", "rate_per_s": r, "cv": c} bursty open loop
    "prompt_len": {"dist": "loguniform"|"uniform"|"fixed", "lo":, "hi":} / {"value":}
    "output_len": same
    "shared_prefix": {"share": 0..1, "len": tokens}   optional: that share of
                  the requests start with the same ``len`` tokens
    "rate_metric": the end-to-end metric under which the runner reports
                  the tokens per second completed

**How much work a window holds does not depend on the seed.** Lengths are
not drawn one by one: they are handed out in blocks (``Strata``), each block
holding one length from every equal slice of the distribution, in an order
the seed decides. Any block of a mix therefore holds the same lengths
whatever the seed; the seed decides which request gets which, what is paired
with what, and every token id. A block is as long as a closed loop has
clients (64 in an open loop), so a closed loop's first wave is one block.

In an open loop a request's latency counts from the time it was DUE, and
how late the generator handed it over is reported beside it.
"""

from __future__ import annotations

import math
from typing import Iterator, List, Optional, Tuple

import numpy as np


def length_at(spec: dict, q: float) -> int:
    """The length at level ``q`` in [0, 1) of the distribution: its
    quantile function."""
    dist = spec["dist"]
    if dist == "fixed":
        return int(spec["value"])
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if dist == "uniform":
        return int(min(hi, lo + math.floor(q * (hi + 1 - lo))))
    if dist == "loguniform":
        x = math.exp(math.log(lo) + q * (math.log(hi + 1) - math.log(lo)))
        return int(min(hi, max(lo, math.floor(x))))
    raise ValueError(f"unknown length distribution {dist!r}")


def draw_length(rng: np.random.RandomState, spec: dict) -> int:
    """One independent draw (the reference check's prompts)."""
    return length_at(spec, rng.uniform())


def longest(spec: dict) -> int:
    return int(spec["value"] if spec["dist"] == "fixed" else spec["hi"])


def residual_levels(spec: dict, grid: int = 256):
    """What is left of the request a closed loop's client is in, looked at
    at a random moment of the steady state: ``(lengths, levels)``, sorted,
    ``levels[i]`` the share of such moments with at most ``lengths[i]``
    tokens to come. The client is more often found inside a long request
    than a short one (in proportion to its length), at a uniform point of
    it; the token its prefill samples is always still to come, and so is at
    least one decode step's, so a request of the first wave never ends
    inside set-up."""
    lv = (np.arange(grid) + 0.5) / grid
    n = np.array([length_at(spec, q) for q in lv], np.float64)
    left = 1 + np.maximum(1, np.ceil((n[:, None] - 1) * lv[None, :]))
    left = np.where(n[:, None] >= 2, left, 1).ravel()
    weight = np.repeat(n, grid)
    order = np.argsort(left, kind="stable")
    return left[order].astype(int), np.cumsum(weight[order]) / weight.sum()


class Strata:
    """Levels in [0, 1) in blocks of ``n``: a block holds the midpoint of
    each of ``n`` equal slices, in an order drawn from ``rng``."""

    def __init__(self, rng: np.random.RandomState, n: int):
        self.rng, self.n, self._block = rng, int(n), []

    def next(self) -> float:
        if not self._block:
            self._block = ((self.rng.permutation(self.n) + 0.5)
                           / self.n).tolist()
        return self._block.pop()


class RequestStream:
    """The seeded stream of ``(prompt_ids, max_new_tokens)``."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.traffic = traffic
        self.vocab = int(vocab)
        self.rng = np.random.RandomState(seed)
        block = int(traffic["arrival"].get("clients") or 64)
        # one order each, so that what one hands out does not shift another
        self._level = {
            what: Strata(np.random.RandomState([seed, i]), block)
            for i, what in enumerate(("prompt", "output", "residual",
                                      "shared"), 1)}
        self._residual = None
        sp = traffic.get("shared_prefix") or {}
        self.share = float(sp.get("share", 0.0))
        n = int(sp.get("len", 0))
        self.prefix = self.rng.randint(0, self.vocab, n).tolist() \
            if self.share > 0 and n else []
        self.issued = 0

    def next(self, residual: bool = False) -> Tuple[List[int], int]:
        """``residual``: a request of a closed loop's first wave, caught
        part-way (``residual_levels``)."""
        level = self._level
        n_prompt = length_at(self.traffic["prompt_len"],
                             level["prompt"].next())
        if residual:
            if self._residual is None:
                self._residual = residual_levels(self.traffic["output_len"])
            lengths, levels = self._residual
            n_out = int(lengths[np.searchsorted(
                levels, level["residual"].next(), side="right")])
        else:
            n_out = length_at(self.traffic["output_len"],
                              level["output"].next())
        prompt = self.rng.randint(0, self.vocab, n_prompt).tolist()
        if self.prefix and level["shared"].next() < self.share:
            k = min(len(self.prefix), n_prompt - 1)
            prompt[:k] = self.prefix[:k]
        self.issued += 1
        return prompt, n_out


def arrival_gaps(rng: np.random.RandomState, spec: dict) -> Iterator[float]:
    """Inter-arrival gaps of an open-loop process, seconds."""
    rate = float(spec["rate_per_s"])
    proc = spec["process"]
    if proc == "poisson":
        while True:
            yield float(rng.exponential(1.0 / rate))
    elif proc == "gamma":
        cv = float(spec["cv"])
        shape = 1.0 / (cv * cv)          # mean 1/rate, std cv/rate
        scale = cv * cv / rate
        while True:
            yield float(rng.gamma(shape, scale))
    else:
        raise ValueError(f"unknown open-loop process {proc!r}")


class Arrivals:
    """When requests become due. Times are seconds on the caller's clock,
    relative to ``start`` (the moment load begins)."""

    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.closed = spec["process"] == "closed"
        self.clients = int(spec.get("clients", 0))
        if not self.closed:
            self._gaps = arrival_gaps(np.random.RandomState(seed + 7919),
                                      spec)
            self._next = next(self._gaps)

    def first_wave(self) -> int:
        """Requests due at time 0 (a closed loop's clients)."""
        return self.clients if self.closed else 0

    def due_by(self, now: float) -> List[float]:
        """Open loop: the due times that have passed by ``now``."""
        out: List[float] = []
        if self.closed:
            return out
        while self._next <= now:
            out.append(self._next)
            self._next += next(self._gaps)
        return out

    def on_complete(self, now: float) -> Optional[float]:
        """Closed loop: the client's next request is due the moment its
        last one completed."""
        return now if self.closed else None

    def next_due(self) -> Optional[float]:
        return None if self.closed else self._next
