"""The one traffic generator: reads a mix's parameters from its data
file (``bench/traffic/<mix>.json``) and makes the cell's requests.

Every seed gets the same work in another order.  Lengths are the
quantiles of the mix's clipped lognormal, one per request, and the gaps
between arrivals are the quantiles of an exponential with the mix's
mean; the seed permutes them and draws the token ids.  So runs of one
cell differ only in order and ids, never in how much they ask.

Mix keys:

* ``loop``: ``"open"`` (arrivals on a wall-clock schedule, whatever the
  server does) or ``"closed"`` (a backlog that keeps every slot busy);
* ``arrival`` (open loop): ``{"kind": "poisson", "rate_per_s": r}`` or
  ``{"kind": "burst", "size": n, "rate_per_s": r}`` -- bursts of ``n``
  requests due together, exponential gaps between bursts, mean rate
  ``r`` requests a second;
* ``prompt_len`` / ``output_len``: ``{"median", "sigma", "min", "max"}``
  of a lognormal clipped to ``[min, max]``;
* ``capacity``: positions each slot reserves (the deployment's context
  limit; at least the longest prompt plus the longest output);
* ``batch_slots`` (optional): decode lanes, where the mix needs other
  than the configuration's ``serve.batch_slots`` (long contexts);
* ``list_size`` (closed loop): length of the seeded request list, a
  power of two, cycled if the window outruns it.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List

import numpy as np


@dataclasses.dataclass
class Spec:
    rid: int
    prompt: np.ndarray        # int32 token ids
    max_new: int
    due: float                # seconds after the window opens (open loop)


def _quantiles(n: int):
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """The ``n`` quantiles of a clipped lognormal, as whole numbers."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf(q) for q in _quantiles(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def gaps(mean: float, n: int) -> np.ndarray:
    """The ``n`` quantiles of an exponential, rescaled to mean ``mean``."""
    g = -np.log1p(-_quantiles(n))
    return g * (mean / g.mean())


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


def _prompt(rng, plen: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, int(plen)).astype(np.int32)


def open_loop(mix: dict, seconds: float, seed: int, vocab: int) -> List[Spec]:
    """The requests due in a window of ``seconds``, sorted by due time."""
    arr = mix["arrival"]
    rate = float(arr["rate_per_s"])
    size = int(arr.get("size", 1)) if arr["kind"] == "burst" else 1
    if arr["kind"] not in ("poisson", "burst"):
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    groups = max(1, math.floor(rate * seconds / size))
    order = _rng(seed, 1)
    g = order.permutation(gaps(size / rate, groups - 1)) if groups > 1 \
        else np.zeros((0,))
    starts = np.concatenate([[0.0], np.cumsum(g)])
    due = np.repeat(starts, size)
    n = len(due)
    plen = order.permutation(lengths(mix["prompt_len"], n))
    olen = order.permutation(lengths(mix["output_len"], n))
    ids = _rng(seed, 2)
    return [Spec(i, _prompt(ids, plen[i], vocab), int(olen[i]), float(due[i]))
            for i in range(n)]


def _bitrev(i: np.ndarray, bits: int) -> np.ndarray:
    out = np.zeros_like(i)
    for b in range(bits):
        out |= ((i >> b) & 1) << (bits - 1 - b)
    return out


def stratified_order(n: int, rng: np.random.Generator) -> np.ndarray:
    """A seeded order of ``0..n-1`` (``n`` a power of two) in which the
    first ``2**k`` entries, for every ``k >= 3``, hold exactly one index
    of each of the ``2**k`` equal strata: bit reversal, a seeded digital
    shift, and a seeded shuffle within each run of 8."""
    bits = n.bit_length() - 1
    if n < 8 or n != 1 << bits:
        raise ValueError(f"list_size {n} is not a power of two >= 8")
    order = _bitrev(np.arange(n), bits) ^ int(rng.integers(0, n))
    for b in range(0, n, 8):
        order[b:b + 8] = rng.permutation(order[b:b + 8])
    return order


class Backlog:
    """Closed loop: an endless seeded list of requests.

    The list holds the quantiles of the mix's lengths, and every seed
    orders them so that any stretch of it taken from the start asks
    nearly the same work (``stratified_order``): a window consumes only
    the list's first requests, and their lengths set how many
    admissions it makes.  The first ``slots`` requests are cut to a
    share of their output budget, the shares paired with the budgets by
    rank, so that lanes finish at staggered steps from the start (the
    residual lives of a busy server) by the same schedule every seed."""

    def __init__(self, mix: dict, seed: int, vocab: int, slots: int):
        self.vocab, self.slots = vocab, slots
        self.n = int(mix["list_size"])
        order = _rng(seed, 1)
        self._ids = _rng(seed, 2)
        self._plen = lengths(mix["prompt_len"], self.n)[
            stratified_order(self.n, order)]
        self._olen = lengths(mix["output_len"], self.n)[
            stratified_order(self.n, order)]
        first = self._olen[:slots]
        rank = np.empty(slots, np.int64)
        rank[np.argsort(first, kind="stable")] = np.arange(slots)
        self._first = [max(2, math.ceil((r + 0.5) / slots * o))
                       for r, o in zip(rank, first)]
        self._next = 0

    def take(self) -> Spec:
        i = self._next
        self._next += 1
        j = i % self.n
        max_new = self._first[i] if i < self.slots else int(self._olen[j])
        return Spec(i, _prompt(self._ids, self._plen[j], self.vocab),
                    max_new, 0.0)


def prompt_range(mix: dict):
    """(shortest, longest) prompt the mix can send."""
    return int(mix["prompt_len"]["min"]), int(mix["prompt_len"]["max"])
