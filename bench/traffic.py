"""One general traffic generator, driven by a mix file (``bench/traffic/<name>.json``).

A mix file holds parameters only:

* ``loop``: ``"open"`` (requests due on a schedule, whatever the server
  does) or ``"closed"`` (``clients`` callers, each sending its next request
  when the previous one has finished).
* ``prompt`` / ``output``: size distributions, ``{"dist": "fixed",
  "value": n}`` or ``{"dist": "lognormal", "median": m, "sigma": s,
  "min": lo, "max": hi}``.
* open loop: ``rate`` (requests per second, Poisson arrivals).
* closed loop: ``clients``; ``blocks`` blocks of ``block`` requests, each
  block one permutation of the same sizes (the pool, reused once spent);
  ``stagger`` (the first round ends one client after another).
* ``source``: where the sizes come from (read by people, not by the
  generator).
* ``sample_tokens``: served tokens the correctness check samples.

Every seed gets the same sizes and inter-arrival gaps in the same order:
sizes are the distribution's quantiles at ``(i + 0.5) / n`` and gaps the
exponential's quantiles, permuted by a fixed seed (``ORDER_SEED``); the
run's seed draws the token ids and per-request sampling seeds.  So two
seeds give the same work, even where a window sees only a few requests
(a closed loop's handful of admissions).
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import Optional

import numpy as np

__all__ = ["PlannedRequest", "Plan", "quantile_sizes", "make_plan"]

# permutes the sizes and gaps, the same for every run
ORDER_SEED = 20231116


@dataclass(frozen=True)
class PlannedRequest:
    index: int
    prompt: np.ndarray          # (P,) int32 token / event ids
    max_new_tokens: int
    seed: int                   # uint32 per-request sampling seed
    due: Optional[float]        # seconds after the window opens (open loop)


@dataclass(frozen=True)
class Plan:
    loop: str                   # "open" | "closed"
    requests: tuple             # PlannedRequest, in sending order
    clients: int
    rate: Optional[float]
    sample_tokens: int

    def first_round(self) -> list:
        """Closed loop: each client's first request."""
        return [r for r in self.requests if r.index < self.clients]


def _unit_quantiles(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def quantile_sizes(spec: dict, n: int) -> np.ndarray:
    """``n`` sizes at the distribution's quantiles (sorted, int64)."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if dist != "lognormal":
        raise ValueError(f"unknown size distribution {dist!r}")
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf(q) for q in _unit_quantiles(n)])
    vals = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    vals = np.clip(np.rint(vals), int(spec["min"]), int(spec["max"]))
    return vals.astype(np.int64)


def size_bounds(spec: dict) -> tuple[int, int]:
    if spec["dist"] == "fixed":
        return int(spec["value"]), int(spec["value"])
    return int(spec["min"]), int(spec["max"])


def _request(rng, index, p_len, n_out, vocab, due=None):
    prompt = rng.integers(0, vocab, int(p_len), dtype=np.int64).astype(np.int32)
    seed = int(rng.integers(0, 2**32, dtype=np.uint64))
    return PlannedRequest(index, prompt, int(n_out), seed, due)


def make_plan(mix: dict, *, vocab: int, seed: int, seconds: float,
              max_total: Optional[int] = None) -> Plan:
    """Requests for one run of ``seconds`` seconds.

    ``max_total``: largest prompt + output the served configuration takes
    without cutting a request short; a mix that could exceed it is refused.
    """
    if max_total is not None:
        hi = size_bounds(mix["prompt"])[1] + size_bounds(mix["output"])[1]
        if hi > max_total:
            raise ValueError(
                f"mix allows {hi} prompt + output tokens, more than the "
                f"{max_total} this configuration serves uncut")
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(ORDER_SEED)
    sample_tokens = int(mix["sample_tokens"])
    if mix["loop"] == "open":
        rate = float(mix["rate"])
        n = max(1, int(round(rate * seconds)))
        p_lens = order.permutation(quantile_sizes(mix["prompt"], n))
        o_lens = order.permutation(quantile_sizes(mix["output"], n))
        gaps = order.permutation(-np.log1p(-_unit_quantiles(n)) / rate)
        due = np.cumsum(gaps)
        # every request falls due inside the window, at the mix's rate
        due *= seconds * n / (n + 1) / due[-1]
        reqs = tuple(_request(rng, i, p_lens[i], o_lens[i], vocab,
                              due=float(due[i])) for i in range(n))
        return Plan("open", reqs, 0, rate, sample_tokens)
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    clients = int(mix["clients"])
    block = int(mix["block"])
    blocks = int(mix["blocks"])
    p_set = quantile_sizes(mix["prompt"], block)
    o_set = quantile_sizes(mix["output"], block)
    reqs = []
    for b in range(blocks):
        p_lens = order.permutation(p_set)
        o_lens = order.permutation(o_set)
        # rank of each first-round output among the first round's
        rank = np.argsort(np.argsort(o_lens[:clients], kind="stable"), kind="stable")
        for j in range(block):
            i = b * block + j
            n_out = int(o_lens[j])
            if i < clients and mix.get("stagger"):
                # the first round ends one client after another, so
                # completions (and admissions) are spread from the start:
                # the k-th shortest output keeps (k + 1) / clients of its
                # tokens, so every seed's first round ends alike
                n_out = max(1, math.ceil(n_out * (rank[j] + 1) / clients))
            reqs.append(_request(rng, i, p_lens[j], n_out, vocab))
    return Plan("closed", tuple(reqs), clients, None, sample_tokens)
