"""Seeded traffic: request sizes and arrival times from a mix file.

A mix file (``chipbench/traffic/<name>.json``) holds parameters only.
Every seed gets the same multiset of sizes and inter-arrival gaps,
drawn as evenly spaced quantiles of the mix's distributions; the seed
only permutes them and draws the token ids.  So two seeds offer the
same work in another order, and a tail such as a 95th percentile does
not move with the seed.

Length distributions (``{"kind": ...}``):

- ``lognormal``: ``median``, ``sigma``, clipped to [``min``, ``max``];
- ``uniform``: integers evenly spread over [``min``, ``max``].

Arrivals (open loop): ``{"kind": "poisson", "rate_per_s": r}``, gaps
are the quantiles of an exponential distribution of mean 1/r.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: Dict, n: int) -> np.ndarray:
    """``n`` token counts, sorted ascending, from a length distribution."""
    q = _quantiles(n)
    kind = dist["kind"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        vals = dist["min"] + q * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def arrival_gaps(arrivals: Dict, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps in seconds, sorted ascending."""
    if arrivals["kind"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['kind']!r}")
    return -np.log1p(-_quantiles(n)) / float(arrivals["rate_per_s"])


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream): seeds may exceed 32
    bits, which ``SeedSequence`` takes whole."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def make_requests(mix: Dict, n: int, vocab: int, seed: int,
                  id_base: int = 0) -> List[Dict]:
    """``n`` requests of the mix: the quantile sets of prompt and output
    lengths, each permuted by the seed, with seeded token ids."""
    r = rng_for(seed, 1)
    plen = r.permutation(lengths(mix["prompt_tokens"], n))
    olen = r.permutation(lengths(mix["output_tokens"], n))
    toks = rng_for(seed, 2)
    return [{"id": id_base + i,
             "prompt": toks.integers(0, vocab, size=int(p)).astype(np.int32),
             "max_new_tokens": int(o)}
            for i, (p, o) in enumerate(zip(plen, olen))]


def open_loop_schedule(mix: Dict, seconds: float, vocab: int,
                       seed: int) -> List[Dict]:
    """Requests due within ``seconds``, each with its ``due`` offset.

    The count is ``ceil(rate * seconds)``; the gaps are that many
    exponential quantiles (their sum is just under ``seconds``) in an
    order drawn from the seed."""
    rate = float(mix["arrivals"]["rate_per_s"])
    n = max(1, math.ceil(rate * seconds))
    gaps = rng_for(seed, 3).permutation(arrival_gaps(mix["arrivals"], n))
    due = np.cumsum(gaps) - gaps[0]          # the first is due at once
    reqs = make_requests(mix, n, vocab, seed)
    for r, t in zip(reqs, due):
        r["due"] = float(t)
    return [r for r in reqs if r["due"] < seconds]


class Backlog:
    """An endless seeded request stream for a backlog mix: block ``b``
    holds ``block`` requests with the quantile sets permuted by
    ``(seed, b)``, so any prefix of the stream has the same sizes for
    every seed up to the order within a block."""

    def __init__(self, mix: Dict, vocab: int, seed: int, block: int = 256):
        self.mix, self.vocab, self.seed, self.block = mix, vocab, seed, block
        self._buf: List[Dict] = []
        self._next_block = 0

    def take(self, n: int) -> List[Dict]:
        while len(self._buf) < n:
            b = self._next_block
            self._buf += make_requests(self.mix, self.block, self.vocab,
                                       self.seed * 1_000_003 + b,
                                       id_base=b * self.block)
            self._next_block += 1
        out, self._buf = self._buf[:n], self._buf[n:]
        return out


def _smooth(x: np.ndarray, iters: int = 8) -> np.ndarray:
    """Neighbour averaging along height and width: low-frequency class
    templates."""
    for _ in range(iters):
        x = (x + np.roll(x, 1, 0) + np.roll(x, -1, 0)
             + np.roll(x, 1, 1) + np.roll(x, -1, 1)) / 5.0
    return x


def image_batches(data: Dict, batches: int, batch: int, seed: int):
    """``batches`` batches of ``batch`` labelled images shaped like the
    data set the mix names (``height`` x ``width`` x ``channels``, 10
    classes): a smooth template per class, scaled by a random contrast,
    plus noise; flattened to rows.  All rows differ.  Returns a list of
    (x float32 (batch, h*w*c), y int32 (batch,))."""
    h, w, ch = data["height"], data["width"], data["channels"]
    classes = data["classes"]
    r = rng_for(seed, 7)
    t = np.stack([_smooth(x) for x in r.normal(size=(classes, h, w, ch))])
    t = (t / np.abs(t).max(axis=(1, 2, 3), keepdims=True)).astype(np.float32)
    out = []
    for _ in range(batches):
        y = r.integers(0, classes, size=batch).astype(np.int32)
        contrast = r.uniform(0.7, 1.3, size=(batch, 1, 1, 1))
        x = t[y] * contrast + data["noise"] * r.normal(size=(batch, h, w, ch))
        out.append((x.reshape(batch, -1).astype(np.float32), y))
    return out
