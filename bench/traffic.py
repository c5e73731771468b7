"""The one traffic generator: reads a traffic file's parameters and makes
the inputs of a run from its seed.

Every seed gets the same set of sizes: lengths are the stratified
quantiles of the stated distribution, paired by a fixed permutation, and
the seed only shuffles their order and draws the token ids.
"""
from __future__ import annotations

import json
import math
import statistics
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


def load_traffic(name: str) -> dict:
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _rng(seed: int, *more: int):
    return np.random.default_rng([int(seed) % 2**64, *more])


def quantiles(d: dict, n: int) -> np.ndarray:
    """n stratified draws of a clipped lognormal ``d`` (median, sigma,
    min, max), as whole numbers, in increasing order."""
    if d["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    z = statistics.NormalDist()
    vals = [d["median"] * math.exp(d["sigma"] * z.inv_cdf((i + 0.5) / n))
            for i in range(n)]
    return np.clip(np.rint(vals), d["min"], d["max"]).astype(np.int64)


def request_sizes(t: dict) -> list[tuple[int, int]]:
    """The pool of (prompt length, output length), the same for every
    seed."""
    n = t["pool"]
    prompts = quantiles(t["prompt"], n)
    outputs = quantiles(t["output"], n)[np.random.default_rng(0).permutation(n)]
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


class Requests:
    """Requests of a serving traffic file, in the seed's order, without
    end: the pool is walked again, with fresh token ids, when it runs
    out.  Token ids are uniform over 1..vocab-1 (id 0 is left out so that
    a prefill chunk's real tokens can be told from its zero padding)."""

    def __init__(self, t: dict, seed: int, vocab: int):
        self.sizes = request_sizes(t)
        self.seed, self.vocab = seed, vocab
        self.order = _rng(seed).permutation(len(self.sizes))
        self.i = 0

    def next(self) -> tuple[np.ndarray, int]:
        k, lap = self.i % len(self.sizes), self.i // len(self.sizes)
        self.i += 1
        plen, olen = self.sizes[self.order[k]]
        toks = _rng(self.seed, 1, lap, k).integers(1, self.vocab, size=plen,
                                                   dtype=np.int32)
        return toks, olen


class TrainTokens:
    """A training traffic file's batches: rows of ``seq + 1`` token ids
    uniform over the vocabulary, a fresh draw for every row of every step.
    ``global_batch(step)`` gives the (tokens, labels) pair, labels the
    tokens shifted by one."""

    def __init__(self, t: dict, seed: int, vocab: int):
        self.batch, self.seq = t["batch"], t["seq"]
        self.seed, self.vocab = seed, vocab

    def global_batch(self, step: int) -> dict[str, np.ndarray]:
        toks = _rng(self.seed, 2, step).integers(
            0, self.vocab, size=(self.batch, self.seq + 1), dtype=np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
