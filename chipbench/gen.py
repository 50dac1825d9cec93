"""The one traffic generator.  Every traffic mix is a JSON file of
parameters under ``chipbench/traffic``; this module turns a mix and a seed
into the inputs of a run.

Serving lengths are drawn once from the mix's own ``shape_seed``, so every
run seed serves the same sequence of prompt and output lengths; ``--seed``
draws the token ids (and the weights).  A window serves only the first
few hundred requests of a call, so an order drawn per seed would change
the work in it; with one sequence, runs with different seeds do the same
work, and their spread measures the system, not the draw.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


def rng(*words: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(w) for w in words]))


# ---------------------------------------------------------------- training


def _unit(g: np.random.Generator, n: int) -> int:
    """A number in [1, n) drawn until it is prime to ``n``."""
    while math.gcd(a := int(g.integers(1, n)), n) != 1:
        pass
    return a


def train_batch(mix: dict, vocab: int, seed: int, step: int) -> dict:
    """Step ``step``'s batch: ``batch`` rows of ``seq`` next-token pairs,
    every row of every step different.  Ids are uniform in [0, vocab),
    or, where the mix gives ``zipf``, each row is a document with a
    vocabulary of its own: ranks drawn from a Zipf law of that exponent
    over the whole vocabulary, mapped to ids by a permutation drawn for
    the row (rank -> (a * rank + b) mod vocab, a prime to vocab), so that
    rows differ in which ids are frequent."""
    B, S = mix["batch"], mix["seq"]
    g = rng(seed, step)
    if "zipf" not in mix:
        toks = g.integers(0, vocab, size=(B, S + 1), dtype=np.int32)
    else:
        p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(mix["zipf"])
        ranks = np.minimum(np.searchsorted(np.cumsum(p / p.sum()), g.random((B, S + 1))), vocab - 1)
        a = np.array([[_unit(g, vocab)] for _ in range(B)], np.int64)
        b = g.integers(0, vocab, size=(B, 1))
        toks = ((a * ranks + b) % vocab).astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


# ---------------------------------------------------------------- serving


def _lengths(spec: dict, n: int, g: np.random.Generator) -> np.ndarray:
    """``n`` lengths from a lognormal with the given median and sigma,
    then clipped to [min, max] or rounded up to the next bucket."""
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * g.standard_normal(n))
    if "buckets" in spec:
        b = np.asarray(spec["buckets"])
        idx = np.minimum(np.searchsorted(b, x, side="left"), len(b) - 1)
        return b[idx].astype(np.int64)
    return np.clip(np.ceil(x), spec["min"], spec["max"]).astype(np.int64)


@dataclass
class ServeRequest:
    index: int
    prompt: list
    max_new_tokens: int


def backlog_call(mix: dict, vocab: int, seed: int, call: int) -> list[ServeRequest]:
    """Call ``call``'s ``requests_per_call`` requests: the mix's own
    sequence of lengths, the same for every seed, with token ids drawn
    from (seed, call)."""
    n = mix["requests_per_call"]
    g = rng(mix["shape_seed"], 1)
    plen = _lengths(mix["prompt_len"], n, g)
    olen = _lengths(mix["output_len"], n, g)
    r = rng(seed, 3, call)
    return [
        ServeRequest(call * n + i, r.integers(0, vocab, int(plen[i])).tolist(), int(olen[i]))
        for i in range(n)
    ]
