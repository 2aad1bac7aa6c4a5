"""The one traffic generator: turns a mix's parameters and a seed into
requests.

Every seed gets the same sizes, in the same order;
the seed draws the prompt tokens (and the weights, elsewhere).  Each
block of requests takes its lengths at the block's stratified quantiles
``(i + 0.5) / n`` of the mix's distribution, shuffled by a generator that
does not depend on the seed.  A window holds only a few tens of requests,
so a seed that drew which sizes fall inside it would change the work it
measures; here two seeds differ in what the model computes, never in how
much.

One kind of mix so far, ``backlog``: an offline batch.  Every request is
due at once and the harness keeps at least ``queue_min`` waiting.  With
``warm_start`` the first ``num_slots`` requests stand for requests
already part way through their output when the window opens: each keeps
one of the shares ``(i + 0.5) / num_slots`` of its output budget, so
slots turn over from the start as they do in steady state.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it."""
    idx: int
    prompt: List[int]
    max_new: int


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator per purpose, from any whole-number seed."""
    tag = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng(
        np.random.SeedSequence([seed % 2 ** 64, tag % 2 ** 64]))


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n lengths at the stratified quantiles of ``dist``, shuffled by
    ``rng``."""
    q = quantiles(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "loguniform":
        v = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
        v = np.clip(np.round(v), lo, hi)
    elif dist["dist"] == "uniform":
        v = lo + np.floor(q * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return rng.permutation(v.astype(np.int64))


class Traffic:
    """Requests of one mix for one seed and one window length."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec = spec
        self.kind = spec["kind"]
        self.vocab = int(vocab)
        self.num_slots = int(spec["engine"]["num_slots"])
        self._sizes = rng_for(0, "sizes")
        self._tokens = rng_for(seed, "tokens")
        self._idx = 0
        if self.kind != "backlog":
            raise ValueError(f"unknown traffic kind {self.kind!r}")

    def _make(self, plen: int, out: int) -> Planned:
        p = Planned(self._idx, self._tokens.integers(
            0, self.vocab, int(plen)).tolist(), int(out))
        self._idx += 1
        return p

    def backlog(self) -> Iterator[Planned]:
        """Endless offline backlog, all due at once."""
        s, n = self.spec, int(self.spec["block"])
        first = True
        while True:
            if first and s.get("warm_start"):
                k = self.num_slots
                plen = lengths(s["prompt_len"], k, self._sizes)
                out = lengths(s["output_len"], k, self._sizes)
                share = self._sizes.permutation(quantiles(k))
                out = np.maximum(1, np.ceil(out * share)).astype(np.int64)
                for a, b in zip(plen, out):
                    yield self._make(a, b)
            first = False
            plen = lengths(s["prompt_len"], n, self._sizes)
            out = lengths(s["output_len"], n, self._sizes)
            for a, b in zip(plen, out):
                yield self._make(a, b)
