"""Reasoning sessions for a closed serving loop, from a seed.

``first_wave(n)`` gives the sessions that hold the slots when the window
opens: each caught mid-generation, its context depth drawn uniformly from
``first_wave.depth`` (the prompt the admission path prefills) and its
remaining output budget uniformly from ``first_wave.budget``, capped so
that every position it decodes fits the cache.  ``next_request()`` gives
the request that takes a freed slot: a prompt length lognormal around
``prompt.median`` (sigma ``prompt.sigma``, clipped to ``[lo, hi]``) and an
output budget lognormal around ``output.median`` (clipped to ``lo`` ...
the cache length less the prompt).  Tokens are uniform over the
vocabulary: with random weights no text is more typical than another.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from bench.common import rng_for


class ReasonSessions:
    def __init__(self, p: dict, vocab: int, cache_len: int, seed: int):
        self.p, self.V, self.T = p, int(vocab), int(cache_len)
        self.rng = rng_for(seed, 11)

    def _tokens(self, n: int) -> List[int]:
        return self.rng.integers(0, self.V, int(n)).tolist()

    def _lognormal(self, q: dict, lo: int, hi: int) -> int:
        x = self.rng.lognormal(np.log(q["median"]), q["sigma"])
        return int(np.clip(round(x), lo, hi))

    def first_wave(self, n: int) -> List[Tuple[List[int], int]]:
        (d0, d1), (b0, b1) = self.p["first_wave"]["depth"], \
            self.p["first_wave"]["budget"]
        out = []
        for _ in range(n):
            depth = int(self.rng.integers(d0, d1 + 1))
            budget = min(int(self.rng.integers(b0, b1 + 1)), self.T - depth)
            out.append((self._tokens(depth), budget))
        return out

    def next_request(self) -> Tuple[List[int], int]:
        pq, oq = self.p["prompt"], self.p["output"]
        n = self._lognormal(pq, pq["lo"], pq["hi"])
        return self._tokens(n), self._lognormal(oq, oq["lo"], self.T - n)


def make(params: dict, *, vocab: int, cache_len: int, seed: int
         ) -> ReasonSessions:
    return ReasonSessions(params, vocab, cache_len, seed)
