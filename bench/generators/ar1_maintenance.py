"""Generator ``ar1_maintenance``: closed-loop channel ticks for a user
population, with the edge nodes taken out of service in turn.

Parameters (the traffic file's keys):

``ar1``       ``rho``, ``sigma``, ``mean``, ``lo``, ``hi``: each user's
              channel quality follows q' = mean + rho (q - mean) + N(0,
              sigma), clipped to [lo, hi] (the Gauss-Markov shadowing model
              of Gudmundson, Electronics Letters 27(23), 1991), started
              from its stationary law;
``outage``    ``period``, ``down_for``: every ``period`` channel ticks the
              next edge node, in index order, fails and recovers
              ``down_for`` ticks later.  The schedule is the same for every
              seed, so every seed gets the same work.

Everything random is drawn from the run's ``--seed``.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from bench.common import rng_for


class Ar1Maintenance:
    """``next_channel()`` advances every user's channel by one tick and
    returns ``(quality (U,), attach None, events)``, where ``events`` is the
    list of ``(kind, node)`` infrastructure events due at this tick.  The
    quality array is updated in place by the next call.

    The innovations come from a pool of ``POOL`` standard normal vectors
    drawn at start: tick ``t`` adds pool vector ``t % POOL`` rotated by a
    fresh seeded offset, so a user's innovations are independent draws from
    tick to tick while a tick costs a few passes over the array rather than
    a million normal draws."""

    POOL = 16

    def __init__(self, params: dict, n_users: int, edge_nodes: Sequence[int],
                 seed: int):
        self.U = int(n_users)
        self.rng = rng_for(seed, 1)
        ar = params["ar1"]
        mean, self.rho = float(ar["mean"]), float(ar["rho"])
        self.shift = mean * (1.0 - self.rho)
        sigma = float(ar["sigma"])
        self.lo, self.hi = float(ar["lo"]), float(ar["hi"])
        sd = sigma / np.sqrt(1.0 - self.rho ** 2)
        self.q = np.clip(mean + sd * self.rng.standard_normal(self.U),
                         self.lo, self.hi)
        self.pool = sigma * self.rng.standard_normal((self.POOL, self.U),
                                                     dtype=np.float32)
        self.t = 0
        out = params["outage"]
        self.period = int(out["period"])
        self.down_for = int(out["down_for"])
        assert 0 < self.down_for < self.period
        self.edge_nodes = list(edge_nodes)
        self.down: Optional[int] = None

    def next_channel(self) -> Tuple[np.ndarray, None, List[Tuple[str, int]]]:
        q, U = self.q, self.U
        noise = self.pool[self.t % self.POOL]
        off = int(self.rng.integers(U))
        q *= self.rho
        q += self.shift
        q[:U - off] += noise[off:]
        q[U - off:] += noise[:off]
        np.clip(q, self.lo, self.hi, out=q)
        self.t += 1
        events: List[Tuple[str, int]] = []
        if self.down is not None and self.t % self.period == self.down_for:
            events.append(("recover", self.down))
            self.down = None
        if self.t % self.period == 0:
            k = (self.t // self.period - 1) % len(self.edge_nodes)
            self.down = self.edge_nodes[k]
            events.append(("fail", self.down))
        return q, None, events


def make(params: dict, *, n_users: int, edge_nodes: Sequence[int],
         seed: int) -> Ar1Maintenance:
    return Ar1Maintenance(params, n_users, edge_nodes, seed)
