"""Shared helpers for the paper-figure reproductions.

Every bench function yields ``Row(name, us_per_call, derived)`` records; the
``derived`` field carries the paper-facing metric (energy, placement,
feature count...) as a compact ``key=value;...`` string so ``run.py`` can
emit a uniform CSV.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable


@dataclass
class Row:
    name: str
    us_per_call: float
    derived: str

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.3f},{self.derived}"


def timed(fn: Callable, *args, repeats: int = 3, **kwargs):
    """Run fn repeatedly; return (last_result, best_us)."""
    best = float("inf")
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return out, best


def kv(**kwargs) -> str:
    parts = []
    for k, v in kwargs.items():
        if isinstance(v, float):
            parts.append(f"{k}={v:.6g}")
        else:
            parts.append(f"{k}={v}")
    return ";".join(parts)
