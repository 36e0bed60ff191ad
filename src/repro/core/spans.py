"""Program spans: the population tick's and the serving step's wall-clock
breakdown.

``span(on, stats, field, name)`` times one region of a tick.  ``on`` is the
one switch, ``Population(timing=...)`` (the orchestrator's spans follow its
cohorts) or ``SplitServeEngine(timing=...)``.  Off, the call returns one shared null context: it costs the flag
check and allocates nothing.  On, it enters
``jax.profiler.TraceAnnotation(name, **meta)``, which puts the region on the
profiler's ``/host:CPU`` plane, on the same clock as the device's ``XLA
Ops`` line (next to nothing without a profiler session), and on exit adds
the elapsed milliseconds to ``stats.<field>``.  ``field=None`` gives an
annotation-only span.  ``meta`` rides on the annotation as its arguments
(the re-key passes the number of users it touched).

Span names are ``orch.*`` for the orchestrator, ``pop.*`` for a cohort and
``serve.*`` for the serving engine;
nesting follows the call tree, so a span's self time is its duration less
its children's.
"""
from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax.profiler

__all__ = ["span"]

_NULL = contextlib.nullcontext()


class _Span:
    __slots__ = ("_stats", "_field", "_ann", "_t0")

    def __init__(self, stats, field: Optional[str], ann):
        self._stats = stats
        self._field = field
        self._ann = ann
        self._t0 = 0.0

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt_ms = (time.perf_counter() - self._t0) * 1e3
        self._ann.__exit__(*exc)
        if self._field is not None:
            setattr(self._stats, self._field,
                    getattr(self._stats, self._field) + dt_ms)
        return False


def span(on: bool, stats, field: Optional[str], name: str, **meta):
    """A context that times ``name`` into ``stats.<field>`` when ``on``."""
    if not on:
        return _NULL
    return _Span(stats, field, jax.profiler.TraceAnnotation(name, **meta))
