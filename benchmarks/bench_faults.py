"""Fault-tolerance benchmark: checkpoint overhead, restore latency, and
serving throughput under injected telemetry corruption.

Three measurement families over the population orchestrator:

  ``fault_checkpoint_off``  the cost of the crash-consistency plumbing
                            when it is DISABLED.  One AR(1) trace is run
                            three ways on the same synchronous path: a
                            bare ``step_arrays`` loop (no fault-tolerance
                            plumbing at all), ``run_arrays`` with
                            checkpointing off (crash hooks + boundary
                            checks, all dormant), and ``run_arrays``
                            with boundary checkpoints every k ticks.
                            ``off_overhead`` = bare-loop time over
                            dormant-plumbing time (1.0 = free; this is
                            the CI-gated ratio), and the enabled cost is
                            reported as ``on_ms``/``save_ms`` for
                            inspection.  All three runs are asserted
                            bit-identical tick-by-tick (saves must not
                            perturb serving state).
  ``fault_restore``         cold-start recovery: a FRESH orchestrator
                            restores the final checkpoint and replays the
                            trace tail.  ``agree`` asserts the resumed
                            tail is bit-identical to the uninterrupted
                            run (reports minus wall-clock timing fields,
                            plus incumbent arrays); ``restore_ms`` is the
                            restore() latency alone.
  ``fault_quarantine``      large-population serving under telemetry
                            corruption (NaN/Inf/negative/stuck via
                            ``FaultPlan``) with the quarantine policy:
                            corrupt-feed throughput relative to the clean
                            feed, plus quarantine/recovery volumes.

Timing protocol: interleaved best-of-N per benchmarks/common.py
convention; checkpoint directories live in a TemporaryDirectory so
repeated passes never collide.
"""
from __future__ import annotations

import dataclasses
import tempfile
import time
from typing import Iterable

import numpy as np

from repro.core.faults import FaultPlan, corrupt_specs
from repro.core.online import (TIMING_FIELDS, ChurnOrchestrator,
                               population_cohorts)
from repro.core.population import TelemetryPolicy

from .common import Row, kv, smoke


def _reports_equal(a, b) -> bool:
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        da, db = dataclasses.asdict(ra), dataclasses.asdict(rb)
        for k in TIMING_FIELDS:
            da.pop(k), db.pop(k)
        if da != db:
            return False
    return True


def _build(users: int, **pop_kw) -> ChurnOrchestrator:
    pops = population_cohorts(users, n_extra_edge=1, gamma=8, **pop_kw)
    return ChurnOrchestrator(population=pops, hysteresis=0.05)


def _trace(ticks: int, users: int, seed: int = 11) -> np.ndarray:
    rng = np.random.default_rng(seed)
    q = np.empty((ticks, users))
    q[0] = 0.4 + 0.4 * rng.random(users)
    for t in range(1, ticks):        # AR(1) fading around the start state
        q[t] = np.clip(0.9 * q[t - 1] + 0.1 * 0.6
                       + 0.05 * rng.standard_normal(users), 0.05, 1.0)
    return q


def _checkpoint_rows(*, users: int, ticks: int, every: int,
                     trials: int) -> Iterable[Row]:
    Q = _trace(ticks, users)
    t_loop = t_off = t_on = restore_ms = float("inf")
    r_loop = r_off = r_on = None
    with tempfile.TemporaryDirectory() as root:
        for i in range(trials):
            # bare loop: the serving work with zero fault-tolerance
            # plumbing, on the same synchronous path
            o0 = _build(users)
            t0 = time.perf_counter()
            r_loop = [o0.step_arrays(quality=Q[t]) for t in range(ticks)]
            t_loop = min(t_loop, time.perf_counter() - t0)
            # dormant plumbing: crash hooks + boundary checks, all off
            o = _build(users)
            t0 = time.perf_counter()
            r_off = o.run_arrays(Q, stream=False)
            t_off = min(t_off, time.perf_counter() - t0)
            # enabled: boundary saves every k ticks + final save
            d = f"{root}/ck{i}"
            o2 = _build(users)
            t0 = time.perf_counter()
            r_on = o2.run_arrays(Q, stream=False, checkpoint_dir=d,
                                 checkpoint_every=every)
            t_on = min(t_on, time.perf_counter() - t0)
        assert _reports_equal(r_loop, r_off), \
            "dormant fault-tolerance plumbing perturbed the serving state"
        assert _reports_equal(r_off, r_on), \
            "boundary checkpointing perturbed the serving state"
        n_saves = ticks // every + (1 if ticks % every else 0)
        off_overhead = t_loop / t_off
        yield Row("fault_checkpoint_off", t_off / ticks * 1e6,
                  kv(users=users, ticks=ticks, every=every,
                     loop_ms=t_loop * 1e3, off_ms=t_off * 1e3,
                     on_ms=t_on * 1e3, off_overhead=off_overhead,
                     save_ms=(t_on - t_off) / max(1, n_saves) * 1e3,
                     n_saves=n_saves))

        # restore latency + resumed-tail bit-identity, against the LAST
        # trial's checkpoint tree
        d = f"{root}/ck{trials - 1}"
        for _ in range(trials):
            o3 = _build(users)
            t0 = time.perf_counter()
            pos = o3.restore(d)
            restore_ms = min(restore_ms,
                             (time.perf_counter() - t0) * 1e3)
        # the final save sits at end-of-trace; replay from the boundary
        # checkpoint instead so a real tail is re-served
        from repro.runtime import checkpoint as ckpt
        steps = ckpt.available_steps(d)
        o4 = _build(users)
        pos = o4.restore(d, step=steps[0])
        tail = o4.run_arrays(Q[pos:], _trace_offset=pos)
        agree = int(_reports_equal(r_off[pos:], tail))
        assert agree == 1, "resumed tail diverged from uninterrupted run"
        yield Row("fault_restore", restore_ms * 1e3,
                  kv(users=users, restore_ms=restore_ms,
                     resumed_ticks=len(tail), agree=agree))


def _quarantine_row(*, users: int, ticks: int) -> Row:
    Q = _trace(ticks, users, seed=5)
    plan = FaultPlan(seed=2, specs=corrupt_specs(
        range(1, ticks, 2), kind="nan",
        users_per_tick=max(1, users // 100)) + corrupt_specs(
        range(2, ticks, 3), kind="stuck", stuck_len=2))
    Qc, info = plan.corrupt(Q)

    o = _build(users)
    t0 = time.perf_counter()
    r_clean = o.run_arrays(Q)
    t_clean = time.perf_counter() - t0

    oq = _build(users, telemetry=TelemetryPolicy(mode="quarantine"))
    t0 = time.perf_counter()
    r_corrupt = oq.run_arrays(Qc)
    t_corrupt = time.perf_counter() - t0

    n_quar = sum(r.n_quarantined for r in r_corrupt)
    n_rec = sum(r.n_recovered for r in r_corrupt)
    assert n_quar > 0, "corruption schedule produced no quarantines"
    user_ticks = users * ticks
    return Row("fault_quarantine", t_corrupt / user_ticks * 1e6,
               kv(users=users, ticks=ticks, injected=len(info),
                  quarantined=n_quar, recovered=n_rec,
                  user_ticks_per_s=user_ticks / t_corrupt,
                  clean_user_ticks_per_s=user_ticks / t_clean,
                  quarantine_overhead=t_clean / t_corrupt))


def run() -> Iterable[Row]:
    if smoke():
        users, ticks, every, trials = 64, 8, 3, 2
        quar_users, quar_ticks = 2_000, 6
    else:
        users, ticks, every, trials = 512, 24, 6, 3
        quar_users, quar_ticks = 100_000, 10
    yield from _checkpoint_rows(users=users, ticks=ticks, every=every,
                                trials=trials)
    yield _quarantine_row(users=quar_users, ticks=quar_ticks)
