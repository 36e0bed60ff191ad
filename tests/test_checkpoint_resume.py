"""Crash-consistent serving: checkpoint/restore of the full population
state through ``ChurnOrchestrator``.

The oracle everywhere is bit-exactness: a run that is killed and resumed
from its newest checkpoint must produce the same TickReports (minus
wall-clock timing fields) and the same incumbent arrays as the same run
left uninterrupted — in plain, congestion-coupled, and contingency-armed
modes.  Crash points are driven deterministically by
``FaultPlan.crash_hook`` (the SIGKILL variant lives in
tests/test_faults_subprocess.py).
"""
import dataclasses
import json
import pathlib

import numpy as np
import pytest

from repro.core.capacity import SharedCapacity
from repro.core.faults import FaultPlan, FaultSpec, InjectedCrash
from repro.core.online import (TIMING_FIELDS, ChurnOrchestrator,
                               population_cohorts)
from repro.runtime import checkpoint as ckpt

T, U, SEED = 12, 24, 7


def _trace():
    rng = np.random.default_rng(SEED)
    Q = 0.4 + 0.6 * rng.random((T, U))
    A = rng.integers(0, 3, size=(T, U))
    return Q, A


def build(mode="plain"):
    pops = population_cohorts(U, n_extra_edge=1, gamma=8)
    kw = {}
    if mode == "congestion":
        N = pops[0].N
        nc = np.full(N, np.inf)
        lc = np.full((N, N), np.inf)
        nc[2] = 120.0                    # one contended edge helper
        kw["shared_capacity"] = SharedCapacity(node_cap=nc, link_cap=lc)
    if mode == "contingency":
        kw["contingency"] = True
    return ChurnOrchestrator(population=pops, hysteresis=0.05, **kw)


def assert_reports_equal(a, b):
    assert len(a) == len(b), (len(a), len(b))
    for ra, rb in zip(a, b):
        da, db = dataclasses.asdict(ra), dataclasses.asdict(rb)
        for k in TIMING_FIELDS:
            da.pop(k), db.pop(k)
        assert da == db, (ra.tick,
                          {k: (da[k], db[k]) for k in da if da[k] != db[k]})


def snap_incumbents(o):
    return [(p.inc_found.copy(), p._inc_exit.copy(), p._inc_place.copy(),
             p._inc_energy.copy()) for p in o.pops]


def assert_inc_equal(sa, sb):
    for a, b in zip(sa, sb):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# kill-free oracle: save at boundaries, resume in a FRESH orchestrator
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["plain", "congestion", "contingency"])
def test_resume_is_bit_identical(mode, tmp_path):
    Q, A = _trace()
    o1 = build(mode)
    r1 = o1.run_arrays(Q, A)

    d = str(tmp_path / "ck")
    o2 = build(mode)
    r2a = o2.run_arrays(Q[:7], A[:7], checkpoint_dir=d, checkpoint_every=4)
    o3 = build(mode)
    r2b = o3.resume(d, Q, A)            # restores trace_pos=7 (final save)
    assert len(r2a) + len(r2b) == T
    assert_reports_equal(r1, r2a + r2b)
    assert_inc_equal(snap_incumbents(o1), snap_incumbents(o3))


@pytest.mark.parametrize("mode", ["plain", "congestion", "contingency"])
def test_mid_boundary_restore(mode, tmp_path):
    Q, A = _trace()
    o1 = build(mode)
    r1 = o1.run_arrays(Q, A)

    d = str(tmp_path / "ck")
    build(mode).run_arrays(Q[:7], A[:7], checkpoint_dir=d,
                           checkpoint_every=4)
    steps = ckpt.available_steps(d)
    assert len(steps) >= 2              # boundary save + final save
    o4 = build(mode)
    pos = o4.restore(d, step=steps[0])
    assert pos == 4
    r3 = o4.run_arrays(Q[pos:], A[pos:], _trace_offset=pos)
    assert_reports_equal(r1[pos:], r3)
    assert_inc_equal(snap_incumbents(o1), snap_incumbents(o4))


def test_checkpoint_off_run_unchanged(tmp_path):
    Q, A = _trace()
    r_off = build().run_arrays(Q, A)
    r_on = build().run_arrays(Q, A, checkpoint_dir=str(tmp_path / "ck"),
                              checkpoint_every=5)
    assert_reports_equal(r_off, r_on)


def test_checkpoint_every_requires_dir():
    Q, A = _trace()
    with pytest.raises(ValueError, match="checkpoint_dir"):
        build().run_arrays(Q, A, checkpoint_every=3)


# ---------------------------------------------------------------------------
# injected crashes at every pipeline stage, then resume
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stage", ["ingest", "relax", "post"])
def test_crash_and_resume_every_stage(stage, tmp_path):
    Q, A = _trace()
    r_clean = build().run_arrays(Q, A)
    plan = FaultPlan(specs=[FaultSpec(kind="crash", tick=6, stage=stage)])
    d = str(tmp_path / "ck")
    o = build()
    with pytest.raises(InjectedCrash):
        o.run_arrays(Q, A, checkpoint_dir=d, checkpoint_every=3,
                     fault_plan=plan)
    o2 = build()
    tail = o2.resume(d, Q, A)           # plan not passed: crash cleared
    pos = T - len(tail)
    assert pos in (3, 6)                # last boundary before the crash
    assert_reports_equal(r_clean[pos:], tail)


def test_resume_rejects_short_trace(tmp_path):
    Q, A = _trace()
    d = str(tmp_path / "ck")
    build().run_arrays(Q, A, checkpoint_dir=d, checkpoint_every=4)
    with pytest.raises(ValueError, match="trace"):
        build().resume(d, Q[:3], A[:3])


# ---------------------------------------------------------------------------
# damage handling at the orchestrator level
# ---------------------------------------------------------------------------

def test_restore_skips_damaged_newest_step(tmp_path):
    Q, A = _trace()
    d = str(tmp_path / "ck")
    build().run_arrays(Q[:7], A[:7], checkpoint_dir=d, checkpoint_every=4)
    steps = ckpt.available_steps(d)
    assert len(steps) >= 2
    # truncate the newest checkpoint's array payload
    newest = pathlib.Path(d) / f"step_{steps[-1]:012d}" / ckpt.ARRAYS
    newest.write_bytes(newest.read_bytes()[:20])
    o = build()
    pos = o.restore(d)                  # falls back to the older step
    assert pos == 4
    r = o.run_arrays(Q[pos:], A[pos:], _trace_offset=pos)
    r_clean = build().run_arrays(Q, A)
    assert_reports_equal(r_clean[pos:], r)


def test_restore_rejects_wrong_population(tmp_path):
    Q, A = _trace()
    d = str(tmp_path / "ck")
    build().run_arrays(Q[:5], A[:5], checkpoint_dir=d, checkpoint_every=5)
    pops = population_cohorts(U - 4, n_extra_edge=1, gamma=8)
    o = ChurnOrchestrator(population=pops, hysteresis=0.05)
    with pytest.raises(ValueError, match="users"):
        o.restore(d)


def test_restore_rejects_congestion_mismatch(tmp_path):
    Q, A = _trace()
    d = str(tmp_path / "ck")
    build("congestion").run_arrays(Q[:5], A[:5], checkpoint_dir=d,
                                   checkpoint_every=5)
    with pytest.raises(ValueError, match="congestion"):
        build("plain").restore(d)


def test_restore_empty_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        build().restore(str(tmp_path / "nothing"))


def test_checkpoint_extra_records_trace_position(tmp_path):
    Q, A = _trace()
    d = str(tmp_path / "ck")
    build().run_arrays(Q, A, checkpoint_dir=d, checkpoint_every=6)
    steps = ckpt.available_steps(d)
    for s in steps:
        man = json.loads((pathlib.Path(d) / f"step_{s:012d}" /
                          ckpt.MANIFEST).read_text())
        extra = man["extra"]
        assert extra["n_users"] == U
        assert extra["trace_pos"] in (6, T)
