"""Program spans of the population tick (``core/spans.py``).

One switch, ``Population(timing=...)``: on, every span adds its wall time
to a ``PopulationStats`` / ``TickReport`` field and lands on the
profiler's host plane, nested as the call tree nests; off, no field moves
and no ``TraceAnnotation`` is built.  Decisions never depend on it.
"""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.core import ChurnOrchestrator, population_cohorts
from repro.core.online import TIMING_FIELDS
from repro.core.scenarios import ChurnEvent

U = 180
T = 4
POP_TIMING = ("t_ingest_ms", "t_relax_ms", "t_post_ms", "t_rekey_ms",
              "t_group_ms")


def _trace(seed=11, ticks=T):
    rng = np.random.default_rng(seed)
    return np.clip(0.55 + 0.25 * rng.standard_normal((ticks, U)), 0.05, 1.0)


def _orch(timing):
    pops = population_cohorts(U, n_extra_edge=2, timing=timing)
    return ChurnOrchestrator(population=pops, hysteresis=0.05)


def _run(o):
    """Channel ticks through the streaming pipeline and the synchronous
    tick, then an edge node down and back up, each event a tick of its
    own.  Returns the reports with the index of the two outage ticks."""
    q = _trace()
    node = o._edge_nodes[0]
    reps = list(o.run_arrays(q[:2]))
    reps.append(o.step_arrays(q[2]))
    outage = [len(reps)]
    reps.append(o.step([ChurnEvent("fail", None, node)]))
    reps.append(o.step_arrays(q[3]))
    outage.append(len(reps))
    reps.append(o.step([ChurnEvent("recover", None, node)]))
    return reps, outage


def test_spans_time_every_phase_of_the_tick():
    o = _orch(True)
    reps, outage = _run(o)
    resolved = [r for r in reps if r.n_resolved]
    assert resolved
    for r in resolved:
        assert r.t_gate_ms > 0.0, r
        assert r.t_group_ms > 0.0, r
        assert r.t_account_ms > 0.0, r
    for i in outage:
        assert reps[i].t_rekey_ms > 0.0, reps[i]
    for i, r in enumerate(reps):
        if i not in outage:
            assert r.t_ingest_ms > 0.0, r
    assert all(r.t_reprice_ms == 0.0 for r in reps)    # no congestion


def test_rekeyed_users_counts_a_whole_cohort_mask_flip():
    o = _orch(True)
    o.run_arrays(_trace()[:1])
    node = o._edge_nodes[1]
    before = [p.stats.rekeyed_users for p in o.pops]
    o.step([ChurnEvent("fail", None, node)])
    grew = [p.stats.rekeyed_users - b for p, b in zip(o.pops, before)]
    # the mask flip re-keys every user; the tick's resolves may re-key
    # more (deferred requantization of the re-placing users)
    assert all(g >= p.U for g, p in zip(grew, o.pops)), grew
    p = o.pops[0]
    n0 = p.stats.rekeyed_users
    p.unmask_node(node)
    assert p.stats.rekeyed_users - n0 == p.U
    p.unmask_node(node)                       # nothing flips: no re-key
    assert p.stats.rekeyed_users - n0 == p.U


def test_timing_changes_no_decision():
    on, off = _orch(True), _orch(False)
    ra, _ = _run(on)
    rb, _ = _run(off)
    assert len(ra) == len(rb)
    for a, b in zip(ra, rb):
        da, db = dataclasses.asdict(a), dataclasses.asdict(b)
        for k in TIMING_FIELDS:
            da.pop(k), db.pop(k)
        assert da == db, a.tick
    for p1, p2 in zip(on.pops, off.pops):
        np.testing.assert_array_equal(p1._inc_exit, p2._inc_exit)
        np.testing.assert_array_equal(p1._inc_place, p2._inc_place)
        np.testing.assert_array_equal(p1._inc_energy, p2._inc_energy)
        assert p1.stats.rekeyed_users == p2.stats.rekeyed_users


def test_timing_off_builds_no_annotation(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("TraceAnnotation built with timing off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    o = _orch(False)
    reps, _ = _run(o)
    for r in reps:
        for f in TIMING_FIELDS:
            assert getattr(r, f) == 0.0, (r.tick, f)
    for p in o.pops:
        for f in POP_TIMING:
            assert getattr(p.stats, f) == 0.0, f
        assert p.stats.rekeyed_users > 0      # counters are not timing


def _host_lines(trace_dir):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    pd = ProfileData.from_file(files[0])
    host = [p for p in pd.planes if p.name == "/host:CPU"]
    assert host
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for e in line.events
             if e.name.startswith(("orch.", "pop."))]
            for line in host[0].lines]


def test_spans_nest_on_the_profilers_host_plane(tmp_path):
    o = _orch(True)
    o.run_arrays(_trace()[:1])
    with jax.profiler.trace(str(tmp_path)):
        o.step([ChurnEvent("fail", None, o._edge_nodes[0])])
    lines = [ln for ln in _host_lines(str(tmp_path))
             if any(n == "orch.tick" for n, _s, _e in ln)]
    assert len(lines) == 1
    (line,) = lines
    (tick,) = [(s, e) for n, s, e in line if n == "orch.tick"]
    for name in ("orch.gate", "pop.rekey", "pop.group", "orch.account"):
        inner = [(s, e) for n, s, e in line if n == name]
        assert inner, name
        assert all(tick[0] <= s and e <= tick[1] for s, e in inner), name
