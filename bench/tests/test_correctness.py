"""What decides ``correct``: sound runs pass, the control fails, and each
fault the cells can have, planted under the timed path, turns ``correct``
false.  The runs skip the harness's look for a chip and use tiny sizes."""
import numpy as np
import pytest

from bench.common import ROOT
from bench.run import run_cell
from bench.tests.conftest import POP_TINY

POP = ["--workload", "pop-paper-1m-ar1", "--seed", "777000111222",
       "--seconds", "2", "--trace", "0"]


def _pop(argv=POP, **over):
    return run_cell(argv, require_chip=False, root=ROOT,
                    overrides=dict(POP_TINY, **over))


def test_population_sound_and_control():
    r = _pop(POP + ["--control", "1"])
    assert r["correct"], r["checks"]
    c = r["control"]
    lim = r["checks"]
    assert (c["answer_mismatches"] > lim["answer_mismatches"]["limit"]
            or c["energy_rel_gap"] > lim["energy_rel_gap"]["limit"]), c


def test_population_tick_that_changes_nothing(monkeypatch):
    from repro.core import online
    from repro.core.online import TickReport

    def frozen(self, qualities, attaches=None, **kw):
        return [TickReport(tick=0) for _ in range(len(qualities))]
    monkeypatch.setattr(online.ChurnOrchestrator, "run_arrays", frozen)
    assert not _pop()["correct"]


def test_population_half_the_users_left_out(monkeypatch):
    from repro.core import online
    orig = online.ChurnOrchestrator.run_arrays

    def half(self, qualities, attaches=None, **kw):
        q = np.array(qualities, dtype=np.float64)
        q[:, self.n_users // 2:] = self.quality[self.n_users // 2:]
        return orig(self, q, attaches, **kw)
    monkeypatch.setattr(online.ChurnOrchestrator, "run_arrays", half)
    assert not _pop()["correct"]


def test_population_answer_altered_where_produced(monkeypatch):
    from repro.core import online
    orig = online.ChurnOrchestrator._account_resolves

    def altered(self, rep, p, gl_res, loc_res, *a):
        p._inc_energy[loc_res] *= 1.0 + 1e-9
        return orig(self, rep, p, gl_res, loc_res, *a)
    monkeypatch.setattr(online.ChurnOrchestrator, "_account_resolves",
                        altered)
    assert not _pop()["correct"]

