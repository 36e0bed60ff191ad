"""``gate_threshold_share``: the share of the gate's rows judged by
threshold, from the ``users`` arguments of ``pop.gate.threshold`` and
``pop.gate.exact`` in a small trace by hand count, and on a CPU rehearsal
of the population cell."""
import importlib.util

import pytest

from bench.common import ROOT
from bench.program_spans import host_table
from bench.run import run_cell
from bench.tests.conftest import POP_TINY

READER = ROOT / "bench" / "layer_metrics" / "gate_threshold_share.py"


def _reader():
    spec = importlib.util.spec_from_file_location("gate_threshold_share",
                                                  READER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _trace(gate_spans):
    """A window with two ticks, each with one ``orch.gate`` holding the
    given ``(name, users)`` spans in turn."""
    evs = [["bench.window", 0.0, 10000.0]]
    for t0 in (1000.0, 6000.0):
        evs.append(["orch.tick", t0, 3000.0])
        evs.append(["orch.gate", t0 + 100, 2000.0])
        for i, (name, users) in enumerate(gate_spans):
            evs.append([name, t0 + 200 + 300 * i, 200.0,
                        {"users": float(users)}])
    return {"planes": [{"name": "/host:CPU",
                        "lines": [{"name": "python", "events": evs}]}]}


def _ctx(trace):
    return {"kind": "population", "ticks": 2,
            "program_spans": host_table(trace)}


@pytest.mark.parametrize("spans,share", [
    # per tick: 900 + 600 rows by threshold, 500 re-evaluated
    ([("pop.gate.threshold", 900), ("pop.gate.exact", 500),
      ("pop.gate.threshold", 600)], 75.0),
    # no exact span entered
    ([("pop.gate.threshold", 1000), ("pop.gate.threshold", 5)], 100.0),
    # only the exact path (the gate's outage ticks)
    ([("pop.gate.exact", 40)], 0.0),
])
def test_share_by_hand(spans, share):
    assert _reader()(_ctx(_trace(spans))) == pytest.approx(share)


def test_nothing_to_read():
    read = _reader()
    # a program without the spans (the gate before it had them)
    assert read(_ctx(_trace([]))) is None
    # no orch.tick: a program without any program spans
    no_tick = _trace([("pop.gate.threshold", 9)])
    for line in no_tick["planes"][0]["lines"]:
        line["events"] = [e for e in line["events"] if e[0] != "orch.tick"]
    assert read(_ctx(no_tick)) is None
    assert read({"kind": "serving", "ticks": 3}) is None


def test_rehearsal_reads_the_share():
    over = dict(POP_TINY, traffic={"outage": {"period": 6, "down_for": 3}})
    r = run_cell(["--workload", "pop-paper-1m-ar1", "--seed", "2718281828",
                  "--seconds", "2", "--trace", "1"], require_chip=False,
                 root=ROOT, overrides=over)
    assert r["correct"], r["checks"]
    share = r["metrics"]["gate_threshold_share"]["value"]
    # channel ticks judge by threshold, the outage ticks' gate re-evaluates
    assert 50.0 < share < 100.0
