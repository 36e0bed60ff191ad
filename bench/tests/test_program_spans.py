"""The program's spans on the host plane: self and idle time per span name
from a small recorded trace, by hand count, and the readers on a CPU
rehearsal of the population cell with an outage."""
import json
from pathlib import Path

import pytest

from bench.common import ROOT
from bench.program_spans import host_table
from bench.run import run_cell
from bench.tests.conftest import POP_TINY

DATA = Path(__file__).parent / "data" / "spans_trace.json"


@pytest.fixture(scope="module")
def trace():
    return json.loads(DATA.read_text())


def test_self_and_idle_by_hand(trace):
    # window [1000, 21000); device ops, clipped: [1000,1500) [2000,3000)
    # [6000,8000) [15000,16000).  Channel tick [1600,9400): children
    # ingest 800, gate 3000, group 500, post 2000, account 600 leave 900
    # self, [2500,2600) and [6200,6300) of it busy.  Outage tick
    # [10100,19900): rekey 6000, gate 2000, account 500 leave 1300 self,
    # none busy.  The tick at [21500,22000) lies past the window.
    t = host_table(trace)
    tick = t["orch.tick"]
    assert tick["count"] == 2
    assert tick["total_s"] == pytest.approx(17600e-9)
    assert tick["self_s"] == pytest.approx(2200e-9)
    assert tick["idle_s"] == pytest.approx(2000e-9)
    gate = t["orch.gate"]
    assert gate["count"] == 2
    assert gate["self_s"] == pytest.approx(5000e-9)
    # [2600,5600) is busy over [2600,3000)
    assert gate["idle_s"] == pytest.approx(4600e-9)
    rekey = t["pop.rekey"]
    assert rekey["total_s"] == pytest.approx(6000e-9)
    assert rekey["idle_s"] == pytest.approx(5000e-9)
    assert rekey["args"] == {"users": 600.0}
    # post [6300,8300) less its fast child [6400,7000): 1400 self, of
    # which [6300,6400) and [7000,8000) busy
    post = t["pop.post"]
    assert post["total_s"] == pytest.approx(2000e-9)
    assert post["self_s"] == pytest.approx(1400e-9)
    assert post["idle_s"] == pytest.approx(300e-9)
    assert t["pop.post.fast"]["idle_s"] == pytest.approx(0.0)
    assert t["pop.group"]["idle_s"] == pytest.approx(300e-9)
    assert t["pop.ingest"]["idle_s"] == pytest.approx(300e-9)
    assert t["orch.account"]["total_s"] == pytest.approx(1100e-9)
    # the relax thread's own line: a top-level span there, all busy
    relax = t["pop.relax"]
    assert relax["self_s"] == pytest.approx(1500e-9)
    assert relax["idle_s"] == pytest.approx(0.0)
    assert not any(n.startswith("bench.") for n in t)


def test_no_device_plane_and_no_window(trace):
    host_only = {"planes": [p for p in trace["planes"]
                            if not p["name"].startswith("/device:")]}
    t = host_table(host_only)
    assert t["orch.tick"]["self_s"] == pytest.approx(2200e-9)
    assert t["orch.tick"]["idle_s"] is None
    no_window = {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [
                ev for ev in ln["events"] if ev[0] != "bench.window"]}
            for ln in p["lines"]]} for p in trace["planes"]]}
    assert host_table(no_window) is None


NEW = ("gate_ms_per_tick", "account_ms_per_tick", "orch_self_ms_per_tick",
       "rekey_ms_per_tick", "rekeyed_users_per_tick",
       "solve_group_ms_per_tick")


def test_rehearsal_reads_the_program_spans():
    over = dict(POP_TINY, traffic={"outage": {"period": 6, "down_for": 3}})
    r = run_cell(["--workload", "pop-paper-1m-ar1", "--seed", "3141592653",
                  "--seconds", "2", "--trace", "1"], require_chip=False,
                 root=ROOT, overrides=over)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    for name in NEW:
        assert m.get(name) is not None, name
    for name in ("gate_ms_per_tick", "rekey_ms_per_tick",
                 "rekeyed_users_per_tick", "solve_group_ms_per_tick",
                 "account_ms_per_tick"):
        assert m[name]["value"] > 0, (name, m[name])
    # each outage event re-keys every user at least once
    assert m["rekeyed_users_per_tick"]["value"] >= POP_TINY["users"] / 6
    for name in ("ingest_ms_per_tick", "postpass_ms_per_tick",
                 "relax_ms_per_tick", "state_hit_share",
                 "relaxed_states_per_tick"):
        assert name in m, name
