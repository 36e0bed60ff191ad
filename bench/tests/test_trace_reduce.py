"""trace_reduce on a small trace: busy union, kernel sums,
idle gaps named by the host span, and the roofline readers on top."""
import json
from pathlib import Path

import pytest

from bench import roofline
from bench.trace_reduce import op_short_name, reduce

DATA = Path(__file__).parent / "data" / "small_trace.json"


@pytest.fixture(scope="module")
def trace():
    return json.loads(DATA.read_text())


def test_short_names():
    assert op_short_name("%fusion.12 = f32[8]{0} fusion(f32[8] %a)") \
        == "fusion"
    assert op_short_name("%ee_gate_pallas.1 = (f32[8,1]) custom-call()") \
        == "ee_gate_pallas"


def test_busy_kernels_and_gaps(trace):
    r = reduce(trace, kernels=("banded_minplus_chain", "ee_gate"))
    # window 1000..11000 ns; device ops (clipped to the window) cover
    # [1000,2000) [2500,4000) [3500,4500) [8000,9000) [10500,11000):
    # union 1000 + 2000 + 1000 + 500 = 4500 ns
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx(4500e-9)
    k = r["kernels"]["banded_minplus_chain"]
    assert k["calls"] == 2
    assert k["seconds"] == pytest.approx(1800e-9)
    assert sum(k["ops"].values()) == 2
    assert r["kernels"]["ee_gate"]["calls"] == 1
    # gaps: [2000,2500) 500, [4500,8000) 3500, [9000,10500) 1500
    gaps = r["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([3500e-9, 1500e-9,
                                                  500e-9])
    assert gaps[0][0] == "bench.tick.outage"
    assert gaps[1][0] == "bench.gen"
    ops = dict(r["device_ops"])
    assert ops["banded_minplus_chain_pallas"] == pytest.approx(1800e-9)


def test_no_device_plane_reads_nothing(trace):
    host_only = {"planes": [p for p in trace["planes"]
                            if not p["name"].startswith("/device:")]}
    assert reduce(host_only) is None


def test_roofline_of_the_recorded_kernel(trace):
    r = reduce(trace, kernels=("banded_minplus_chain",))
    from bench.common import BENCH, load_module
    reader = load_module(BENCH / "layer_metrics"
                         / "minplus_chain_roofline.py")
    cfg = {"network": {"layout": [["mobile", 1], ["edge", 3],
                                  ["cloud", 1]]},
           "solver": {"gamma": 10, "quantize": "floor"},
           "apps": {"h1": {"model": "m5"}, "h2": {"model": "m3"}},
           "models": {"m5": {"blocks": [[1, 1]] * 5},
                      "m3": {"blocks": [[1, 1]] * 3}}}
    ctx = {"kind": "population", "trace": r, "config": cfg,
           "roofline": roofline, "peaks": roofline.peaks("TPU v5 lite"),
           "relaxed_by_app": {"h1": 8, "h2": 0}}
    v = reader.read(ctx)
    # 8 states of h1, two modes each: 16 chains of 4 layers at N=5,
    # G+1=11 (hand count in test_roofline); memory bound
    f, b = roofline.minplus_chain(16, 4, 5, 11)
    want = 100.0 * b / 819e9 / 1800e-9
    assert v == pytest.approx(want)
    ctx["relaxed_by_app"] = {"h1": 0, "h2": 0}
    assert reader.read(ctx) is None


def test_enclosing_op_counts_only_its_own_time():
    # a while loop [0, 1000) encloses two body ops [100, 400) and
    # [500, 900); a copy [1200, 1300) stands alone
    ops = [["%while.1 = (f32[8]) while(f32[8] %a)", 0, 1000],
           ["%fusion.2 = f32[8] fusion(f32[8] %b)", 100, 300],
           ["%fusion.3 = f32[8] fusion(f32[8] %c)", 500, 400],
           ["%copy.4 = f32[8] copy(f32[8] %d)", 1200, 100]]
    trace = {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.window", 0, 2000], ["bench.step", 0, 1500]]}]}]}
    r = reduce(trace)
    assert r["busy_s"] == pytest.approx(1100e-9)
    got = dict(r["device_ops"])
    assert got["while"] == pytest.approx(300e-9)
    assert got["fusion"] == pytest.approx(700e-9)
    assert got["copy"] == pytest.approx(100e-9)
