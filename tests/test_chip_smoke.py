"""``chip_smoke.py``'s phases on the CPU at tiny sizes, so they keep working
between chip runs (the script's ``main`` itself refuses to run off a TPU).
"""
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from repro.configs import get

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_population_phase_device_backends_match_reference(smoke):
    out = smoke.population_phase(
        1000, {"minplus": {}, "pallas": {"backend": "pallas"},
               "mesh": {"backend": "mesh"}}, ref="minplus", ticks=3)
    assert set(out["tick_s"]) == {"minplus", "pallas", "mesh"}
    assert all(len(ts) == 3 for ts in out["tick_s"].values())
    assert all(len(c) == 3 and min(c) >= 0
               for c in out["compiles"].values())
    for orch in out["orchestrators"].values():
        assert sum(p.U for p in orch.pops) == 1000


def test_population_phase_mesh_variants_on_one_device(smoke):
    from repro.sharding.population import population_mesh
    out = smoke.population_phase(
        600, {"a": {"backend": "mesh", "mesh": population_mesh(1)},
              "b": {"backend": "mesh"}}, ref="a", ticks=2)
    rx = out["orchestrators"]["b"].pops[0]._mesh_relaxer
    assert rx.demotions == 0 and rx.retries == 0
    assert len(rx.last_shard_devices) == rx.n_devices


def test_population_phase_rejects_differing_decisions(smoke, monkeypatch):
    """The lockstep check must catch a variant whose decisions diverge."""
    real = smoke._tick_decisions

    def skewed(orch, rep):
        d = real(orch, rep)
        if orch.pops[0].backend == "mesh":
            d["exit"] = [e + 1 for e in d["exit"]]
        return d

    monkeypatch.setattr(smoke, "_tick_decisions", skewed)
    with pytest.raises(AssertionError, match="exits differ"):
        smoke.population_phase(300, {"ref": {}, "m": {"backend": "mesh"}},
                               ref="ref", ticks=1)


def test_compile_counter_counts_backend_compiles(smoke):
    import jax
    f = jax.jit(lambda x: x * 3 + 1)
    with smoke.CompileCounter() as cc:
        f(np.zeros((3, 7), np.float32))
        f(np.ones((3, 7), np.float32))     # same shape: no new compile
        f(np.zeros((5, 7), np.float32))
    assert cc.n == 2
    f(np.zeros((9, 7), np.float32))        # unregistered: not counted
    assert cc.n == 2


def test_serving_phase_reduced_model(smoke):
    cfg = get("qwen3-4b", reduced=True)
    out = smoke.serving_phase(cfg, batch=4, cache_len=64, n_requests=6,
                              prompt_len=4, new_tokens=4)
    assert out["tokens"] > 0 and out["tokens_per_s"] > 0
    assert set(out["gate_conf_max_rel_err"]) == {
        *(f"exit_{p}" for p in cfg.exit_layer_list), "final"}
    assert max(out["gate_conf_max_rel_err"].values()) \
        <= smoke.GATE_CONF_RTOL


def test_ingest_phase_bytes_match(smoke):
    assert smoke.ingest_phase(128) > 0


def test_ar1_qualities_seeded_and_clipped(smoke):
    a = smoke.ar1_qualities(50, 4, seed=7)
    assert a.shape == (4, 50)
    assert np.array_equal(a, smoke.ar1_qualities(50, 4, seed=7))
    assert a.min() >= 0.3 and a.max() <= 1.0


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert "needs a TPU" in out.err
    for line in out.out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
