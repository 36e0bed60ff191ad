"""The serving cell on the CPU at a tiny size: a clean run is ``correct``;
the stale-position fault, a float8 KV cache, a logit altered where it is
produced, swapped query and key norms and the float8-weights control are
not; every new reader returns a number from a traced run's context and
None on the population cell."""

import numpy as np
import pytest

from bench import roofline
from bench.common import ROOT, load_json, load_module
from bench.run import run_cell

#: the reduced qwen3 on a 128-position cache, 4 slots, short sessions
SERVE_TINY = {
    "reduced": True, "serving": {"batch": 4, "cache_len": 128},
    "compare": {"judge_seconds": 3},
    "traffic": {"first_wave": {"depth": [16, 48], "budget": [4, 24]},
                "prompt": {"median": 16, "sigma": 0.8, "lo": 4, "hi": 48},
                "output": {"median": 20, "sigma": 0.6, "lo": 8}}}
CELL = "serve-qwen3-4b-reason-b8"
READERS = ("serve_mfu", "decode_hbm_roofline", "ee_gate_roofline",
           "exit_gate_ms_per_step", "admit_ms_per_step",
           "device_idle_share.serve", "decode_ms_per_step")


def _serve(seed=4100000007, trace=0, control=0):
    return run_cell(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "1.5", "--trace", str(trace), "--control",
                     str(control)], require_chip=False, root=ROOT,
                    overrides=SERVE_TINY)


def test_serve_clean_run_is_correct_and_control_is_not():
    r = _serve(control=1)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"tick_ms_p95", "user_ticks_per_s",
                                 "setup_s"}
    lim = r["checks"]
    c = r["control"]
    assert (c["logit_rel_rms"] > lim["logit_rel_rms"]["limit"]
            or c["top1_flips"] > lim["top1_flips"]["limit"]), c


def test_serve_shared_position_fault(monkeypatch):
    """One position shared by the batch (the deepest slot's), as the engine
    had before each slot kept its own."""
    from repro.runtime.serve_engine import SplitServeEngine
    orig = SplitServeEngine._fill_slots

    def shared(self):
        orig(self)
        live = [i for i, r in enumerate(self.slots) if r is not None]
        if live:
            self._slot_pos[:] = self._slot_pos[live].max()
    monkeypatch.setattr(SplitServeEngine, "_fill_slots", shared)
    assert not _serve()["correct"]


def test_serve_float8_kv_cache_fault(monkeypatch):
    import jax.numpy as jnp
    from repro.models import attention
    monkeypatch.setattr(attention.KVCacheSpec, "_kv_dtype",
                        lambda self, dtype: jnp.float8_e4m3fn)
    assert not _serve()["correct"]


def test_serve_logit_altered_where_produced(monkeypatch):
    """The final head wired to the last exit's hidden state."""
    from repro.models import transformer
    orig = transformer._heads

    def altered(params, cfg, hs, h):
        logits, exits = orig(params, cfg, hs, h)
        return exits[f"exit_{cfg.exit_layer_list[-1]}"], exits
    monkeypatch.setattr(transformer, "_heads", altered)
    assert not _serve()["correct"]


def test_serve_swapped_qk_norm_fault(monkeypatch):
    """Each query head normalised with the key norm's scale and each key
    head with the query norm's: the drawn scales differ, so the logits
    move."""
    from repro.models import attention
    orig = attention._project_qkv

    def swapped(params, cfg, x, positions):
        p = dict(params, q_norm=params["k_norm"], k_norm=params["q_norm"])
        return orig(p, cfg, x, positions)
    monkeypatch.setattr(attention, "_project_qkv", swapped)
    assert not _serve()["correct"]


def test_drawn_weights_fill_the_program_layout():
    """``qwen3_ref.weights`` fills the program's pytree, its norm scales
    are not 1, and one seed draws the same weights twice."""
    import jax
    from bench.reference import qwen3_ref
    from repro.configs import get
    from repro.models import transformer as T
    drv = load_module(ROOT / "bench" / "drivers" / "serve.py")
    arch = get("qwen3-4b", reduced=True)
    model = drv.model_block(arch)

    def draw(seed):
        return qwen3_ref.weights(model, arch.exit_layer_list,
                                 arch.padded_vocab, seed, np.float32)
    w = draw(11)
    drv.check_layout(w, jax.eval_shape(lambda k: T.init_model(k, arch),
                                       jax.random.PRNGKey(0)))
    q = np.asarray(w["layers"]["l0"]["mix"]["q_norm"]["scale"])
    k = np.asarray(w["layers"]["l0"]["mix"]["k_norm"]["scale"])
    assert abs(q.mean() - 1) < 0.05 and 0.05 < q.std() < 0.15
    assert not np.allclose(q, k)
    for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(draw(11))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(SystemExit):
        drv.check_layout(dict(w, final_norm={}), jax.eval_shape(
            lambda k: T.init_model(k, arch), jax.random.PRNGKey(0)))


def test_program_seconds_from_the_trace():
    """The programs' device time inside the window, from the ``XLA
    Modules`` line; without it, the ops the gate kernel did not run."""
    drv = load_module(ROOT / "bench" / "drivers" / "serve.py")
    host = {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["bench.window", 1000, 10000]]}]}
    ops = {"name": "XLA Ops", "events": [
        ["%fusion.1 = bf16[8] fusion(%a)", 1500, 3000],
        ["%ee_gate.2 = f32[8] custom-call(%b)", 4500, 500],
        ["%fusion.3 = bf16[8] fusion(%c)", 10500, 1000]]}
    mods = {"name": "XLA Modules", "events": [
        ["jit_decode_step(123)", 500, 4000],
        ["jit_ee_gate(7)", 4500, 500],
        ["jit_prefill_into_slot(9)", 6000, 2000]]}
    dev = {"name": "/device:TPU:0", "lines": [ops, mods]}
    got = drv.program_seconds({"planes": [host, dev]})
    assert got == pytest.approx({"jit_decode_step": 3500e-9,
                                 "jit_prefill_into_slot": 2000e-9})
    dev["lines"] = [ops]
    got = drv.program_seconds({"planes": [host, dev]})
    assert got == pytest.approx({"jit_decode_step": 3500e-9,
                                 "jit_prefill_into_slot": 0.0})
    assert drv.program_seconds({"planes": [host]}) is None


def _ctx_from_traced_run():
    """The driver's layer context of a traced tiny run, with the chip's
    peaks and a device trace in which the gate ran, as a chip run has."""
    from types import SimpleNamespace

    from bench import common
    from bench.run import Tracer
    bench = common.load_benchmark(ROOT)
    cell = common.find_named(bench["workloads"], CELL, "workload")
    config = load_json(ROOT / "bench" / "configs" / "qwen3-4b-ee.json")
    traffic = load_json(ROOT / "bench" / "traffic" / "reason_b8.json")
    traffic.update(SERVE_TINY["traffic"])
    args = SimpleNamespace(seed=5, seconds=1.0, trace=1, control=0)
    import jax
    ctx = SimpleNamespace(
        args=args, cell=cell, config=config, traffic=traffic,
        generator=load_module(ROOT / "bench" / "generators"
                              / "reason_sessions.py"),
        chips=1, devices=jax.devices()[:1],
        tracer=Tracer(False, ROOT / "bench_out"), t0=0.0,
        limits=config["limits"], overrides=dict(SERVE_TINY), root=ROOT)
    driver = load_module(ROOT / "bench" / "drivers" / "serve.py")
    lctx = dict(driver.run(ctx)["layer_ctx"])
    w = lctx["window_s"]
    lctx.update(peaks=roofline.peaks("TPU v5 lite"), roofline=roofline,
                trace={"window_s": w, "busy_s": 0.25 * w, "kernels": {
                    "ee_gate": {"seconds": 0.01 * w, "calls": 3}}},
                programs_s={"jit_decode_step": 0.2 * w,
                            "jit_prefill_into_slot": 0.02 * w})
    return lctx


def test_every_reader_reads_the_serving_cell_only():
    lctx = _ctx_from_traced_run()
    assert lctx["steps"] > 0 and lctx["spans_ms"]["t_gate_ms"] > 0
    assert lctx["spans_ms"]["t_decode_ms"] > 0
    pop = {"kind": "population", "ticks": 3, "peaks": lctx["peaks"],
           "roofline": roofline, "trace": lctx["trace"]}
    for name in READERS:
        read = load_module(ROOT / "bench" / "layer_metrics"
                           / f"{name}.py").read
        v = read(dict(lctx))
        assert isinstance(v, float) and np.isfinite(v) and v >= 0, name
        assert read(dict(pop)) is None, name
    share = load_module(ROOT / "bench" / "layer_metrics"
                        / "device_idle_share.serve.py").read(dict(lctx))
    assert share == pytest.approx(75.0)


def test_roofline_lm_counts_by_hand():
    from bench import roofline_lm
    m = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 4, "intermediate_size": 16,
         "vocab_size": 10}
    P = 8 * 4 * 8 + 16 * 8 + 3 * 8 * 16 + 2 * 8 + 2 * 4
    assert roofline_lm.layer_params(m) == P
    f, b = roofline_lm.decode(m, steps=1, slot_steps=2, depth_sum=5,
                              heads=3)
    assert f == 2 * (2 * 2 * P + 3 * 2 * 8 * 10) + 5 * 2 * 4 * 4 * 4
    assert b == (2 * P + 3 * 10 * 8) * 2 + (5 + 2) * 2 * 2 * 2 * 4 * 2
    f, b = roofline_lm.admission(m, 3, heads=1)
    assert f == 3 * 2 * 2 * P + 2 * 4 * 4 * 4 * 6 + 2 * 8 * 10
    assert b == (2 * P + 10 * 8) * 2 + 3 * 2 * 2 * 2 * 4 * 2
