"""Chip smoke test: the population tick and the split-serving engine on a TPU.

Run from the checkout root, on a machine that holds a TPU:

    python chip_smoke.py             # one chip: population, serving, ingest
    python chip_smoke.py --chips 4   # only the users mesh over four chips

One chip (the default) runs three phases:

* population — 1,000,000 users (``population_cohorts(n_extra_edge=2)``)
  over five seeded AR(1) channel ticks through
  ``ChurnOrchestrator.run_arrays``, once with ``backend="pallas"`` (the
  compiled banded chain kernel) and once with ``backend="mesh"`` (the
  sharded jnp scan on a one-device mesh), in lockstep with the float64
  ``minplus`` reference on the same draws.  Every tick's decisions
  (``n_resolved``/``n_held``/``n_failed``/``n_migrations``, each user's
  incumbent placement and exit) must be identical to the reference, and
  energies must agree within ``tolerances.DIST_RTOL_F32``.
* serving — qwen3-4b at its published widths (random bf16 weights from a
  seed) through ``SplitServeEngine`` under a FIN placement of its own
  profile (``paper_scenario()``, ``profile_from_arch``): batch 8, cache
  512, 8 requests of 16-token prompts, each admitted by one prefill, 16
  new tokens each.  Every request must
  return 16 tokens, and on one more step the compiled ``ee_gate`` on the
  real exit logits must match ``ee_gate_ref`` exactly in argmax and within
  ``GATE_CONF_RTOL`` in confidence.
* ingest — ``quant_signature_jnp`` against the numpy oracle, byte for byte.

``--chips 4`` runs only the mesh population phase at 1,000,000 users on a
four-device ``population_mesh()`` against the same phase on one device:
identical decisions, four relaxer devices, input shards on four distinct
TPU devices.

Every phase fails its run on any mesh retry or demotion.  The phases are
importable functions (``tests/test_chip_smoke.py`` runs them on the CPU at
tiny sizes); ``main`` refuses to run without a TPU.  Measurements go on
earlier lines; the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Mapping, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get  # noqa: E402
from repro.core import (AppRequirements, ChurnOrchestrator,  # noqa: E402
                        Population, paper_profile, population_cohorts,
                        profile_from_arch)
from repro.core.multiapp import PAPER_MULTIAPP_REQS  # noqa: E402
from repro.core.scenarios import paper_scenario  # noqa: E402
from repro.core.tolerances import DIST_RTOL_F32  # noqa: E402
from repro.kernels.ee_gate.ops import ee_gate  # noqa: E402
from repro.kernels.ee_gate.population import (quant_signature_jnp,  # noqa: E402
                                              quant_signature_np)
from repro.kernels.ee_gate.ref import ee_gate_ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.runtime.serve_engine import SplitServeEngine  # noqa: E402
from repro.sharding.population import population_mesh  # noqa: E402

#: the kernel tests' confidence tolerance: the gate's blockwise online
#: softmax sums exp terms in another order than the reference's one pass
GATE_CONF_RTOL = 2e-3

POPULATION_USERS = 1_000_000


def ar1_qualities(users: int, ticks: int, *, seed: int = 0,
                  q_mean: float = 0.65, rho: float = 0.95,
                  sigma: float = 0.05) -> np.ndarray:
    """(ticks, users) seeded AR(1) channel qualities, clipped to
    [0.3, 1.0] — the ``scenarios.churn_trace`` fading model in array form."""
    rng = np.random.default_rng(seed)
    q = np.full(users, q_mean)
    out = np.empty((ticks, users))
    for t in range(ticks):
        q = np.clip(q_mean + rho * (q - q_mean)
                    + rng.normal(0.0, sigma, users), 0.3, 1.0)
        out[t] = q
    return out


def _tick_decisions(orch: ChurnOrchestrator, rep) -> Dict[str, object]:
    incs = [p.incumbents() for p in orch.pops]
    return {
        "counts": (rep.n_resolved, rep.n_held, rep.n_failed,
                   rep.n_migrations),
        "place": [i[0] for i in incs],
        "exit": [i[1] for i in incs],
        "energy": np.concatenate([i[2] for i in incs]),
        "tick_energy": rep.energy,
    }


def _assert_same_decisions(name: str, ref_name: str, t: int,
                           got: Dict[str, object],
                           want: Dict[str, object]) -> None:
    ctx = f"{name} vs {ref_name}, tick {t}"
    assert got["counts"] == want["counts"], (ctx, got["counts"],
                                             want["counts"])
    for a, b in zip(got["place"], want["place"]):
        assert np.array_equal(a, b), f"{ctx}: incumbent placements differ"
    for a, b in zip(got["exit"], want["exit"]):
        assert np.array_equal(a, b), f"{ctx}: incumbent exits differ"
    ea, eb = got["energy"], want["energy"]
    assert np.array_equal(np.isfinite(ea), np.isfinite(eb)), ctx
    fin = np.isfinite(eb)
    np.testing.assert_allclose(ea[fin], eb[fin], rtol=DIST_RTOL_F32,
                               err_msg=ctx)
    np.testing.assert_allclose(got["tick_energy"], want["tick_energy"],
                               rtol=DIST_RTOL_F32, err_msg=ctx)


class CompileCounter:
    """Counts XLA backend compiles (JAX's compile-duration events) while
    registered; ``n`` is the running total.  A persistent-cache hit
    records no such event."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def __call__(self, event: str, duration_secs: float, **kw) -> None:
        if event == self.EVENT:
            self.n += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self)


def population_phase(n_users: int, variants: Mapping[str, Mapping],
                     *, ref: str, ticks: int = 5, seed: int = 0
                     ) -> Dict[str, object]:
    """Run every variant's orchestrator in lockstep over one AR(1) trace.

    ``variants`` maps a name to ``population_cohorts`` keyword arguments
    (backend, mesh, ...); ``ref`` names the one every other variant must
    match tick by tick.  Returns ``{"tick_s": {name: [seconds per tick]},
    "compiles": {name: [XLA compiles per tick]}, "orchestrators": {name:
    ChurnOrchestrator}}``; tick 0 includes compilation."""
    draws = ar1_qualities(n_users, ticks, seed=seed)
    orchs = {name: ChurnOrchestrator(
                 population=population_cohorts(n_users, n_extra_edge=2,
                                               **kw),
                 hysteresis=0.05)
             for name, kw in variants.items()}
    tick_s: Dict[str, List[float]] = {name: [] for name in variants}
    compiles: Dict[str, List[int]] = {name: [] for name in variants}
    for t in range(ticks):
        decisions = {}
        for name, orch in orchs.items():
            with CompileCounter() as cc:
                t0 = time.perf_counter()
                (rep,) = orch.run_arrays(draws[t:t + 1])
                tick_s[name].append(time.perf_counter() - t0)
            compiles[name].append(cc.n)
            assert rep.n_mesh_retries == 0 and rep.n_mesh_demotions == 0, (
                f"{name} tick {t}: {rep.n_mesh_retries} mesh retries, "
                f"{rep.n_mesh_demotions} demotions")
            decisions[name] = _tick_decisions(orch, rep)
        for name in variants:
            if name != ref:
                _assert_same_decisions(name, ref, t, decisions[name],
                                       decisions[ref])
    for name, kw in variants.items():
        if kw.get("backend", "minplus") in ("pallas", "mesh"):
            launches = sum(p.stats.fused_relaxes + p.stats.chunked_relaxes
                           for p in orchs[name].pops)
            assert launches > 0, f"{name}: no relaxation reached the device"
    return {"tick_s": tick_s, "compiles": compiles, "orchestrators": orchs}


def serving_phase(cfg, *, batch: int = 8, cache_len: int = 512,
                  n_requests: int = 8, prompt_len: int = 16,
                  new_tokens: int = 16, seed: int = 0) -> Dict[str, object]:
    """Serve ``n_requests`` random prompts through ``SplitServeEngine``
    under the paper's FIN placement, then check the compiled gate against
    its oracle on one more step's real exit logits.  Returns throughput,
    the decode step's compiled memory analysis and the gate errors; step 0
    (compilation) is excluded from the timed window."""
    params = jax.jit(T.init_model, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    eng = SplitServeEngine(cfg, params, batch_size=batch,
                           cache_len=cache_len, network=paper_scenario(),
                           profile=profile_from_arch(cfg),
                           req=AppRequirements(alpha=1.0, delta=0.05))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(1, cfg.vocab_size, (n_requests, prompt_len))
    reqs = [eng.submit(p.tolist(), new_tokens) for p in prompts]
    t0 = time.perf_counter()
    eng.step()                      # compiles the step and the gates
    first_s = time.perf_counter() - t0
    steps0, tokens0 = eng.stats.steps, eng.stats.tokens_out
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    for r in reqs:
        assert r.done and len(r.tokens) == new_tokens, (
            f"request {r.rid}: {len(r.tokens)} of {new_tokens} tokens")

    toks = jnp.asarray(np.resize(prompts[:, 0], (batch, 1)), jnp.int32)
    pos = jnp.asarray(eng._slot_pos)
    compiled = eng.decode_step.lower(eng.params, eng.caches, toks,
                                     pos).compile()
    logits, eng.caches, exits = eng.decode_step(
        eng.params, eng.caches, toks, pos)
    gate_err = {}
    for name, x in {**exits, "final": logits}.items():
        conf, arg = ee_gate(x)
        conf_r, arg_r = ee_gate_ref(x)
        np.testing.assert_array_equal(np.asarray(arg), np.asarray(arg_r),
                                      err_msg=f"gate argmax at {name}")
        np.testing.assert_allclose(np.asarray(conf), np.asarray(conf_r),
                                   rtol=GATE_CONF_RTOL,
                                   err_msg=f"gate confidence at {name}")
        gate_err[name] = float(np.max(np.abs(np.asarray(conf)
                                             / np.asarray(conf_r) - 1.0)))
    stats = jax.devices()[0].memory_stats() or {}
    return {
        "tokens": eng.stats.tokens_out - tokens0,
        "steps": eng.stats.steps - steps0,
        "seconds": dt,
        "first_step_s": first_s,
        "tokens_per_s": (eng.stats.tokens_out - tokens0) / dt,
        "memory_analysis": compiled.memory_analysis(),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "gate_conf_max_rel_err": gate_err,
    }


def ingest_phase(n_users: int = 4096, *, seed: int = 3) -> int:
    """``quant_signature_jnp`` on the default device against the numpy
    oracle, byte for byte, over draws with dead, negative and huge links.
    Returns the number of signature bytes compared."""
    nw = paper_scenario(n_extra_edge=2)
    rng = np.random.default_rng(seed)
    n = 0
    for app in ("h1", "h4", "h6"):
        pop = Population(nw, paper_profile(app), PAPER_MULTIAPP_REQS[app],
                         n_users)
        c = pop._quant()
        vec = rng.uniform(0.1, 2.0, (pop.U, pop.N)) * 1e9
        vec[rng.random(vec.shape) < 0.08] = 0.0
        vec[rng.random(vec.shape) < 0.04] = -1.0
        vec[rng.random(vec.shape) < 0.04] = 1e30
        want = quant_signature_np(vec, c).tobytes()
        got = quant_signature_jnp(vec, c).tobytes()
        assert got == want, f"{app}: quant_signature_jnp bytes differ"
        n += len(want)
    return n


def _describe_memory(ma) -> str:
    if ma is None:
        return "n/a"
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")
    return " ".join(f"{f.replace('_size_in_bytes', '')}="
                    f"{getattr(ma, f) / 2**30:.3f}GiB" for f in fields
                    if hasattr(ma, f))


def _report_population(out, n_users: int, kind: str) -> None:
    for name, ts in out["tick_s"].items():
        steady = ts[1:]
        print(f"population {name}: users={n_users} "
              f"warmup_tick_s={ts[0]:.6f} "
              f"steady_tick_s={[round(x, 6) for x in steady]} "
              f"median_steady_tick_s={float(np.median(steady)):.6f} "
              f"xla_compiles_per_tick={out['compiles'][name]} "
              f"(host wall clock around run_arrays, one {kind})",
              flush=True)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the users-mesh phase over four chips")
    args = ap.parse_args(argv)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devs[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache()}", flush=True)
    kind = devs[0].device_kind

    if args.chips == 4:
        out = population_phase(
            POPULATION_USERS,
            {"mesh_1dev": {"backend": "mesh", "mesh": population_mesh(1)},
             "mesh_4dev": {"backend": "mesh", "mesh": population_mesh(4)}},
            ref="mesh_1dev")
        relaxers = [p._mesh_relaxer
                    for p in out["orchestrators"]["mesh_4dev"].pops]
        for rx in relaxers:
            assert rx.n_devices == 4 and rx.demotions == 0, rx.n_devices
        shard_devs = {d for rx in relaxers for d in rx.last_shard_devices}
        assert len(shard_devs) == 4, shard_devs
        assert all(d.platform == "tpu" for d in shard_devs), shard_devs
        _report_population(out, POPULATION_USERS, f"{kind} host, 4 chips")
        print(f"mesh_4dev: decisions identical to mesh_1dev on every tick; "
              f"relaxer n_devices=4 in {len(relaxers)} cohorts; input "
              f"shards on {len(shard_devs)} distinct devices "
              f"{sorted(d.id for d in shard_devs)}; 0 retries, "
              f"0 demotions", flush=True)
    else:
        out = population_phase(
            POPULATION_USERS,
            {"minplus": {}, "pallas": {"backend": "pallas"},
             "mesh": {"backend": "mesh"}},
            ref="minplus")
        _report_population(out, POPULATION_USERS, kind)
        print("population: pallas and mesh decisions identical to the "
              "float64 minplus reference on every tick", flush=True)
        del out

        serve = serving_phase(get("qwen3-4b"))
        print(f"serving qwen3-4b: {serve['tokens']} tokens in "
              f"{serve['steps']} steps, {serve['seconds']:.6f}s -> "
              f"tokens_per_s={serve['tokens_per_s']:.6f} (host wall clock, "
              f"one {kind}; first step incl. compile "
              f"{serve['first_step_s']:.6f}s)", flush=True)
        print(f"serving decode step memory_analysis: "
              f"{_describe_memory(serve['memory_analysis'])}; "
              f"peak_bytes_in_use={serve['peak_bytes_in_use']}", flush=True)
        print(f"serving ee_gate vs ee_gate_ref: argmax identical, "
              f"max conf rel err {serve['gate_conf_max_rel_err']}",
              flush=True)
        n = ingest_phase()
        print(f"ingest: quant_signature_jnp identical to the numpy oracle "
              f"over {n} signature bytes", flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
