"""Serving-engine bench: exit-aware continuous batching under a FIN placement.

Quantifies the paper's mechanism end-to-end (reduced granite config, fused
ee_gate kernel): placement-model energy per token with exits off vs on, the
measured phi, and the continuous-batching step saving vs sequential serving.
This is the orchestration-level half of §Perf cell 3 (EXPERIMENTS.md).
"""
from __future__ import annotations

from typing import List

import jax
import numpy as np

from repro.configs import get
from repro.core import AppRequirements, profile_from_arch
from repro.core.scenarios import paper_scenario
from repro.kernels.ee_gate.ops import ee_gate
from repro.models import transformer as T
from repro.runtime.serve_engine import SplitServeEngine

from .common import Row, kv, timed


def _engine(cfg, params, thresholds):
    # the model's own profile; alpha 1.0 keeps its final head deployed
    return SplitServeEngine(
        cfg, params, batch_size=4, cache_len=128, thresholds=thresholds,
        network=paper_scenario(), profile=profile_from_arch(cfg),
        req=AppRequirements(alpha=1.0, delta=8e-3))


def run() -> List[Row]:
    rows: List[Row] = []
    cfg = get("granite-34b", reduced=True)
    params = T.init_model(jax.random.PRNGKey(0), cfg)

    # calibrate the gate threshold at the observed exit-0 confidence median
    import jax.numpy as jnp
    caches = T.init_caches(cfg, 4, 128)
    _, _, exits = T.decode_step(params, cfg, jnp.ones((4, 1), jnp.int32),
                                caches, jnp.zeros(4, jnp.int32))
    conf0, _ = ee_gate(exits[f"exit_{cfg.exit_layer_list[0]}"])
    thr = float(np.median(np.asarray(conf0)))

    stats = {}
    n_early = len(cfg.exit_layer_list)
    for name, th in (("exits_off", 1.1), ("exits_on", thr)):
        eng = _engine(cfg, params, [th] * n_early)
        for i in range(16):
            eng.submit([1 + i % 7, 2, 3], max_new_tokens=6)
        st, us = timed(lambda e=eng: e.run(max_steps=400), repeats=1)
        stats[name] = st
        rows.append(Row(
            f"engine/{name}", us / max(1, st.steps),
            kv(tokens=st.tokens_out, steps=st.steps,
               energy_per_token_mJ=st.energy_j / max(1, st.tokens_out) * 1e3,
               blocks_executed=st.blocks_executed,
               blocks_saved=st.blocks_saved,
               phi="/".join(f"{v:.2f}" for _, v in
                            sorted(st.measured_phi.items())))))
    off = stats["exits_off"]
    on = stats["exits_on"]
    ratio = ((on.energy_j / max(1, on.tokens_out))
             / (off.energy_j / max(1, off.tokens_out)))
    seq_steps = 16 * 5    # sequential: 5 steps a request (the first admits)
    rows.append(Row(
        "engine/summary", 0.0,
        kv(energy_ratio_exits_on_over_off=ratio,
           continuous_batching_step_saving=1 - on.steps / seq_steps,
           gate_threshold=thr)))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r.csv())
