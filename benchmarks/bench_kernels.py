"""Pallas kernel benches: correctness + timing vs the jnp oracles.

The kernels compile on TPU and are interpreted on CPU
(``kernels/platform.py``); on CPU the numbers measure the *interpreted*
kernel (a Python loop over grid steps), so wall time there is diagnostic
only and the `rel_err` and tiling metadata are the deliverable.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attn.ops import decode_attn
from repro.kernels.decode_attn.ref import decode_attn_ref
from repro.kernels.ee_gate.ops import ee_gate
from repro.kernels.ee_gate.ref import ee_gate_ref
from repro.kernels.minplus.ops import (banded_minplus_argmin, minplus_matmat,
                                       minplus_vecmat, minplus_vecmat_argmin)
from repro.kernels.minplus.ref import (banded_minplus_ref, minplus_argmin_ref,
                                       minplus_ref)

from .common import Row, batched_solver_row, kv, timed


def run() -> List[Row]:
    rows: List[Row] = []
    rng = np.random.default_rng(0)

    # minplus: FIN relaxation at multi-app scale (S = N*gamma states)
    for B, S in ((8, 512), (64, 1024)):
        dist = jnp.asarray(rng.uniform(0, 10, (B, S)), jnp.float32)
        W = rng.uniform(0, 5, (S, S)).astype(np.float32)
        W[rng.uniform(size=W.shape) < 0.5] = np.inf
        W = jnp.asarray(W)
        got, us_k = timed(lambda: jax.block_until_ready(
            minplus_vecmat(dist, W)), repeats=2)
        want, us_r = timed(lambda: jax.block_until_ready(
            minplus_ref(dist, W)), repeats=2)
        m = np.isfinite(np.asarray(want))
        err = float(np.abs(np.asarray(got)[m] - np.asarray(want)[m]).max())
        rows.append(Row(f"kernels/minplus/B{B}xS{S}", us_k,
                        kv(ref_us=us_r, max_abs_err=err,
                           block="8x128x128")))

    # minplus argmin variant (parent recovery) and tropical matmat
    B, S = 8, 512
    dist = jnp.asarray(rng.uniform(0, 10, (B, S)), jnp.float32)
    W = rng.uniform(0, 5, (S, S)).astype(np.float32)
    W[rng.uniform(size=W.shape) < 0.5] = np.inf
    W = jnp.asarray(W)
    (got, arg), us_k = timed(lambda: jax.block_until_ready(
        minplus_vecmat_argmin(dist, W)), repeats=2)
    (want, arg_r), us_r = timed(lambda: jax.block_until_ready(
        minplus_argmin_ref(dist, W)), repeats=2)
    agree = float((np.asarray(arg) == np.asarray(arg_r)).mean())
    rows.append(Row(f"kernels/minplus-argmin/B{B}xS{S}", us_k,
                    kv(ref_us=us_r, argmin_agree=agree, block="8x128x128")))
    got_mm, us_mm = timed(lambda: jax.block_until_ready(
        minplus_matmat(dist, W)), repeats=2)
    rows.append(Row(f"kernels/minplus-matmat/B{B}xS{S}", us_mm,
                    kv(max_abs_err=float(np.abs(
                        np.asarray(got_mm)[np.isfinite(np.asarray(want))]
                        - np.asarray(want)[np.isfinite(np.asarray(want))]
                    ).max()))))

    # banded minplus: one FIN relaxation layer over the compact (node, depth)
    # grid — the O(N^2 G) variant the banded solver backends run on TPU
    for N, G in ((32, 24), (64, 48)):
        bdist = rng.uniform(0, 10, (N, G + 1)).astype(np.float32)
        bdist[rng.uniform(size=bdist.shape) < 0.4] = np.inf
        bE = rng.uniform(0, 5, (N, N)).astype(np.float32)
        bE[rng.uniform(size=bE.shape) < 0.3] = np.inf
        bst = rng.integers(0, G + 1, (N, N)).astype(np.int32)
        args = (jnp.asarray(bdist), jnp.asarray(bE), jnp.asarray(bst))
        (gb, ab), us_k = timed(lambda: jax.block_until_ready(
            banded_minplus_argmin(*args)), repeats=2)
        (wb, wab), us_r = timed(lambda: jax.block_until_ready(
            banded_minplus_ref(*args)), repeats=2)
        m = np.isfinite(np.asarray(wb))
        err = float(np.abs(np.asarray(gb)[m] - np.asarray(wb)[m]).max()) \
            if m.any() else 0.0
        agree = float((np.asarray(ab) == np.asarray(wab)).mean())
        rows.append(Row(f"kernels/minplus-banded/N{N}xG{G}", us_k,
                        kv(ref_us=us_r, max_abs_err=err, argmin_agree=agree,
                           dense_S=N * (G + 1))))

    rows.extend(_batched_solver_rows())

    # ee_gate: decode-batch gating at large vocab
    for B, V in ((64, 50304), (128, 151936)):
        logits = jnp.asarray(rng.normal(0, 4, (B, V)), jnp.float32)
        (conf, arg), us_k = timed(lambda: jax.block_until_ready(
            ee_gate(logits)), repeats=2)
        (cr, ar), us_r = timed(lambda: jax.block_until_ready(
            ee_gate_ref(logits)), repeats=2)
        err = float(np.abs(np.asarray(conf) - np.asarray(cr)).max())
        agree = float((np.asarray(arg) == np.asarray(ar)).mean())
        rows.append(Row(f"kernels/ee_gate/B{B}xV{V}", us_k,
                        kv(ref_us=us_r, conf_err=err, argmax_agree=agree,
                           block="8x2048")))

    # decode_attn: flash-decode over a 32k cache (GQA 6:1)
    for B, H, KVh, D, T in ((4, 32, 8, 128, 4096),):
        q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(B, T, KVh, D)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(B, T, KVh, D)), jnp.bfloat16)
        cpos = jnp.arange(T, dtype=jnp.int32)
        pos = jnp.int32(T - 1)
        got, us_k = timed(lambda: jax.block_until_ready(
            decode_attn(q, k, v, cpos, pos)), repeats=2)
        want, us_r = timed(lambda: jax.block_until_ready(
            decode_attn_ref(q, k, v, cpos, pos)), repeats=2)
        err = float(np.abs(np.asarray(got, np.float32)
                           - np.asarray(want, np.float32)).max())
        rows.append(Row(f"kernels/decode_attn/B{B}H{H}T{T}", us_k,
                        kv(ref_us=us_r, max_abs_err=err, block_t=512)))
    return rows


def _batched_solver_rows() -> List[Row]:
    """Batched-solver mode: solver wall-clock of one solve_many relaxation
    vs the equivalent loop of legacy ``backend="python"`` solves."""
    from repro.core.scenarios import sweep_scenarios

    ps, ns, rs = sweep_scenarios(apps=("h2", "h6"),
                                 deltas_ms=(1.0, 2.0, 4.0, 8.0, 12.0),
                                 n_extra_edge=6)
    return [batched_solver_row("kernels/solver-batched", ps, ns, rs,
                               repeats=2)]


if __name__ == "__main__":
    for r in run():
        print(r.csv())
