"""Jitted wrappers for the minplus Pallas kernels.

The kernels compile on TPU and run through the Pallas interpreter on CPU;
``kernels/platform.py`` decides from the default backend."""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from .minplus import (banded_minplus_chain_kbest_pallas,
                      banded_minplus_chain_pallas, banded_minplus_pallas,
                      minplus_argmin_pallas, minplus_pallas)


def minplus_vecmat(dist: jnp.ndarray, W: jnp.ndarray) -> jnp.ndarray:
    """dist: [B, S] float; W: [S, T] float (inf = no edge) -> [B, T]."""
    return minplus_pallas(dist, W)


def minplus_matmat(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """Tropical matmul: out[i, j] = min_k A[i, k] + B[k, j].

    The kernel is the same VMEM-tiled reduction as ``minplus_vecmat`` — a
    row-batch of relaxation fronts IS a (min,+) matrix product — exposed
    under the algebraic name for batched scenario sweeps (the rows of A are
    the per-scenario distance fronts sharing one transition matrix B)."""
    return minplus_pallas(A, B)


def minplus_vecmat_argmin(dist: jnp.ndarray, W: jnp.ndarray
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """dist: [B, S]; W: [S, T] -> (out [B, T], argmin_s [B, T] int32, -1
    where t is unreachable).  Parent-recovery variant for the FIN DP."""
    return minplus_argmin_pallas(dist, W)


def banded_minplus_argmin(dist: jnp.ndarray, E: jnp.ndarray, st: jnp.ndarray,
                          *, lo=None
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depth-banded relaxation layer over the compact (node, depth) grid.

    dist: [N, G+1]; E: [N, N] (inf = pruned); st: [N, N] int steepness ->
    (out [N, G+1], argmin source node [N, G+1] int32, -1 unreachable).
    O(N^2 G) work/memory vs the O(N^2 G^2) scattered ``minplus_vecmat``."""
    return banded_minplus_pallas(dist, E, st, lo=lo)


def banded_minplus_chain_kbest(dist: jnp.ndarray, E: jnp.ndarray,
                               st: jnp.ndarray, K: int, *, lo=None):
    """Chained banded k-best relaxation: K cheapest paths per state.

    dist: [B, N, G+1]; E/st: [B, L, N, N]; K slots -> (hist
    [B, L, N, G+1, K], par_n / par_k [B, L, N, G+1, K] int32, -1 unused).
    The k-slot grid stays in VMEM across the layer chain; slot order
    matches the numpy k-best engine.  This is the kernel behind the
    Pareto-frontier subsystem's k-best DP (``core/frontier.py``)."""
    return banded_minplus_chain_kbest_pallas(dist, E, st, K, lo=lo)


def banded_minplus_chain(dist: jnp.ndarray, E: jnp.ndarray, st: jnp.ndarray,
                         *, lo=None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chained banded relaxation: a whole (B, L)-layer batch per call.

    dist: [B, N, G+1]; E/st: [B, L, N, N] -> (hist [B, L, N, G+1] — the
    grid AFTER each layer — and argmin source node [B, L, N, G+1] int32,
    -1 unreachable).  The distance grid stays in VMEM across the layer
    chain (one launch per scenario), which is what the FIN population
    engine drives per churn tick."""
    return banded_minplus_chain_pallas(dist, E, st, lo=lo)
