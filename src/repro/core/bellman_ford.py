"""(min,+) relaxation primitives backing FIN's minimum-cost traversal.

FIN's feasible graph is a layered DAG over states s = (node, depth); the
minimum-cost traversal is a sequence of (min,+) ("tropical") matrix-vector
products — exactly a Bellman-Ford relaxation restricted to the layer
structure.  Two families of engines:

  * dense    — (S, S) flattened-state matrices, S = N*(gamma+1)
               (numpy reference with argmin backtracking, jitted jnp, and
               the dense ``minplus`` TPU kernel); O(N^2 G^2) per layer,
               kept for equivalence testing and the k-best mode;
  * banded   — the compact (N, G+1) grid exploiting the graph's band
               structure in depth (see the banded section below): numpy
               (float64, bit-exact vs dense), jnp (f32 lax.scan), and the
               banded ``minplus`` Pallas kernel; O(N^2 G) per layer.

The paper reports solver wall-time (Table VII), so this *is* a hot spot the
paper measures; on TPU the relaxation maps naturally onto the VPU with
(min,+) in place of (+,*) — see kernels/minplus/minplus.py.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# cache-residency chunking (shared by every batched relaxation call site)
# ---------------------------------------------------------------------------

#: default per-chunk budget for a batched relaxation's candidate tensor
#: ((D, N, N, G+1) banded / (D, S, S) dense); override with the
#: REPRO_RELAX_CHUNK_BYTES environment variable (see docs/ARCHITECTURE.md).
_RELAX_CHUNK_BYTES_DEFAULT = 4 << 20


def relax_chunk_bytes() -> int:
    """Cache-residency budget (bytes) for one relaxation chunk's candidate
    tensor.  Beyond ~L2/L3 size the broadcast turns memory-bound and batched
    throughput collapses; the chunk count is derived from this budget and
    the per-scenario candidate size (compact banded or dense).

    A set-but-invalid REPRO_RELAX_CHUNK_BYTES raises immediately (an unset
    or empty variable means the default): a typo'd budget silently falling
    back would only surface as an inexplicable perf cliff deep inside the
    chunked relaxation.
    """
    raw = os.environ.get("REPRO_RELAX_CHUNK_BYTES", "")
    if not raw:
        return _RELAX_CHUNK_BYTES_DEFAULT
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(
            f"REPRO_RELAX_CHUNK_BYTES must be a positive integer (bytes), "
            f"got {raw!r}") from None
    if val <= 0:
        raise ValueError(
            f"REPRO_RELAX_CHUNK_BYTES must be a positive integer (bytes), "
            f"got {raw!r}")
    return val


def relax_chunk_rows(bytes_per_row: int) -> int:
    """Scenario rows per cache-resident relaxation chunk.

    ``bytes_per_row`` is the size of ONE scenario's live working set inside
    the batched relaxation (candidate tensor plus whatever per-scenario
    index/argmin payload rides along).  Always at least 1, so callers never
    have to special-case a single over-budget scenario.  This is the one
    home of the ``max(1, budget // row_bytes)`` arithmetic that the solver
    (``fin._run_dp_batch``), the plan IR (``plan._warm_round0``) and the
    population engine all share.
    """
    if bytes_per_row <= 0:
        raise ValueError(f"bytes_per_row must be positive, got "
                         f"{bytes_per_row!r}")
    return max(1, relax_chunk_bytes() // bytes_per_row)


# ---------------------------------------------------------------------------
# device batch buckets (shared by the jnp/pallas engines and the mesh)
# ---------------------------------------------------------------------------

#: fewest scenario rows a device relaxation pads its batch to
MIN_RELAX_ROWS = 16


def bucket_rows(D: int, n_devices: int = 1) -> int:
    """Rows per device that a device relaxation of ``D`` scenarios pads to:
    at least ``MIN_RELAX_ROWS`` in all, rounded up to a power of two.

    A tick's dirty-state count changes from tick to tick, and each new
    batch shape is a new XLA/Mosaic compile (about a second per compile on
    a TPU v5e).  Buckets bound a run to a few programs per chain length."""
    per = max(-(-D // n_devices), -(-MIN_RELAX_ROWS // n_devices), 1)
    return 1 << (per - 1).bit_length()


def pad_relax_rows(init: np.ndarray, E: np.ndarray, st: np.ndarray,
                   rows: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad a device relaxation's float32 init (D, N, G+1), E (D, L, N, N)
    and int32 steepness to ``rows`` scenarios with empty chains (all-inf
    init and energies, zero steepness).  Scenarios relax independently,
    so callers strip the padding from the outputs with ``[:D]``."""
    pad = rows - init.shape[0]
    if pad <= 0:
        return init, E, st
    return (np.concatenate([init, np.full((pad,) + init.shape[1:], np.inf,
                                          np.float32)]),
            np.concatenate([E, np.full((pad,) + E.shape[1:], np.inf,
                                       np.float32)]),
            np.concatenate([st, np.zeros((pad,) + st.shape[1:], np.int32)]))


# ---------------------------------------------------------------------------
# numpy reference
# ---------------------------------------------------------------------------

def minplus_vecmat_np(dist: np.ndarray, W: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """out[t] = min_s dist[s] + W[s, t]; returns (out, argmin_s)."""
    cand = dist[:, None] + W                     # (S, T)
    arg = np.argmin(cand, axis=0)
    out = cand[arg, np.arange(W.shape[1])]
    return out, arg


def bellman_ford_np(W: np.ndarray, src: int, *, max_iters: Optional[int] = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Classic dense Bellman-Ford on an (S, S) weight matrix (inf = no edge).

    Returns (dist, parent).  Used to cross-validate the layered DP and to
    solve non-layered instances (e.g. MCP on general meshes).
    """
    S = W.shape[0]
    dist = np.full(S, np.inf)
    parent = np.full(S, -1, dtype=np.int64)
    dist[src] = 0.0
    iters = max_iters if max_iters is not None else S - 1
    for _ in range(iters):
        new, arg = minplus_vecmat_np(dist, W)
        improved = new < dist - 1e-18
        if not improved.any():
            break
        parent[improved] = arg[improved]
        dist = np.where(improved, new, dist)
    return dist, parent


# ---------------------------------------------------------------------------
# jnp (jit) backend
# ---------------------------------------------------------------------------

@jax.jit
def minplus_vecmat_jnp(dist: jnp.ndarray, W: jnp.ndarray) -> jnp.ndarray:
    """out[t] = min_s dist[s] + W[s, t] (cost only, differentiable-free)."""
    return jnp.min(dist[:, None] + W, axis=0)


@jax.jit
def minplus_matmat_jnp(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """Tropical matmul: out[i, j] = min_k A[i, k] + B[k, j].

    This is the batched form used when relaxing many sources at once
    (multi-application orchestration relaxes one row per user).
    """
    return jnp.min(A[:, :, None] + B[None, :, :], axis=1)


def layered_relax_jnp(init: jnp.ndarray, Ws: jnp.ndarray) -> jnp.ndarray:
    """Relax through a stack of layer transition matrices via lax.scan.

    init: (S,) initial distances; Ws: (L, S, S).  Returns (L+1, S) distances
    after each layer.  jit-compiled once per (S, L) shape.
    """
    def step(dist, W):
        new = minplus_vecmat_jnp(dist, W)
        return new, new

    _, hist = jax.lax.scan(step, init, Ws)
    return jnp.concatenate([init[None], hist], axis=0)


def layered_relax(init: np.ndarray, Ws: np.ndarray, backend: str = "numpy",
                  ) -> np.ndarray:
    """Dispatch layered relaxation to a backend. Returns (L+1, S) distances."""
    if backend == "numpy":
        out = [init]
        d = init
        for W in Ws:
            d, _ = minplus_vecmat_np(d, W)
            out.append(d)
        return np.stack(out)
    if backend == "jnp":
        return np.asarray(layered_relax_jnp(jnp.asarray(init), jnp.asarray(Ws)))
    if backend == "pallas":
        from repro.kernels.minplus.ops import minplus_vecmat as mp_pallas
        out = [init]
        d = jnp.asarray(init, jnp.float32)
        for W in Ws:
            d = mp_pallas(d[None, :], jnp.asarray(W, jnp.float32))[0]
            out.append(np.asarray(d))
        return np.stack(out)
    raise ValueError(f"unknown backend {backend!r}")


# ---------------------------------------------------------------------------
# argmin-tracking relaxation (exact path reconstruction for the FIN DP)
# ---------------------------------------------------------------------------

def layered_relax_argmin(init: np.ndarray, Ws: np.ndarray,
                         backend: str = "numpy"
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """Layered relaxation with parent recovery.

    init: (S,), Ws: (L, S, S).  Returns (dist, parent) where dist is (L+1, S)
    distances after each layer and parent is (L, S): parent[l, t] is the
    argmin source state in layer l for state t in layer l+1, or -1 where t is
    unreached.  Single-scenario view of ``batched_layered_relax_argmin``
    (which see for the backend contract); the pallas backend runs the
    ``minplus`` argmin kernel layer by layer.
    """
    hist, par = batched_layered_relax_argmin(np.asarray(init)[None],
                                             np.asarray(Ws)[None],
                                             backend=backend)
    return hist[0], par[0]


def batched_layered_relax_argmin(init: np.ndarray, Ws: np.ndarray,
                                 backend: str = "numpy"
                                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Batched layered relaxation with parents: one (min,+) chain per scenario.

    init: (B, S); Ws: (B, L, S, S).  Returns (dist (B, L+1, S), parent
    (B, L, S)); parent is -1 where the target state is unreachable.  Backends:
    ``numpy`` (vectorized over the whole batch per layer), ``jnp`` (one
    lax.scan over layers, batch in the leading dim), ``pallas`` (argmin
    kernel, looped per scenario — per-scenario W defeats the shared-W kernel
    batching; block-diagonal matmat batching is the TPU follow-up).
    """
    B, S = init.shape
    L = Ws.shape[1]
    if L == 0:                       # single-block chain: no transitions
        return (np.asarray(init)[:, None, :].astype(np.float64),
                np.zeros((B, 0, S), dtype=np.int64))
    if backend == "numpy":
        dist = init
        hist = [dist]
        pars = []
        cand = np.empty((B, S, S), dtype=np.float64)   # reused across layers
        for l in range(L):
            np.add(dist[:, :, None], Ws[:, l], out=cand)     # (B, S, T)
            arg = np.argmin(cand, axis=1)
            new = np.take_along_axis(cand, arg[:, None, :], axis=1)[:, 0, :]
            pars.append(np.where(np.isfinite(new), arg, -1))
            hist.append(new)
            dist = new
        return np.stack(hist, axis=1), np.stack(pars, axis=1).astype(np.int64)
    if backend == "jnp":
        def step(d, W):
            cand = d[:, :, None] + W                         # (B, S, T)
            new = jnp.min(cand, axis=1)
            arg = jnp.argmin(cand, axis=1)
            return new, (new, jnp.where(jnp.isfinite(new), arg, -1))
        _, (h, p) = jax.lax.scan(step, jnp.asarray(init),
                                 jnp.moveaxis(jnp.asarray(Ws), 1, 0))
        hist = np.concatenate([np.asarray(init)[:, None],
                               np.moveaxis(np.asarray(h), 0, 1)], axis=1)
        return hist, np.moveaxis(np.asarray(p), 0, 1).astype(np.int64)
    if backend == "pallas":
        from repro.kernels.minplus.ops import minplus_vecmat_argmin
        hists, pars = [], []
        for b in range(B):
            d = jnp.asarray(init[b], jnp.float32)
            hist = [np.asarray(init[b])]
            par = []
            for W in Ws[b]:
                out, arg = minplus_vecmat_argmin(
                    d[None, :], jnp.asarray(W, jnp.float32))
                d = out[0]
                hist.append(np.asarray(d, np.float64))
                par.append(np.asarray(arg[0], np.int64))
            hists.append(np.stack(hist))
            pars.append(np.stack(par))
        return np.stack(hists), np.stack(pars)
    raise ValueError(f"unknown backend {backend!r}")


# ---------------------------------------------------------------------------
# k-best relaxation (beyond-paper quantizer-collision fix, vectorized)
# ---------------------------------------------------------------------------

def batched_layered_relax_min(init: np.ndarray, Ws: np.ndarray) -> np.ndarray:
    """Batched layered relaxation, distances only (numpy).

    init: (B, S); Ws: (B, L, S, S).  Returns dist (B, L+1, S).  The parent
    tensor is deliberately NOT computed: callers that need path
    reconstruction recover a parent with one argmin column scan per
    backtracked step (see fin._FlatDP) — orders of magnitude fewer argmins
    than materializing (B, L, S) parents when only a handful of end states
    are ever traced back.
    """
    B, S = init.shape
    L = Ws.shape[1]
    if L == 0:
        return np.asarray(init)[:, None, :].astype(np.float64)
    dist = init
    hist = [dist]
    cand = np.empty((B, S, S), dtype=np.float64)   # reused across layers
    for l in range(L):
        np.add(dist[:, :, None], Ws[:, l], out=cand)
        dist = np.min(cand, axis=1)
        hist.append(dist)
    return np.stack(hist, axis=1)


def batched_layered_relax_kbest(init: np.ndarray, Ws: np.ndarray, K: int
                                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Keep the K cheapest paths per state while relaxing layer by layer.

    init: (B, S); Ws: (B, L, S, S).  Returns (dist (B, L+1, S, K), par_s,
    par_k (B, L, S, K)) — the k-th cheapest distance at each state with the
    (source state, source rank) that produced it (-1 where unused).  Each
    layer sorts the S*K candidate pool per target with a stable argsort, so
    tie order is deterministic (source-state-major).  numpy only: K > 1 is
    the beyond-paper small-gamma mode and stays far from the hot path.
    """
    B, S = init.shape
    L = Ws.shape[1]
    dist = np.full((B, S, K), np.inf)
    dist[:, :, 0] = init
    if L == 0:
        return (dist[:, None], np.zeros((B, 0, S, K), dtype=np.int64),
                np.zeros((B, 0, S, K), dtype=np.int64))
    hist = [dist]
    ps, pk = [], []
    for l in range(L):
        # (B, S, K, T) candidate pool -> K smallest per (B, T)
        cand = (dist[:, :, :, None] + Ws[:, l, :, None, :]).reshape(B, S * K, S)
        idx = np.argsort(cand, axis=1, kind="stable")[:, :K, :]    # (B, K, T)
        val = np.take_along_axis(cand, idx, axis=1)
        new = np.moveaxis(val, 1, 2)                               # (B, T, K)
        src = np.moveaxis(idx, 1, 2)
        fin = np.isfinite(new)
        ps.append(np.where(fin, src // K, -1))
        pk.append(np.where(fin, src % K, -1))
        hist.append(new)
        dist = new
    return (np.stack(hist, axis=1), np.stack(ps, axis=1).astype(np.int64),
            np.stack(pk, axis=1).astype(np.int64))


# ---------------------------------------------------------------------------
# depth-banded relaxation (compact (node, depth) states, no (S, S) tensors)
# ---------------------------------------------------------------------------
#
# The feasible graph's transition structure is *banded* in depth: an edge only
# connects (n, g) to (n', g + steep[n, n']), so the dense (S, S) layer matrix
# with S = N*(G+1) holds exactly one finite entry per (source node, target
# state) pair.  The relaxation over the compact (N, G+1) distance grid is a
# shift-by-steep gather + min over source nodes:
#
#   new[n', g'] = min_n  dist[n, g' - steep[n, n']] + E[n, n']
#
# (inadmissible where g' - steep < 0, the edge is pruned, or the
# lambda-proximity window excludes g').  Per-layer work and memory drop from
# O(N^2 G^2) to O(N^2 G) — a (gamma+1)-fold win over the dense path.
#
# Equivalence with the dense engines is exact on the numpy path: the banded
# candidate set per target state is identical to the finite entries of the
# dense column, the float64 adds are the same operations, and the argmin-
# over-source-nodes tie order equals the dense first-occurrence flat-state
# order (states are node-major, and each source node contributes at most one
# candidate depth per target).

def _banded_gather_idx(steep: np.ndarray, Gp1: int,
                       lo: Optional[int]) -> np.ndarray:
    """(..., N, N, G+1) int32 source-depth gather indices for banded layers.

    steep: (..., N, N) integer steepness (inf = pruned).  Index g - steep per
    target depth g; every inadmissible candidate (pruned edge, negative
    source depth, lambda window) is routed to the sentinel index ``Gp1`` —
    gathering from a distance grid padded with one inf column then yields
    the fully masked candidate tensor with no boolean where-pass over it.
    """
    finite = np.isfinite(steep)
    # sentinel Gp1 steepness makes every source depth negative -> inf column
    sti = np.where(finite, steep, Gp1).astype(np.int32)
    g = np.arange(Gp1, dtype=np.int32)
    idx = g - sti[..., None]
    if lo is not None:
        np.copyto(idx, np.int32(-1), where=(g < lo) & (sti[..., None] != 0))
    np.copyto(idx, np.int32(Gp1), where=idx < 0)
    return idx


def batched_banded_relax_min(init: np.ndarray, E: np.ndarray,
                             steep: np.ndarray,
                             lo: Optional[int] = None,
                             *, idx: Optional[np.ndarray] = None) -> np.ndarray:
    """Banded layered relaxation, distances only (numpy, float64 exact).

    init: (B, N, G+1); E/steep: (B, L, N, N).  Returns hist
    (B, L+1, N, G+1).  Distances are bit-for-bit equal to the dense
    ``batched_layered_relax_min`` on the scattered (S, S) matrices — the
    banded candidate set per target state is exactly the finite entries of
    the dense column, computed with the same float64 adds.

    ``idx`` optionally supplies the (B, L, N, N, G+1) gather-index tensor
    (``_banded_gather_idx(steep, G+1, lo)``) precomputed by the caller — the
    incremental ``Plan`` layer maintains it across deltas (only mutated
    rows/cols are recomputed), turning the per-solve index build into a
    no-op on the warm path.  When given, ``steep`` is not read.
    """
    B, N, Gp1 = init.shape
    L = E.shape[1]
    dist = np.asarray(init, dtype=np.float64)
    if L == 0:
        return dist[:, None]
    if idx is None:
        # all layers' gather indices in one vectorized pass (O(L N^2 G))
        idx = _banded_gather_idx(steep, Gp1, lo)         # (B, L, N, N, G+1)
    pad = np.empty((B, N, Gp1 + 1))                      # dist + inf column
    pad[:, :, Gp1] = np.inf
    b_i = np.arange(B)[:, None, None, None]
    n_i = np.arange(N)[None, :, None, None]
    hist = [dist]
    for l in range(L):
        pad[:, :, :Gp1] = dist
        cand = pad[b_i, n_i, idx[:, l]]                  # (B, N, N, G+1)
        cand += E[:, l, :, :, None]
        dist = cand.min(axis=1)                          # (B, N, G+1)
        hist.append(dist)
    return np.stack(hist, axis=1)


def batched_banded_relax_minarg(init: np.ndarray, E: np.ndarray,
                                steep: np.ndarray,
                                lo: Optional[int] = None,
                                *, idx: Optional[np.ndarray] = None
                                ) -> Tuple[np.ndarray, np.ndarray]:
    """Banded relaxation with stored argmin parents (numpy, float64 exact).

    Same contract as :func:`batched_banded_relax_min` (distances are
    bit-identical — the min is read back through the argmin), plus par_n
    (B, L, N, G+1) int64: the argmin *source node* of each state, -1 where
    unreachable, with the same first-occurrence tie order as
    ``banded_parent_np`` / the dense flat-state column argmin.  This is the
    engine of the incremental ``Plan`` layer: a warm plan backtracks its DP
    grid repeatedly across churn ticks, so paying one vectorized argmin per
    relaxation beats re-deriving parents with per-step candidate scans.
    ``idx`` as in ``batched_banded_relax_min``.
    """
    B, N, Gp1 = init.shape
    L = E.shape[1]
    dist = np.asarray(init, dtype=np.float64)
    if L == 0:
        return dist[:, None], np.zeros((B, 0, N, Gp1), dtype=np.int64)
    if idx is None:
        idx = _banded_gather_idx(steep, Gp1, lo)
    pad = np.empty((B, N, Gp1 + 1))
    pad[:, :, Gp1] = np.inf
    b_i = np.arange(B)[:, None, None, None]
    n_i = np.arange(N)[None, :, None, None]
    hist = [dist]
    pars = []
    for l in range(L):
        pad[:, :, :Gp1] = dist
        cand = pad[b_i, n_i, idx[:, l]]                  # (B, N, N, G+1)
        cand += E[:, l, :, :, None]
        arg = np.argmin(cand, axis=1)                    # (B, N, G+1)
        # min == cand[argmin] exactly (no NaNs in the tropical semiring),
        # and one fused reduction beats a take_along_axis gather
        dist = cand.min(axis=1)
        pars.append(np.where(np.isfinite(dist), arg, -1))
        hist.append(dist)
    return np.stack(hist, axis=1), np.stack(pars, axis=1)


def batched_banded_relax_kbest(init: np.ndarray, E: np.ndarray,
                               steep: np.ndarray, K: int,
                               lo: Optional[int] = None,
                               *, idx: Optional[np.ndarray] = None
                               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Banded k-slot relaxation: the K cheapest paths per (node, depth).

    init: (B, N, G+1); E/steep: (B, L, N, N).  Returns (hist
    (B, L+1, N, G+1, K), par_n, par_k (B, L, N, G+1, K) int64, -1 where the
    slot is unused); the parent *depth* is implied by the band: g_src =
    g - steep[par_n, n].  Distances and slot order are bit-for-bit equal to
    the dense ``batched_layered_relax_kbest`` on the scattered (S, S)
    matrices: per target state each source node contributes at most one
    candidate depth, so the banded (source-node-major, rank-minor) pool
    order equals the dense flat-state (source-state-major, rank-minor)
    order, and both engines pick the K smallest with a stable argsort over
    the same float64 sums.  This is the k-best engine behind the Pareto
    frontier subsystem (``core/frontier.py``): where the K=1 engines keep
    only the energy argmin per state, the k slots carry the alternative
    placements the frontier is built from.

    ``idx`` as in :func:`batched_banded_relax_min` — the incremental
    ``Plan`` layer passes its maintained gather indices so warm k-best
    re-solves skip the index build.
    """
    if K < 1:
        raise ValueError(f"K must be >= 1, got {K}")
    B, N, Gp1 = init.shape
    L = E.shape[1]
    dist = np.full((B, N, Gp1, K), np.inf)
    dist[..., 0] = np.asarray(init, dtype=np.float64)
    if L == 0:
        return (dist[:, None], np.zeros((B, 0, N, Gp1, K), dtype=np.int64),
                np.zeros((B, 0, N, Gp1, K), dtype=np.int64))
    if idx is None:
        idx = _banded_gather_idx(steep, Gp1, lo)         # (B, L, N, N, G+1)
    pad = np.empty((B, N, Gp1 + 1, K))                   # dist + inf column
    pad[:, :, Gp1] = np.inf
    b_i = np.arange(B)[:, None, None, None]
    n_i = np.arange(N)[None, :, None, None]
    hist = [dist]
    pns, pks = [], []
    for l in range(L):
        pad[:, :, :Gp1] = dist
        cand = pad[b_i, n_i, idx[:, l]]                  # (B, N, N, G+1, K)
        cand += E[:, l, :, :, None, None]
        # candidate pool per target state: source-node-major, rank-minor —
        # the same relative order as the dense flat-state pool (states are
        # node-major and each source node contributes one depth per target)
        pool = np.ascontiguousarray(np.moveaxis(cand, 4, 2))
        pool = pool.reshape(B, N * K, N, Gp1)
        sel = np.argsort(pool, axis=1, kind="stable")[:, :K]   # (B, K, N, G+1)
        val = np.take_along_axis(pool, sel, axis=1)
        dist = np.moveaxis(val, 1, 3)                    # (B, N, G+1, K)
        src = np.moveaxis(sel, 1, 3)
        ok = np.isfinite(dist)
        pns.append(np.where(ok, src // K, -1))
        pks.append(np.where(ok, src % K, -1))
        hist.append(dist)
    return (np.stack(hist, axis=1), np.stack(pns, axis=1).astype(np.int64),
            np.stack(pks, axis=1).astype(np.int64))


def batched_banded_relax_kbest_pallas(init: np.ndarray, E: np.ndarray,
                                      steep: np.ndarray, K: int,
                                      lo: Optional[int] = None
                                      ) -> Tuple[np.ndarray, np.ndarray,
                                                 np.ndarray]:
    """k-best variant of the chained banded pallas engine (float32).

    Same contract as :func:`batched_banded_relax_kbest`; the whole
    (B, L) batch relaxes as one chained kernel launch per scenario with
    the (N, K, G+1) k-slot distance grid carried in VMEM across layers
    (see ``kernels/minplus``).  Slot order matches the numpy engine's
    stable-argsort order (iterated first-occurrence argmin extraction);
    distances carry the usual f32 relaxation error.
    """
    from repro.kernels.minplus.ops import banded_minplus_chain_kbest
    B, N, Gp1 = init.shape
    finite = np.isfinite(steep)
    sti = np.where(finite, steep, 0).astype(np.int32)
    Ef = np.where(finite, E, np.inf).astype(np.float32)
    import jax.numpy as _jnp
    h, pn, pk = banded_minplus_chain_kbest(
        _jnp.asarray(np.asarray(init, np.float32)), _jnp.asarray(Ef),
        _jnp.asarray(sti), K, lo=lo)
    init64 = np.full((B, 1, N, Gp1, K), np.inf)
    init64[:, 0, :, :, 0] = init
    hist = np.concatenate([init64, np.asarray(h, np.float64)], axis=1)
    return (hist, np.asarray(pn).astype(np.int64),
            np.asarray(pk).astype(np.int64))


def banded_parent_np(dist_prev: np.ndarray, E_l: np.ndarray, st_l: np.ndarray,
                     n: int, g: int, lo: Optional[int]) -> Tuple[int, int]:
    """Recover the argmin parent of target state (n, g) for one layer.

    dist_prev: (N, G+1) previous-layer distances; E_l/st_l: (N, N).  Returns
    (parent node, parent depth).  First-occurrence argmin over source nodes —
    identical tie order to the dense flat-state column argmin (see module
    comment).  One O(N) scan per backtracked step (the dense lazy path scans
    O(S) = O(N G)).
    """
    st = st_l[:, n]                                      # (N,)
    finite = np.isfinite(st)
    sti = np.where(finite, st, 0).astype(np.int64)
    gsrc = g - sti
    ok = finite & (gsrc >= 0)
    if lo is not None:
        ok &= (g >= lo) | (sti == 0)
    cand = np.where(ok, dist_prev[np.arange(len(st)), np.where(ok, gsrc, 0)]
                    + E_l[:, n], np.inf)
    pn = int(np.argmin(cand))
    return pn, g - int(sti[pn])


@functools.partial(jax.jit, static_argnames=("lo",))
def _banded_relax_scan_jnp(init: jnp.ndarray, E: jnp.ndarray,
                           st: jnp.ndarray, lo: Optional[int]):
    """jit core of the banded jnp engine (float32, argmin parents).

    init: (B, N, G+1); E: (B, L, N, N) f32 (inf = pruned); st: (B, L, N, N)
    int32 (0 where pruned — E's inf kills those candidates).  Returns
    (hist (B, L+1, N, G+1), par_n (B, L, N, G+1) int32, -1 unreachable).
    """
    B, N, Gp1 = init.shape
    g = jnp.arange(Gp1)

    def step(dist, layer):
        e, s = layer                                      # (B, N, N) each
        gsrc = g[None, None, None, :] - s[..., None]      # (B, N, N, G+1)
        ok = gsrc >= 0
        if lo is not None:
            ok &= (g[None, None, None, :] >= lo) | (s[..., None] == 0)
        gat = jnp.take_along_axis(
            dist[:, :, None, :],
            jnp.clip(gsrc, 0, Gp1 - 1), axis=3)
        cand = jnp.where(ok, gat + e[..., None], jnp.inf)
        new = jnp.min(cand, axis=1)                       # (B, N, G+1)
        arg = jnp.argmin(cand, axis=1).astype(jnp.int32)
        return new, (new, jnp.where(jnp.isfinite(new), arg, -1))

    _, (h, p) = jax.lax.scan(step, init,
                             (jnp.moveaxis(E, 1, 0), jnp.moveaxis(st, 1, 0)))
    hist = jnp.concatenate([init[:, None], jnp.moveaxis(h, 0, 1)], axis=1)
    return hist, jnp.moveaxis(p, 0, 1)


def batched_banded_relax_argmin(init: np.ndarray, E: np.ndarray,
                                steep: np.ndarray, lo: Optional[int] = None,
                                backend: str = "jnp"
                                ) -> Tuple[np.ndarray, np.ndarray]:
    """Banded relaxation with argmin-over-source-node parents.

    init: (B, N, G+1); E/steep: (B, L, N, N) (steep: int values or inf).
    Returns (hist (B, L+1, N, G+1) float64, par_n (B, L, N, G+1) int64, -1
    where unreachable).  The parent *depth* is implied: g_src = g -
    steep[par_n, n].  Backends: ``jnp`` (float32 lax.scan) and ``pallas``
    (the chained banded minplus kernel).  Both pad the batch to
    ``bucket_rows(B)`` scenarios.
    """
    B, N, Gp1 = init.shape
    L = E.shape[1]
    if L == 0:
        return (np.asarray(init)[:, None].astype(np.float64),
                np.zeros((B, 0, N, Gp1), dtype=np.int64))
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown banded backend {backend!r}")
    finite = np.isfinite(steep)
    initf, Ef, sti = pad_relax_rows(
        np.asarray(init, np.float32),
        np.where(finite, E, np.inf).astype(np.float32),
        np.where(finite, steep, 0).astype(np.int32), bucket_rows(B))
    if backend == "jnp":
        hist, par = _banded_relax_scan_jnp(jnp.asarray(initf),
                                           jnp.asarray(Ef), jnp.asarray(sti),
                                           lo)
        return (np.asarray(hist, np.float64)[:B],
                np.asarray(par).astype(np.int64)[:B])
    from repro.kernels.minplus.ops import banded_minplus_chain
    # one chained launch relaxes the whole (B, L) batch — the distance
    # grid is carried in VMEM across layers instead of round-tripping
    # through HBM between per-layer kernel calls
    h, p = banded_minplus_chain(jnp.asarray(initf), jnp.asarray(Ef),
                                jnp.asarray(sti), lo=lo)
    hist = np.concatenate([np.asarray(init, np.float64)[:, None],
                           np.asarray(h, np.float64)[:B]], axis=1)
    return hist, np.asarray(p).astype(np.int64)[:B]
