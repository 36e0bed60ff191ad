"""Pallas TPU kernel: tropical (min,+) matrix product — FIN's relaxation.

out[b, t] = min_s ( dist[b, s] + W[s, t] )

This is the inner loop of FIN's minimum-cost traversal over the feasible
graph (one product per DNN block layer; see core/bellman_ford.py).  On TPU
the (min,+) semiring cannot use the MXU (no min-accumulate), but maps onto
the VPU as a broadcast-add + row-min, tiled so each (dist-block, W-block)
pair stays in VMEM.

Tiling: grid (B/bb, T/bt, S/bs); S is the minor (fastest) axis so the output
block acts as a VMEM accumulator across S-steps:

  acc[bb, bt]  <- min(acc, min_s(dist[bb, bs, None] + W[bs, bt]))

Block sizes default to (8, 128, 128) — lane-aligned (8 sublanes x 128 lanes
for f32) and 8*128 + 128*128 + 8*128 floats ~= 70 KB of VMEM per step.

Every entry point takes ``interpret=None``: compiled on TPU, interpreted on
CPU (``kernels/platform.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import interpret_mode

BIG = 3.0e38          # acts as +inf under min/add without NaNs (python float,
                      # NOT jnp scalar: kernels must not capture tracers)


def _pad_to(x, m, axis, value):
    """Pad ``axis`` of ``x`` up to a multiple of ``m`` with ``value``."""
    r = (-x.shape[axis]) % m
    if r == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, r)
    return jnp.pad(x, widths, constant_values=value)


def _minplus_kernel(dist_ref, w_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, BIG)

    d = dist_ref[...]              # [bb, bs]
    w = w_ref[...]                 # [bs, bt]
    cand = jnp.min(d[:, :, None] + w[None, :, :], axis=1)   # [bb, bt]
    out_ref[...] = jnp.minimum(out_ref[...], cand)


@functools.partial(jax.jit, static_argnames=("bb", "bs", "bt", "interpret"))
def minplus_pallas(dist: jnp.ndarray, W: jnp.ndarray, *, bb: int = 8,
                   bs: int = 128, bt: int = 128,
                   interpret=None) -> jnp.ndarray:
    """dist: [B, S]; W: [S, T] (use BIG or +inf for missing edges).
    Returns [B, T] min-plus product.  Inputs are padded to block multiples.
    """
    B, S = dist.shape
    S2, T = W.shape
    assert S == S2
    dist = jnp.where(jnp.isfinite(dist), dist, BIG).astype(jnp.float32)
    W = jnp.where(jnp.isfinite(W), W, BIG).astype(jnp.float32)

    dist_p = _pad_to(_pad_to(dist, bb, 0, BIG), bs, 1, BIG)
    W_p = _pad_to(_pad_to(W, bs, 0, BIG), bt, 1, BIG)
    Bp, Sp = dist_p.shape
    Tp = W_p.shape[1]

    out = pl.pallas_call(
        _minplus_kernel,
        grid=(Bp // bb, Tp // bt, Sp // bs),
        in_specs=[
            pl.BlockSpec((bb, bs), lambda i, j, k: (i, k)),
            pl.BlockSpec((bs, bt), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bb, bt), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Tp), jnp.float32),
        interpret=interpret_mode(interpret),
    )(dist_p, W_p)
    # saturate padded-path artifacts back to BIG (add of two BIGs overflows
    # to +inf in f32; clamp for clean downstream comparisons)
    out = jnp.where(out >= BIG, jnp.inf, out)
    return out[:B, :T]


def _minplus_argmin_kernel(bs, dist_ref, w_ref, out_ref, arg_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, BIG)
        arg_ref[...] = jnp.full_like(arg_ref, -1)

    d = dist_ref[...]              # [bb, bs]
    w = w_ref[...]                 # [bs, bt]
    cand = d[:, :, None] + w[None, :, :]                     # [bb, bs, bt]
    local = jnp.min(cand, axis=1)
    larg = jnp.argmin(cand, axis=1).astype(jnp.int32) + k * bs
    prev = out_ref[...]
    # strict < keeps the first-occurrence argmin across S-blocks, matching
    # np.argmin tie order (within a block jnp.argmin is first-occurrence too)
    improved = local < prev
    arg_ref[...] = jnp.where(improved, larg, arg_ref[...])
    out_ref[...] = jnp.where(improved, local, prev)


@functools.partial(jax.jit, static_argnames=("bb", "bs", "bt", "interpret"))
def minplus_argmin_pallas(dist: jnp.ndarray, W: jnp.ndarray, *, bb: int = 8,
                          bs: int = 128, bt: int = 128,
                          interpret=None):
    """dist: [B, S]; W: [S, T].  Returns (out [B, T], argmin_s [B, T] int32);
    argmin is -1 where no finite path reaches t.  Same VMEM tiling as
    ``minplus_pallas`` with an int32 accumulator riding along — this is the
    parent-recovery variant backing exact FIN path reconstruction."""
    B, S = dist.shape
    S2, T = W.shape
    assert S == S2
    dist = jnp.where(jnp.isfinite(dist), dist, BIG).astype(jnp.float32)
    W = jnp.where(jnp.isfinite(W), W, BIG).astype(jnp.float32)

    dist_p = _pad_to(_pad_to(dist, bb, 0, BIG), bs, 1, BIG)
    W_p = _pad_to(_pad_to(W, bs, 0, BIG), bt, 1, BIG)
    Bp, Sp = dist_p.shape
    Tp = W_p.shape[1]

    out, arg = pl.pallas_call(
        functools.partial(_minplus_argmin_kernel, bs),
        grid=(Bp // bb, Tp // bt, Sp // bs),
        in_specs=[
            pl.BlockSpec((bb, bs), lambda i, j, k: (i, k)),
            pl.BlockSpec((bs, bt), lambda i, j, k: (k, j)),
        ],
        out_specs=(pl.BlockSpec((bb, bt), lambda i, j, k: (i, j)),
                   pl.BlockSpec((bb, bt), lambda i, j, k: (i, j))),
        out_shape=(jax.ShapeDtypeStruct((Bp, Tp), jnp.float32),
                   jax.ShapeDtypeStruct((Bp, Tp), jnp.int32)),
        interpret=interpret_mode(interpret),
    )(dist_p, W_p)
    unreached = out >= BIG
    out = jnp.where(unreached, jnp.inf, out)
    arg = jnp.where(unreached, -1, arg)
    return out[:B, :T], arg[:B, :T]


# ---------------------------------------------------------------------------
# depth-banded variant: compact (node, depth) states, no (S, S) tensors
# ---------------------------------------------------------------------------
#
# A banded layer reads, for source node n and target m, the source row
# shifted right by the integer steepness st[n, m] along the depth lanes:
# cand[m, g] = dist[n, g - st[n, m]] + E[n, m].  Mosaic has no 3-D lane
# gather, so the shift is a barrel shifter: for each bit k of the
# steepness, rotate the lanes by 2**k (``pltpu.roll``, a static lane
# rotate) where that bit is set.  After ceil(log2(Gp)) stages every target
# row holds its source row rotated by st; the lanes that wrapped around are
# exactly those with g < st, which the admissibility mask drops.  The
# min/argmin over source nodes is a static fold with a strict ``<``, i.e.
# first-occurrence argmin — the numpy engine's tie order.
#
# E and st reach the kernels as [..., N(src), N(tgt), 1]: one source's
# column over targets is then a leading-index read of shape [tgt, 1], which
# broadcasts along the lanes without a relayout.

def _band_setup(st_col, e_col, lo, M, Gp):
    """Per (layer, source) constants for a block of M targets: the barrel
    stage masks, the admissibility mask and the broadcast edge energies.
    st_col / e_col: [M, 1] (one source node's steepness / energy per
    target)."""
    s = jnp.broadcast_to(st_col, (M, Gp))
    stages = [(1 << k, (s & (1 << k)) != 0)
              for k in range(max(1, (Gp - 1).bit_length()))]
    g = jax.lax.broadcasted_iota(jnp.int32, (M, Gp), 1)
    ok = g >= s
    if lo is not None:
        ok &= (g >= lo) | (s == 0)
    return stages, ok, jnp.broadcast_to(e_col, (M, Gp))


def _band_candidates(row, setup, M, Gp):
    """row: [1, Gp] one source node's distances -> [M, Gp] candidates
    row[g - st[m]] + E[m] where admissible, BIG elsewhere."""
    stages, ok, e = setup
    x = jnp.broadcast_to(row, (M, Gp))
    for shift, bit in stages:
        x = jnp.where(bit, pltpu.roll(x, shift, 1), x)
    return jnp.where(ok, x + e, BIG)


def _fold_min(cands):
    """First-occurrence (min, argmin) over a list of [M, Gp] candidates."""
    best = cands[0]
    arg = jnp.zeros(best.shape, jnp.int32)
    for i in range(1, len(cands)):
        better = cands[i] < best
        best = jnp.where(better, cands[i], best)
        arg = jnp.where(better, i, arg)
    return best, arg


def _banded_minplus_kernel(lo, N, dist_ref, e_ref, st_ref, out_ref, arg_ref):
    """One banded layer step for a block of target nodes.

    dist_ref: [Np, Gp] previous-layer distances; e_ref/st_ref: [Np, bm, 1]
    the energy / integer-steepness columns of the target block; out/arg:
    [bm, Gp].  ``N`` (static) is the real source-node count — padded
    sources only ever carry BIG and cannot win a min.  ``lo`` (static) is
    the lambda window bound, or None when inactive.
    """
    d = dist_ref[...]
    bm, Gp = out_ref.shape
    cands = [_band_candidates(d[n:n + 1, :],
                              _band_setup(st_ref[n], e_ref[n], lo, bm, Gp),
                              bm, Gp)
             for n in range(N)]
    out_ref[...], arg_ref[...] = _fold_min(cands)


def _banded_chain_kernel(lo, L, N, dist_ref, e_ref, st_ref, hist_ref,
                         arg_ref):
    """Chained banded relaxation: ALL layers of one scenario per launch.

    dist_ref: [1, Np, Gp] the scenario's init grid; e_ref/st_ref:
    [1, L, Np, Np, 1]; hist/arg: [1, L, Np, Gp].  The distance grid is
    carried across layers in VMEM (``d`` below) instead of round-tripping
    through HBM between per-layer launches — the population engine's churn
    ticks relax thousands of short chains, where the per-launch overhead of
    the layer-by-layer kernel dominates.  The layer loop is a static unroll
    (L is a trace-time constant), so every e/st/hist index is static.
    """
    d = dist_ref[0]                                      # [Np, Gp]
    Np, Gp = d.shape
    for l in range(L):
        cands = [_band_candidates(
                     d[n:n + 1, :],
                     _band_setup(st_ref[0, l, n], e_ref[0, l, n], lo, Np, Gp),
                     Np, Gp)
                 for n in range(N)]
        d, arg = _fold_min(cands)                        # [tgt, Gp]
        hist_ref[0, l] = d
        arg_ref[0, l] = arg


def _banded_chain_kbest_kernel(lo, L, K, N, dist_ref, e_ref, st_ref,
                               hist_ref, pn_ref, pk_ref):
    """Chained banded k-slot relaxation: ALL layers of one scenario.

    dist_ref: [1, Np, Gp] the scenario's init grid (slot 0; the other
    slots start BIG); e_ref/st_ref: [1, L, Np, Np, 1]; hist/pn/pk:
    [1, L, K, Np, Gp].  The K slot grids are carried across layers in VMEM
    like ``_banded_chain_kernel``'s scalar grid.  Per layer the candidate
    pool per target state is (source node, source rank) in node-major
    rank-minor order; the K cheapest are extracted by iterated
    first-occurrence argmin + mask — the same selection order as a stable
    ascending argsort, hence the same slot order as the numpy engine
    (``bellman_ford.batched_banded_relax_kbest``).
    """
    d0 = dist_ref[0]                                     # [Np, Gp]
    Np, Gp = d0.shape
    slots = [d0] + [jnp.full((Np, Gp), BIG, jnp.float32)] * (K - 1)
    for l in range(L):
        pool, pool_n, pool_k = [], [], []
        for n in range(N):
            setup = _band_setup(st_ref[0, l, n], e_ref[0, l, n], lo, Np, Gp)
            for k in range(K):
                pool.append(_band_candidates(slots[k][n:n + 1, :], setup,
                                             Np, Gp))
                pool_n.append(n)
                pool_k.append(k)
        slots = []
        for j in range(K):
            best, a = _fold_min(pool)
            pn = jnp.zeros(a.shape, jnp.int32)
            pk = jnp.zeros(a.shape, jnp.int32)
            for i in range(1, len(pool)):
                hit = a == i
                pn = jnp.where(hit, pool_n[i], pn)
                pk = jnp.where(hit, pool_k[i], pk)
            hist_ref[0, l, j] = best
            pn_ref[0, l, j] = pn
            pk_ref[0, l, j] = pk
            slots.append(best)
            if j + 1 < K:
                pool = [jnp.where(a == i, BIG, c) for i, c in enumerate(pool)]


def _banded_inputs(dist, E, st):
    """Sanitize + pad the banded kernels' inputs: depths lane-padded,
    nodes sublane-padded; padded source rows carry BIG distances / BIG
    energies so they never win a min, and padded depth lanes are never
    read by a real target depth (g - st <= g)."""
    dist = jnp.where(jnp.isfinite(dist), dist, BIG).astype(jnp.float32)
    E = jnp.where(jnp.isfinite(E), E, BIG).astype(jnp.float32)
    dist_p = _pad_to(_pad_to(dist, 128, -1, BIG), 8, -2, BIG)
    return dist_p, E, st.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("K", "lo", "interpret"))
def banded_minplus_chain_kbest_pallas(dist: jnp.ndarray, E: jnp.ndarray,
                                      st: jnp.ndarray, K: int, *, lo=None,
                                      interpret=None):
    """Chained banded k-best relaxation, batched over scenarios.

    dist: [B, N, G+1] init grids; E: [B, L, N, N] (inf = pruned); st:
    [B, L, N, N] int steepness; K >= 1 slots per state.  Returns (hist
    [B, L, N, G+1, K] float32 — the k-slot grid AFTER each layer — and
    par_n / par_k [B, L, N, G+1, K] int32, -1 where the slot is unused).
    One launch per scenario relaxes the whole layer chain with the k-slot
    grid resident in VMEM; slot order equals the numpy k-best engine's
    stable-argsort order (see ``_banded_chain_kbest_kernel``).
    """
    assert K >= 1
    B, N, Gp1 = dist.shape
    L = E.shape[1]
    dist_p, E, st = _banded_inputs(dist, E, st)
    Np, Gp = dist_p.shape[1:]
    E_p = _pad_to(_pad_to(E, 8, 2, BIG), 8, 3, BIG)[..., None]
    st_p = _pad_to(_pad_to(st, 8, 2, 0), 8, 3, 0)[..., None]

    out_spec = pl.BlockSpec((1, L, K, Np, Gp), lambda b: (b, 0, 0, 0, 0))
    hist, pn, pk = pl.pallas_call(
        functools.partial(_banded_chain_kbest_kernel, lo, L, K, N),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Np, Gp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, L, Np, Np, 1), lambda b: (b, 0, 0, 0, 0)),
            pl.BlockSpec((1, L, Np, Np, 1), lambda b: (b, 0, 0, 0, 0)),
        ],
        out_specs=(out_spec, out_spec, out_spec),
        out_shape=(jax.ShapeDtypeStruct((B, L, K, Np, Gp), jnp.float32),
                   jax.ShapeDtypeStruct((B, L, K, Np, Gp), jnp.int32),
                   jax.ShapeDtypeStruct((B, L, K, Np, Gp), jnp.int32)),
        interpret=interpret_mode(interpret),
    )(dist_p, E_p, st_p)
    unreached = hist >= BIG
    hist = jnp.where(unreached, jnp.inf, hist)
    pn = jnp.where(unreached, -1, pn)
    pk = jnp.where(unreached, -1, pk)
    # [B, L, K, N, Gp1] -> [B, L, N, Gp1, K]
    return tuple(jnp.moveaxis(x[:, :, :, :N, :Gp1], 2, 4)
                 for x in (hist, pn, pk))


@functools.partial(jax.jit, static_argnames=("lo", "interpret"))
def banded_minplus_chain_pallas(dist: jnp.ndarray, E: jnp.ndarray,
                                st: jnp.ndarray, *, lo=None,
                                interpret=None):
    """Chained banded relaxation with argmin carry, batched over scenarios.

    dist: [B, N, G+1] init grids; E: [B, L, N, N] (inf = pruned); st:
    [B, L, N, N] int steepness.  Returns (hist [B, L, N, G+1] float32 —
    the distance grid AFTER each layer — and argmin source node
    [B, L, N, G+1] int32, -1 where unreachable).  One kernel launch per
    scenario relaxes its whole layer chain with the distance grid resident
    in VMEM (see ``_banded_chain_kernel``); the grid axis is the scenario
    batch, so a population tick's dirty cohort rides in one pallas_call.
    """
    B, N, Gp1 = dist.shape
    L = E.shape[1]
    dist_p, E, st = _banded_inputs(dist, E, st)
    Np, Gp = dist_p.shape[1:]
    E_p = _pad_to(_pad_to(E, 8, 2, BIG), 8, 3, BIG)[..., None]
    st_p = _pad_to(_pad_to(st, 8, 2, 0), 8, 3, 0)[..., None]

    hist, arg = pl.pallas_call(
        functools.partial(_banded_chain_kernel, lo, L, N),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Np, Gp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, L, Np, Np, 1), lambda b: (b, 0, 0, 0, 0)),
            pl.BlockSpec((1, L, Np, Np, 1), lambda b: (b, 0, 0, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, L, Np, Gp), lambda b: (b, 0, 0, 0)),
                   pl.BlockSpec((1, L, Np, Gp), lambda b: (b, 0, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct((B, L, Np, Gp), jnp.float32),
                   jax.ShapeDtypeStruct((B, L, Np, Gp), jnp.int32)),
        interpret=interpret_mode(interpret),
    )(dist_p, E_p, st_p)
    unreached = hist >= BIG
    hist = jnp.where(unreached, jnp.inf, hist)
    arg = jnp.where(unreached, -1, arg)
    return hist[:, :, :N, :Gp1], arg[:, :, :N, :Gp1]


@functools.partial(jax.jit, static_argnames=("lo", "bm", "interpret"))
def banded_minplus_pallas(dist: jnp.ndarray, E: jnp.ndarray, st: jnp.ndarray,
                          *, lo=None, bm: int = 8, interpret=None):
    """One banded relaxation layer over the compact (node, depth) grid.

    dist: [N, G+1] float; E: [N, N] float (inf = pruned edge); st: [N, N]
    int32 steepness (ignored where E is inf).  Returns (out [N, G+1], argmin
    source node [N, G+1] int32, -1 unreachable):

        out[m, g] = min_n dist[n, g - st[n, m]] + E[n, m]

    The depth axis (G+1 lanes) and node axes (sublanes) are padded to tile
    multiples; each grid step handles one block of ``bm`` target nodes with
    the full source grid resident in VMEM — O(N^2 G) work where the dense
    ``minplus_pallas`` on scattered (S, S) matrices pays O(N^2 G^2).
    """
    N, Gp1 = dist.shape
    dist_p, E, st = _banded_inputs(dist, E, st)
    Np, Gp = dist_p.shape
    E_p = _pad_to(_pad_to(E, 8, 0, BIG), bm, 1, BIG)[..., None]
    st_p = _pad_to(_pad_to(st, 8, 0, 0), bm, 1, 0)[..., None]
    Mp = E_p.shape[1]

    out, arg = pl.pallas_call(
        functools.partial(_banded_minplus_kernel, lo, N),
        grid=(Mp // bm,),
        in_specs=[
            pl.BlockSpec((Np, Gp), lambda j: (0, 0)),
            pl.BlockSpec((Np, bm, 1), lambda j: (0, j, 0)),
            pl.BlockSpec((Np, bm, 1), lambda j: (0, j, 0)),
        ],
        out_specs=(pl.BlockSpec((bm, Gp), lambda j: (j, 0)),
                   pl.BlockSpec((bm, Gp), lambda j: (j, 0))),
        out_shape=(jax.ShapeDtypeStruct((Mp, Gp), jnp.float32),
                   jax.ShapeDtypeStruct((Mp, Gp), jnp.int32)),
        interpret=interpret_mode(interpret),
    )(dist_p, E_p, st_p)
    unreached = out >= BIG
    out = jnp.where(unreached, jnp.inf, out)
    arg = jnp.where(unreached, -1, arg)
    return out[:N, :Gp1], arg[:N, :Gp1]
