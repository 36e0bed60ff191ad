"""Pallas kernel tests: shape/dtype sweeps vs pure-jnp oracles (interpret)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attn.ops import decode_attn
from repro.kernels.decode_attn.ref import decode_attn_ref
from repro.kernels.ee_gate.ops import ee_gate
from repro.kernels.ee_gate.ref import ee_gate_ref
from repro.kernels.minplus.ops import (banded_minplus_argmin, minplus_matmat,
                                       minplus_vecmat, minplus_vecmat_argmin)
from repro.kernels.minplus.ref import (banded_minplus_ref, minplus_argmin_ref,
                                       minplus_matmat_ref, minplus_ref)


# ---------------------------------------------------------------------------
# minplus
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,T", [(1, 16, 16), (8, 128, 128), (3, 37, 65),
                                   (16, 300, 129), (2, 1, 257)])
@pytest.mark.parametrize("density", [1.0, 0.4])
def test_minplus_sweep(B, S, T, density):
    rng = np.random.default_rng(B * 1000 + S + T)
    dist = rng.uniform(0, 10, (B, S)).astype(np.float32)
    W = rng.uniform(0, 5, (S, T)).astype(np.float32)
    W[rng.uniform(size=W.shape) > density] = np.inf
    dist[rng.uniform(size=dist.shape) > 0.9] = np.inf
    got = np.asarray(minplus_vecmat(jnp.asarray(dist), jnp.asarray(W)))
    want = np.asarray(minplus_ref(jnp.asarray(dist), jnp.asarray(W)))
    finite = np.isfinite(want)
    assert (np.isfinite(got) == finite).all()
    np.testing.assert_allclose(got[finite], want[finite], rtol=1e-6)


def test_minplus_identity():
    S = 64
    ident = np.full((S, S), np.inf, np.float32)
    np.fill_diagonal(ident, 0.0)
    d = np.random.default_rng(0).uniform(0, 3, (4, S)).astype(np.float32)
    got = np.asarray(minplus_vecmat(jnp.asarray(d), jnp.asarray(ident)))
    np.testing.assert_allclose(got, d, rtol=1e-6)


@pytest.mark.parametrize("B,S,T", [(1, 16, 16), (8, 128, 128), (3, 37, 65),
                                   (2, 1, 257)])
@pytest.mark.parametrize("density", [1.0, 0.4])
def test_minplus_argmin_sweep(B, S, T, density):
    rng = np.random.default_rng(B * 999 + S + T)
    dist = rng.uniform(0, 10, (B, S)).astype(np.float32)
    W = rng.uniform(0, 5, (S, T)).astype(np.float32)
    W[rng.uniform(size=W.shape) > density] = np.inf
    dist[rng.uniform(size=dist.shape) > 0.9] = np.inf
    got, arg = minplus_vecmat_argmin(jnp.asarray(dist), jnp.asarray(W))
    want, arg_r = minplus_argmin_ref(jnp.asarray(dist), jnp.asarray(W))
    got, arg = np.asarray(got), np.asarray(arg)
    want, arg_r = np.asarray(want), np.asarray(arg_r)
    finite = np.isfinite(want)
    assert (np.isfinite(got) == finite).all()
    np.testing.assert_allclose(got[finite], want[finite], rtol=1e-6)
    assert (arg[~finite] == -1).all()
    # the reported parent reproduces the min exactly (ties may differ from
    # the oracle's argmin only between equal-valued sources)
    b, t = np.nonzero(finite)
    np.testing.assert_allclose(dist[b, arg[b, t]] + W[arg[b, t], t],
                               got[finite], rtol=1e-6)
    np.testing.assert_array_equal(arg, arg_r)


def test_minplus_matmat_is_tropical_matmul():
    rng = np.random.default_rng(7)
    A = rng.uniform(0, 5, (17, 33)).astype(np.float32)
    B = rng.uniform(0, 5, (33, 21)).astype(np.float32)
    B[rng.uniform(size=B.shape) < 0.3] = np.inf
    got = np.asarray(minplus_matmat(jnp.asarray(A), jnp.asarray(B)))
    want = np.asarray(minplus_matmat_ref(jnp.asarray(A), jnp.asarray(B)))
    finite = np.isfinite(want)
    assert (np.isfinite(got) == finite).all()
    np.testing.assert_allclose(got[finite], want[finite], rtol=1e-6)
    # associativity on a chain: (A*B)*C == A*(B*C) in the tropical semiring
    C = rng.uniform(0, 5, (21, 9)).astype(np.float32)
    left = minplus_matmat(minplus_matmat(jnp.asarray(A), jnp.asarray(B)),
                          jnp.asarray(C))
    right = minplus_matmat(jnp.asarray(A),
                           np.asarray(minplus_matmat(jnp.asarray(B),
                                                     jnp.asarray(C))))
    l, r = np.asarray(left), np.asarray(right)
    m = np.isfinite(l)
    np.testing.assert_allclose(l[m], r[m], rtol=1e-5)


@pytest.mark.parametrize("N,G", [(4, 3), (16, 10), (23, 25), (8, 130)])
@pytest.mark.parametrize("lo", [None, 5])
def test_banded_minplus_sweep(N, G, lo):
    """Banded kernel vs its jnp oracle across shapes and lambda windows."""
    rng = np.random.default_rng(N * 100 + G)
    dist = rng.uniform(0, 10, (N, G + 1)).astype(np.float32)
    dist[rng.uniform(size=dist.shape) < 0.4] = np.inf
    E = rng.uniform(0, 5, (N, N)).astype(np.float32)
    E[rng.uniform(size=E.shape) < 0.3] = np.inf
    st = rng.integers(0, G + 1, (N, N)).astype(np.int32)
    args = (jnp.asarray(dist), jnp.asarray(E), jnp.asarray(st))
    got, arg = banded_minplus_argmin(*args, lo=lo)
    want, arg_r = banded_minplus_ref(*args, lo=lo)
    got, arg = np.asarray(got), np.asarray(arg)
    want, arg_r = np.asarray(want), np.asarray(arg_r)
    finite = np.isfinite(want)
    assert (np.isfinite(got) == finite).all()
    np.testing.assert_allclose(got[finite], want[finite], rtol=1e-6)
    assert (arg[~finite] == -1).all()
    np.testing.assert_array_equal(arg, arg_r)


@pytest.mark.parametrize("B,L,N,G", [(1, 1, 4, 3), (3, 4, 7, 10),
                                     (5, 2, 9, 25)])
@pytest.mark.parametrize("lo", [None, 2])
def test_banded_minplus_chain_matches_per_layer(B, L, N, G, lo):
    """The chained argmin-carrying kernel (one launch per scenario, whole
    layer chain in VMEM) must reproduce the per-layer kernel exactly —
    same distances, same first-occurrence argmin tie order."""
    from repro.kernels.minplus.ops import banded_minplus_chain

    rng = np.random.default_rng(B * 1000 + L * 100 + N * 10 + G)
    dist = rng.uniform(0, 10, (B, N, G + 1)).astype(np.float32)
    dist[rng.uniform(size=dist.shape) < 0.5] = np.inf
    E = rng.uniform(0, 5, (B, L, N, N)).astype(np.float32)
    E[rng.uniform(size=E.shape) < 0.3] = np.inf
    st = rng.integers(0, G + 1, (B, L, N, N)).astype(np.int32)
    hist, par = banded_minplus_chain(jnp.asarray(dist), jnp.asarray(E),
                                     jnp.asarray(st), lo=lo)
    hist, par = np.asarray(hist), np.asarray(par)
    for b in range(B):
        d = jnp.asarray(dist[b])
        for l in range(L):
            want, arg = banded_minplus_argmin(d, jnp.asarray(E[b, l]),
                                              jnp.asarray(st[b, l]), lo=lo)
            np.testing.assert_array_equal(hist[b, l], np.asarray(want),
                                          err_msg=f"b={b} l={l}")
            np.testing.assert_array_equal(par[b, l], np.asarray(arg))
            d = want


def test_banded_minplus_equals_scattered_dense():
    """The banded kernel on (E, steep) equals the dense kernel on the
    scattered (S, S) matrix of the same feasible-graph layer."""
    from repro.core import (AppRequirements, build_extended_graph,
                            build_feasible_graph, paper_profile)
    from repro.core.scenarios import paper_scenario

    nw = paper_scenario()
    prof = paper_profile("h2")
    ext = build_extended_graph(nw, prof, AppRequirements(0.8, 5e-3))
    fg = build_feasible_graph(ext, gamma=10)
    N, G = ext.n_nodes, fg.gamma
    E, st = fg.banded_tensors()
    dist = fg.init_grid()
    W = fg.layer_matrices()[0]
    sti = np.where(np.isfinite(st[0]), st[0], 0).astype(np.int32)
    got, _ = banded_minplus_argmin(
        jnp.asarray(dist, jnp.float32),
        jnp.asarray(np.where(np.isfinite(st[0]), E[0], np.inf), jnp.float32),
        jnp.asarray(sti))
    want = np.asarray(minplus_vecmat(
        jnp.asarray(dist.reshape(1, -1), jnp.float32),
        jnp.asarray(W, jnp.float32))).reshape(N, G + 1)
    m = np.isfinite(want)
    assert (np.isfinite(np.asarray(got)) == m).all()
    np.testing.assert_allclose(np.asarray(got)[m], want[m], rtol=1e-6)


def test_minplus_backs_fin_dp():
    """The kernel reproduces the FIN layered relaxation end-to-end."""
    from repro.core import (AppRequirements, build_extended_graph,
                            build_feasible_graph, paper_profile)
    from repro.core.bellman_ford import layered_relax
    from repro.core.scenarios import paper_scenario

    nw = paper_scenario()
    prof = paper_profile("h2")
    ext = build_extended_graph(nw, prof, AppRequirements(0.8, 5e-3))
    fg = build_feasible_graph(ext, gamma=10)
    Ws = fg.layer_matrices()
    init = fg.init_vector()
    want = layered_relax(init, Ws, backend="numpy")
    got = layered_relax(init, Ws, backend="pallas")
    mask = np.isfinite(want)
    assert (np.isfinite(got) == mask).all()
    np.testing.assert_allclose(got[mask], want[mask], rtol=1e-5)


# ---------------------------------------------------------------------------
# ee_gate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,V", [(1, 128), (8, 2048), (5, 5000), (16, 50304),
                                 (2, 131)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ee_gate_sweep(B, V, dtype):
    key = jax.random.PRNGKey(B + V)
    logits = (jax.random.normal(key, (B, V), jnp.float32) * 4).astype(dtype)
    conf, arg = ee_gate(logits)
    conf_r, arg_r = ee_gate_ref(logits)
    np.testing.assert_allclose(np.asarray(conf), np.asarray(conf_r),
                               rtol=2e-3)
    np.testing.assert_array_equal(np.asarray(arg), np.asarray(arg_r))
    assert (np.asarray(conf) > 0).all() and (np.asarray(conf) <= 1.0).all()


def test_ee_gate_handles_padded_vocab():
    """-inf padded tail (masked vocab) must not poison the reduction."""
    B, V = 4, 1000
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (B, V), jnp.float32)
    padded = jnp.concatenate(
        [logits, jnp.full((B, 24), -jnp.inf)], axis=1)
    conf, arg = ee_gate(padded)
    conf_r, arg_r = ee_gate_ref(logits)
    np.testing.assert_allclose(np.asarray(conf), np.asarray(conf_r),
                               rtol=2e-3)
    np.testing.assert_array_equal(np.asarray(arg), np.asarray(arg_r))


def test_ee_gate_peaked_distribution():
    """A very confident head must yield conf ~ 1 at the right token."""
    logits = jnp.full((2, 512), -5.0).at[:, 77].set(20.0)
    conf, arg = ee_gate(logits)
    assert (np.asarray(arg) == 77).all()
    assert (np.asarray(conf) > 0.999).all()


# ---------------------------------------------------------------------------
# decode_attn
# ---------------------------------------------------------------------------

def _rows(x, B):
    """A position shared by every row, as the kernel takes it: per row."""
    return jnp.broadcast_to(jnp.asarray(x, jnp.int32), (B,) + jnp.shape(x))


@pytest.mark.parametrize("B,H,KV,D,T,bt", [
    (1, 4, 4, 32, 128, 64),       # MHA
    (2, 8, 2, 64, 256, 128),      # GQA 4:1
    (1, 8, 1, 64, 300, 128),      # MQA, ragged T
    (3, 4, 2, 16, 64, 64),        # single block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attn_sweep(B, H, KV, D, T, bt, dtype):
    key = jax.random.PRNGKey(B + H + T)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (1, B, T, KV, D), dtype)
    v = jax.random.normal(ks[2], (1, B, T, KV, D), dtype)
    cache_pos = _rows(jnp.arange(T, dtype=jnp.int32), B)[None]
    pos = _rows(T - 3, B)    # last slots masked (future)
    got = decode_attn(q, k, v, cache_pos, 0, pos, block_t=bt)
    want = decode_attn_ref(q, k, v, cache_pos, 0, pos)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_decode_attn_sliding_window():
    B, H, KV, D, T = 1, 4, 2, 32, 256
    key = jax.random.PRNGKey(9)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (1, B, T, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (1, B, T, KV, D), jnp.float32)
    cache_pos = _rows(jnp.arange(T, dtype=jnp.int32), B)[None]
    pos = _rows(T - 1, B)
    for w in (16, 64):
        got = decode_attn(q, k, v, cache_pos, 0, pos, window=w, block_t=64)
        want = decode_attn_ref(q, k, v, cache_pos, 0, pos, window=w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_decode_attn_empty_slots_masked():
    """Slots with cache_pos = -1 (unwritten ring entries) contribute nothing."""
    B, H, KV, D, T = 1, 2, 2, 16, 64
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (1, B, T, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (1, B, T, KV, D), jnp.float32)
    cache_pos = _rows(jnp.where(jnp.arange(T) < 10, jnp.arange(T), -1), B
                      )[None]
    pos = _rows(9, B)
    got = decode_attn(q, k, v, cache_pos, 0, pos, block_t=32)
    want = decode_attn_ref(q, k[:, :, :10], v[:, :, :10],
                           cache_pos[:, :, :10], 0, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


#: (rows, layers, layer, T, window): each row's position, cache length
#: and window; block length 64 throughout
_STACKS = {
    # one row at each edge of a block, and one at the cache's end
    "depths": ([0, 63, 64, 65, 255], 2, 1, 256, 0),
    # a re-admitted slot: stale K/V past its depth, marked -1
    "stale": ([40, 130, 7], 2, 0, 256, 0),
    # a sliding-window ring that has wrapped (pos >= T) beside one that
    # has not
    "wrapped_ring": ([200, 127, 30, 64], 1, 0, 128, 128),
    # the layer index picks its own slab of the stack
    "layer": ([100, 5], 3, 2, 128, 0),
}


@pytest.mark.parametrize("case", sorted(_STACKS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attn_reads_the_stack_in_place(case, dtype):
    """Per-row depths over a stacked [L, B, T, KV, D] cache equal
    ``decode_attention`` on the layer's slab, whatever lies past each
    row's depth in the blocks the kernel skips."""
    rows, L, layer, T, window = _STACKS[case]
    B, H, KV, D = len(rows), 8, 2, 32
    pos = jnp.asarray(rows, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (L, B, T, KV, D), dtype)
    v = jax.random.normal(ks[2], (L, B, T, KV, D), dtype)
    t = jnp.arange(T, dtype=jnp.int32)
    # the ring's layout: entry i holds the latest position congruent to i
    # that the row has reached, -1 where none has been written
    held = pos[:, None] - (pos[:, None] - t[None]) % T
    cache_pos = jnp.broadcast_to(jnp.where(held >= 0, held, -1), (L, B, T))
    if case == "stale":
        gone = t[None] > pos[:, None]
        cache_pos = jnp.where(gone, -1, cache_pos)
        k = jnp.where(gone[None, :, :, None, None], 1e3, k).astype(dtype)
        v = jnp.where(gone[None, :, :, None, None], -1e3, v).astype(dtype)
    got = decode_attn(q, k, v, cache_pos, jnp.int32(layer), pos,
                      window=window, block_t=64)
    want = decode_attn_ref(q, k, v, cache_pos, jnp.int32(layer), pos,
                           window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    if case == "layer":
        other = decode_attn_ref(q, k, v, cache_pos, 0, pos)
        assert not np.allclose(np.asarray(got, np.float32),
                               np.asarray(other, np.float32), atol=tol)
