"""Attention correctness: chunked online-softmax vs naive, SWA, GQA, decode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.models import attention as ATT
from repro.models.layers import F32


def naive_attention(q, k, v, q_pos, k_pos, causal, window):
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, D).astype(F32)
    s = jnp.einsum("bqkgd,bskd->bqkgs", qg, k.astype(F32)) * D ** -0.5
    ok = (k_pos[None, :] >= 0)
    ok = jnp.broadcast_to(ok, (Sq, k_pos.shape[0]))
    if causal:
        ok = ok & (k_pos[None, :] <= q_pos[:, None])
    if window > 0:
        ok = ok & (k_pos[None, :] > q_pos[:, None] - window)
    s = jnp.where(ok[None, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkgs,bskd->bqkgd", p, v.astype(F32))
    return out.reshape(B, Sq, H, D)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 7), (False, 0)])
@pytest.mark.parametrize("S,chunk", [(16, 4), (16, 16), (13, 4), (33, 8)])
def test_chunked_matches_naive(causal, window, S, chunk):
    key = jax.random.PRNGKey(0)
    B, H, KV, D = 2, 4, 2, 8
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), F32)
    k = jax.random.normal(ks[1], (B, S, KV, D), F32)
    v = jax.random.normal(ks[2], (B, S, KV, D), F32)
    pos = jnp.arange(S, dtype=jnp.int32)
    got = ATT.chunked_attention(q, k, v, pos, pos, causal=causal,
                                window=window, chunk=chunk)
    want = naive_attention(q, k, v, pos, pos, causal, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_matches_naive_gqa():
    key = jax.random.PRNGKey(1)
    B, H, KV, D, T = 2, 8, 2, 16, 24
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), F32)
    kc = jax.random.normal(ks[1], (B, T, KV, D), F32)
    vc = jax.random.normal(ks[2], (B, T, KV, D), F32)
    pos_arr = jnp.arange(T, dtype=jnp.int32)
    cur = jnp.int32(T - 5)
    got = ATT.decode_attention(q, kc, vc, pos_arr, cur, window=0)
    want = naive_attention(q, kc, vc, cur[None], pos_arr, True, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_swa_ring_buffer_decode():
    """SWA decode with a ring cache equals full-cache decode with a window."""
    cfg = dataclasses.replace(get("mixtral-8x22b", reduced=True),
                              sliding_window=8)
    key = jax.random.PRNGKey(2)
    params = ATT.attn_init(key, cfg, F32)
    B, S = 1, 20
    xs = jax.random.normal(key, (B, S, cfg.d_model), F32)
    # sequential ring-buffer decode
    ring = jax.tree.map(lambda a: a[None],
                        ATT.cache_spec(cfg, B, S).init(F32))  # one layer
    assert ring["k"].shape[2] == 8  # ring = window
    outs = []
    for t in range(S):
        y, ring = ATT.attn_decode_step(params, cfg, xs[:, t:t + 1],
                                       ring, jnp.int32(t), layer=0)
        outs.append(y)
    got = jnp.concatenate(outs, axis=1)
    # full-sequence chunked attention with the same window
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    want = ATT.attn_apply(params, cfg, xs, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_qk_norm_changes_output_but_stays_finite():
    cfg = get("qwen3-4b", reduced=True)
    assert cfg.qk_norm
    key = jax.random.PRNGKey(3)
    params = ATT.attn_init(key, cfg, F32)
    x = jax.random.normal(key, (2, 8, cfg.d_model), F32)
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))
    y = ATT.attn_apply(params, cfg, x, pos)
    assert bool(jnp.isfinite(y).all())
    cfg2 = dataclasses.replace(cfg, qk_norm=False)
    params2 = {k: v for k, v in params.items()
               if k not in ("q_norm", "k_norm")}
    y2 = ATT.attn_apply(params2, cfg2, x, pos)
    assert not np.allclose(np.asarray(y), np.asarray(y2))
