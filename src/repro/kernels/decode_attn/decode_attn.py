"""Pallas TPU kernel: flash-decode GQA attention (split-K over the cache).

One new query token per sequence attends over a long KV cache:

  q: [B, H, D]; k/v cache: [B, T, KV, D]; cache_pos: [T] (absolute position
  per slot, -1 = empty); pos: current position (masking/SWA).

Tiling: grid (B, KV, T/bt) with the T axis minor — the classic
FlashDecoding split-K schedule.  Each step loads a [bt, D] K/V tile plus the
[G, D] query group into VMEM, computes [G, bt] scores on the MXU, and
maintains running (max, sum, weighted-V accumulator) in VMEM scratch.  This
is the hot loop of the serving path: at 32k context the cache read is the
roofline term, and the fused single pass reads K/V exactly once.

The pure-jnp oracle is models/attention.decode_attention (re-exported in
ref.py) — the same function the serving engine uses, so kernel == engine
semantics by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import interpret_mode

NEG = -3.0e38         # python float: kernels must not capture traced constants


def _decode_attn_kernel(window, q_ref, k_ref, v_ref, cpos_ref, pos_ref,
                        o_ref, m_ref, s_ref, acc_ref):
    t = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)       # [G, D]
    k = k_ref[0, :, 0].astype(jnp.float32)    # [bt, D]
    v = v_ref[0, :, 0].astype(jnp.float32)    # [bt, D]
    cpos = cpos_ref[...]                      # [bt]
    pos = pos_ref[0]

    scale = q.shape[-1] ** -0.5
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [G, bt]
    ok = (cpos >= 0) & (cpos <= pos)
    if window > 0:
        ok &= cpos > pos - window
    s = jnp.where(ok[None, :], s, NEG)

    m_old = m_ref[...]                        # [G]
    m_new = jnp.maximum(m_old, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_old - m_new)
    s_ref[...] = s_ref[...] * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(t == nt - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(s_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "bt", "interpret"))
def decode_attn_pallas(q, k_cache, v_cache, cache_pos, pos, *,
                       window: int = 0, bt: int = 512,
                       interpret=None) -> jnp.ndarray:
    """q: [B, H, D]; k/v: [B, T, KV, D]; cache_pos: [T] i32; pos scalar i32.
    Returns [B, H, D] (same dtype as q)."""
    B, H, D = q.shape
    T, KV = k_cache.shape[1], k_cache.shape[2]
    assert H % KV == 0
    G = H // KV
    if T % bt:
        pad = bt - T % bt
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cache_pos = jnp.pad(cache_pos, (0, pad), constant_values=-1)
        T += pad
    qg = q.reshape(B, KV, G, D)
    pos_arr = jnp.broadcast_to(pos, (1,)).astype(jnp.int32)

    out = pl.pallas_call(
        functools.partial(_decode_attn_kernel, window),
        grid=(B, KV, T // bt),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, t: (b, h, 0, 0)),
            pl.BlockSpec((1, bt, 1, D), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, bt, 1, D), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((bt,), lambda b, h, t: (t,)),
            pl.BlockSpec((1,), lambda b, h, t: (0,)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, t: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((G,), jnp.float32),
                        pltpu.VMEM((G,), jnp.float32),
                        pltpu.VMEM((G, D), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(qg, k_cache, v_cache, cache_pos, pos_arr)
    return out.reshape(B, H, D)
