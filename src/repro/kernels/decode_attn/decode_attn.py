"""Pallas TPU kernel: flash-decode GQA attention, read in place from the
stacked decode cache.

One new query token per batch row attends over that row's KV cache, read
straight from the stack that rides in the decode scan's carry:

  q: [B, H, D]; k/v: [L, B, T, KV, D]; cache_pos: [L, B, T] (absolute
  position held in each entry, -1 = empty); layer: which of the L slabs;
  pos: [B], the position each row decodes.

Nothing of K/V is sliced out of the stack: ``layer`` and ``pos`` arrive by
scalar prefetch and the index maps pick block ``(layer, b, t)`` directly.
Grid (B, T/bt) with the T axis minor; each step one block of ``bt``
positions with all KV heads, in the ``[.., T, KV, D]`` layout one
contiguous run, seen as ``[bt * KV, D]`` (row ``i * KV + h``: position i,
head h; a free reshape).  One score matmul ``q [H, D] @ block^T`` covers
every query head against every row; a query head keeps the rows of its own
KV head (the rest masked), so one AV matmul ``p @ block`` yields every
head's output.  The MXU reads each block once either way; per-head slices
of a block would cost a relayout of every row.  Running (max, sum,
weighted-V accumulator) per query head are float32 VMEM scratch; the
matmuls take the cache's own dtype and accumulate in float32.

Depth bound.  The cache is a ring: entry i holds a position congruent to
i modulo T (or -1).  So while ``pos[b] < T`` no entry past ``pos[b]`` can
be valid, and row b reads only blocks ``t <= last[b] = min(pos[b], T-1)
// bt``: the index maps clamp the block index there (a repeated index
fetches nothing) and ``pl.when`` skips the compute.  Once a ring has
wrapped, every block is read.  The mask is exactly ``decode_attention``'s:
entry valid iff ``0 <= cache_pos <= pos[b]``, and ``> pos[b] - window``
under a sliding window; the layer's positions enter repeated once per KV
head (``[B, T * KV]``, small beside K/V), in the block's row order.

The pure-jnp oracle is models/attention.decode_attention (re-exported in
ref.py), which the decode step keeps for quantized caches and partitioned
programs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import interpret_mode

NEG = -3.0e38         # python float: kernels must not capture traced constants
#: positions per K/V block of a cache longer than one block
BLOCK_T = 512


def block_len(T: int, block_t: int = BLOCK_T) -> int:
    """Positions per block over a cache of ``T`` entries: the whole cache
    when it fits one block, else ``block_t`` (the last block ragged when it
    does not divide ``T``)."""
    return T if T <= block_t else block_t


def blocks_read(pos, T: int, bt: int):
    """Blocks of ``bt`` positions the kernel reads for rows at ``pos``
    (numpy or jnp, any shape): ``min(pos, T-1) // bt + 1`` each."""
    return (pos.clip(0, T - 1) // bt) + 1


def _decode_attn_kernel(window, bt, T, KV, layer_ref, pos_ref, q_ref, k_ref,
                        v_ref, cpos_ref, o_ref, m_ref, l_ref, acc_ref):
    del layer_ref                             # used by the index maps only
    b, t = pl.program_id(0), pl.program_id(1)
    H, D = q_ref.shape[1:]
    G, n = H // KV, bt * KV
    pos = pos_ref[b]

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t <= jnp.clip(pos, 0, T - 1) // bt)
    def _block():
        cpos = cpos_ref[pl.ds(b, 1), :]               # [1, n]
        ok = (cpos >= 0) & (cpos <= pos)
        if window > 0:
            ok &= cpos > pos - window
        # query head r reads the rows of KV head r // G
        ok &= (jax.lax.broadcasted_iota(jnp.int32, (H, n), 1) % KV ==
               jax.lax.broadcasted_iota(jnp.int32, (H, n), 0) // G)
        k, v = k_ref[0, 0], v_ref[0, 0]               # [n, D]
        if T % bt:        # a ragged last block: its rows past T are garbage
            left = (T - t * bt) * KV
            ok &= jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) < left
            v = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) <
                          left, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * D ** -0.5   # [H, n]
        s = jnp.where(ok, s, NEG)
        m_old = m_ref[...]                            # [H, 1]
        m_new = jnp.maximum(m_old, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_old - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attn_pallas(q, k, v, cache_pos, layer, pos, *, window: int = 0,
                       bt: int = BLOCK_T, interpret=None) -> jnp.ndarray:
    """q: [B, H, D]; k/v: [L, B, T, KV, D]; cache_pos: [L, B, T] i32; layer:
    scalar i32; pos: [B] i32.  Returns [B, H, D] (q's dtype): attention of
    each row's query over slab ``layer`` of the stack, read in place."""
    B, H, D = q.shape
    L, _, T, KV, _ = k.shape
    assert H % KV == 0
    bt = block_len(T, bt)
    n = bt * KV
    # one row per (position, KV head): the cache's own bytes
    k, v = (a.reshape(L, B, T * KV, D) for a in (k, v))
    cpos = jnp.repeat(jax.lax.dynamic_index_in_dim(cache_pos, layer, 0,
                                                   keepdims=False), KV, -1)

    def block(b, t, pos_ref):          # clamped to row b's last block
        return jnp.minimum(t, jnp.clip(pos_ref[b], 0, T - 1) // bt)

    def kv_block(b, t, layer_ref, pos_ref):
        return layer_ref[0], b, block(b, t, pos_ref), 0

    def pos_block(b, t, layer_ref, pos_ref):
        return 0, block(b, t, pos_ref)

    def row(b, t, layer_ref, pos_ref):
        return b, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pl.cdiv(T, bt)),
        in_specs=[pl.BlockSpec((1, H, D), row),
                  pl.BlockSpec((1, 1, n, D), kv_block),
                  pl.BlockSpec((1, 1, n, D), kv_block),
                  # every row's positions of the block: a row alone is no
                  # tile; the kernel picks row b
                  pl.BlockSpec((B, n), pos_block)],
        out_specs=pl.BlockSpec((1, H, D), row),
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, D), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_decode_attn_kernel, window, bt, T, KV),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
        name="decode_attn",      # the kernel's name in a device trace
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.broadcast_to(pos, (B,)).astype(jnp.int32), q, k, v, cpos)
