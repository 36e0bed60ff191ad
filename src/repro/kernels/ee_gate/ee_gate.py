"""Pallas TPU kernel: fused early-exit confidence gate.

Computes, per row of a logits matrix [B, V]:
  conf[b]   = max softmax probability = exp(max - logsumexp)
  argmax[b] = the arg max (the greedy token if the sample exits here)

without materializing softmax over the (padded, possibly 256k-wide) vocab.
This is the per-token gating statistic of the paper's early-exit execution
(Sec. II: early exits "capture" samples) on the decode hot path — one fused
reduction instead of softmax + max + argmax passes over HBM.

Tiling: grid (B/bb, V/bv); V minor.  Scratch carries the running max, the
running sum of exponentials (rescaled flash-style on max updates), and the
running argmax, all [bb, 1] VMEM columns; the outputs are [B, 1] columns
too, so every block is (8k, 1)-shaped and any batch size lowers (a rank-1
block must span its array or be a multiple of 128 lanes).  Compiled on TPU,
interpreted on CPU (``kernels/platform.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import interpret_mode

NEG = -3.0e38         # python float: kernels must not capture traced constants


def _ee_gate_kernel(logits_ref, conf_ref, arg_ref, m_ref, s_ref, a_ref):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        a_ref[...] = jnp.zeros_like(a_ref)

    x = logits_ref[...].astype(jnp.float32)            # [bb, bv]
    x = jnp.maximum(x, NEG)                            # -inf padding safe
    bv = x.shape[1]
    base = j * bv
    local_max = x.max(axis=1, keepdims=True)           # [bb, 1]
    local_arg = base + jnp.argmax(x, axis=1, keepdims=True).astype(jnp.int32)

    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, local_max)
    # rescale old sum, add this block's mass
    s_ref[...] = (s_ref[...] * jnp.exp(m_old - m_new)
                  + jnp.exp(x - m_new).sum(axis=1, keepdims=True))
    a_ref[...] = jnp.where(local_max > m_old, local_arg, a_ref[...])
    m_ref[...] = m_new

    @pl.when(j == nv - 1)
    def _finish():
        conf_ref[...] = 1.0 / s_ref[...]    # exp(max - lse) = 1/sum(exp(x-m))
        arg_ref[...] = a_ref[...]


@functools.partial(jax.jit, static_argnames=("bb", "bv", "interpret"))
def ee_gate_pallas(logits: jnp.ndarray, *, bb: int = 8, bv: int = 2048,
                   interpret=None):
    """logits: [B, V] (any float; -inf padding ok).
    Returns (conf [B] f32, argmax [B] i32)."""
    B, V = logits.shape
    Bp = ((B + bb - 1) // bb) * bb
    Vp = ((V + bv - 1) // bv) * bv
    x = logits
    if (Bp, Vp) != (B, V):
        x = jnp.pad(x, ((0, Bp - B), (0, Vp - V)),
                    constant_values=-jnp.inf)

    conf, arg = pl.pallas_call(
        _ee_gate_kernel,
        grid=(Bp // bb, Vp // bv),
        in_specs=[pl.BlockSpec((bb, bv), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((bb, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((Bp, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((bb, 1), jnp.float32),
                        pltpu.VMEM((bb, 1), jnp.float32),
                        pltpu.VMEM((bb, 1), jnp.int32)],
        interpret=interpret_mode(interpret),
    )(x)
    return conf[:B, 0], arg[:B, 0]
