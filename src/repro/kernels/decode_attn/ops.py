"""Jitted wrapper for the decode_attn Pallas kernel."""
from __future__ import annotations

import jax.numpy as jnp

from .decode_attn import decode_attn_pallas


def decode_attn(q, k_cache, v_cache, cache_pos, pos, *, window: int = 0,
                block_t: int = 512) -> jnp.ndarray:
    """Flash-decode GQA attention over a KV cache.

    q: [B, H, D]; k/v: [B, T, KV, D]; cache_pos: [T] i32; pos: scalar i32.
    """
    return decode_attn_pallas(q, k_cache, v_cache, cache_pos, pos,
                              window=window, bt=block_t)
