"""Jitted wrapper for the decode_attn Pallas kernel."""
from __future__ import annotations

import jax.numpy as jnp

from .decode_attn import BLOCK_T, decode_attn_pallas


def decode_attn(q, k_cache, v_cache, cache_pos, layer, pos, *,
                window: int = 0, block_t: int = BLOCK_T) -> jnp.ndarray:
    """Flash-decode GQA attention over slab ``layer`` of a stacked cache,
    each row to its own depth.

    q: [B, H, D]; k/v: [L, B, T, KV, D]; cache_pos: [L, B, T] i32; layer:
    scalar i32; pos: [B] i32 (a scalar is shared by every row).
    """
    return decode_attn_pallas(q, k_cache, v_cache, cache_pos, layer, pos,
                              window=window, bt=block_t)
