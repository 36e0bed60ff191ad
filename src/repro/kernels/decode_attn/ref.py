"""Oracle for the decode_attn kernel: the decode step's own jnp path."""
import jax

from repro.models.attention import decode_attention


def decode_attn_ref(q, k_cache, v_cache, cache_pos, layer, pos, *,
                    window: int = 0):
    """q: [B, H, D] -> [B, H, D] via models.attention.decode_attention on
    slab ``layer`` of the [L, B, T, ...] stacks."""
    mine = [jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            for a in (k_cache, v_cache, cache_pos)]
    return decode_attention(q[:, None], *mine, pos, window=window)[:, 0]
