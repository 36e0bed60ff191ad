"""Operations and bytes of a dense decoder's serving work, from its shapes
(the configuration file's ``model`` block, Hugging Face keys): the work of
the algorithm in bf16, not what an implementation happens to read or pad.

A decode step reads the layer weights once, the tied table once for each
head (the final one and every exit's), the K/V entries each live slot
attends to (its position + 1, the new one included) and writes each live
slot's new entry.  An admission of an ``n``-token prompt reads the same
weights once, computes its prompt's tokens causally and writes their
entries.  FLOPs are the matmuls': 2 per weight per token, 4 * heads *
head_dim per attended position per layer, 2 * d_model * vocab per head
row.  Rows of empty slots and padded prompt positions are not counted."""
from __future__ import annotations

from typing import Tuple

#: bytes of a bf16 value
BF16 = 2


def _d(model: dict):
    return (int(model["num_hidden_layers"]), int(model["hidden_size"]),
            int(model["num_attention_heads"]),
            int(model["num_key_value_heads"]), int(model["head_dim"]),
            int(model["intermediate_size"]), int(model["vocab_size"]))


def layer_params(model: dict) -> int:
    """Parameters of one layer: q/k/v/o, SwiGLU, two norms, q/k norms."""
    _L, d, H, KV, hd, ff, _V = _d(model)
    return d * hd * (H + 2 * KV) + H * hd * d + 3 * d * ff + 2 * d + 2 * hd


def decode(model: dict, steps: int, slot_steps: int, depth_sum: int,
           heads: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``steps`` decode steps that decoded ``slot_steps``
    live slots in all, reading ``depth_sum`` K/V entries per layer."""
    L, d, H, KV, hd, _ff, V = _d(model)
    P = layer_params(model)
    entry = L * 2 * KV * hd * BF16          # one position's K and V
    flops = (slot_steps * (2.0 * L * P + heads * 2.0 * d * V)
             + depth_sum * L * 4.0 * H * hd)
    nbytes = (steps * (L * P + heads * V * d) * BF16
              + (depth_sum + slot_steps) * entry)
    return flops, nbytes


def admission(model: dict, n: int, heads: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of prefilling an ``n``-token prompt into a slot and
    reading every head at its last position."""
    L, d, H, KV, hd, _ff, V = _d(model)
    P = layer_params(model)
    flops = (n * 2.0 * L * P + L * 4.0 * H * hd * n * (n + 1) / 2
             + heads * 2.0 * d * V)
    nbytes = (L * P + heads * V * d) * BF16 + n * L * 2 * KV * hd * BF16
    return flops, nbytes


def window(ctx: dict) -> Tuple[float, float]:
    """(FLOPs, bytes) of a serving window's decode steps and admissions,
    from a driver's layer context."""
    c, m = ctx["counters"], ctx["model"]
    heads = 1 + len(ctx["exit_layers"])
    slot_steps = c["tokens_out"] - c["admissions"]
    f, b = decode(m, c["steps"], slot_steps, c["live_depth_sum"], heads)
    for n in ctx["prompts"]:
        fa, ba = admission(m, n, heads)
        f, b = f + fa, b + ba
    return f, b


def gate_bytes(ctx: dict) -> float:
    """Bytes of float32 logits the exit gate reads in a window: each gated
    head's rows, every row of a decode step's batch and one row an
    admission."""
    c = ctx["counters"]
    rows = ctx["heads_gated"] * (c["steps"] * ctx["batch"] + c["admissions"])
    return rows * int(ctx["model"]["vocab_size"]) * 4.0
