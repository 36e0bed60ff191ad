"""Jitted wrapper for the ee_gate Pallas kernel (interpreted on CPU)."""
from __future__ import annotations

import jax.numpy as jnp

from .ee_gate import ee_gate_pallas


def ee_gate(logits: jnp.ndarray):
    """logits: [B, V] -> (confidence [B], greedy token [B])."""
    return ee_gate_pallas(logits)
