"""Production mesh construction (multi-pod dry-run contract).

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Shapes: (16, 16) = one v5e pod slice of 256 chips with
("data", "model") axes; (2, 16, 16) = two pods = 512 chips with a leading
pure-DP "pod" axis (gradient all-reduce crosses DCN).

The process must expose enough host devices first — dryrun.py sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any import.
When more devices exist than the mesh needs (single-pod mesh in the
512-device dry-run process), the first prod(shape) devices are used.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax

from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape: Tuple[int, ...] = (2, 4),
                   axes: Tuple[str, ...] = ("data", "model")) -> Mesh:
    """Small mesh for CPU sharding tests (8 host devices)."""
    return _mesh(shape, axes)


def _mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devs)} — launch via "
            f"dryrun.py (sets --xla_force_host_platform_device_count)")
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


#: TPU v5e hardware constants for the roofline model (per chip).
HW = dict(
    peak_flops_bf16=197e12,      # FLOP/s
    hbm_bw=819e9,                # B/s
    ici_bw_per_link=50e9,        # B/s per link (~)
)
