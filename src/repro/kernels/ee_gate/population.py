"""Population-scale fused ingest gate: quantize -> int16 pack -> signature.

``Population._requant_users`` used to run three separate host passes over
a stale-row batch — the Eq. (4) requantization into a float64 ``(Us, M,
2L-1, N)`` pack, an elementwise compare against each user's *stored* pack,
and a second full encode of the same values into the int16 signature rows
``_assign_states`` hashes.  This module fuses all of it into ONE batched
launch that maps the ``(Us, N)`` bandwidth rows straight to the ``(Us,
M*(2L-1)*N)`` int16 signature encoding (the exact bytes the cohort-state
table keys on): values are integers in ``[0, gamma]`` or ``+inf`` by the
ctor invariant (``gamma`` < int16 max), stored with ``-1`` for inf —
exactly invertible, so comparing/keying in encoded space is equivalent to
comparing the float64 packs elementwise.

Two backends, selected per call:

``numpy``   the host oracle — elementwise identical to the historical
            ``_requant_users`` + ``_enc_int16`` composition (same
            ``_quant_raw`` formulas, same copyto semantics), one int16
            output and no float64 pack materialization.
``jnp``     one jitted XLA launch under a *scoped* ``jax.enable_x64`` context
            (the repo never enables x64 globally — the f32 relaxation
            engines must keep their dtypes).  float64 on CPU XLA follows
            the same IEEE arithmetic as numpy, and ``jnp.round`` matches
            numpy's round-half-to-even, so the encoded signatures are
            REQUIRED to agree bit-for-bit with the numpy oracle — the
            bench asserts ``agree=1`` and the tests compare bytes.

The constants bundle (:class:`QuantConsts`) snapshots the proto plan's
packed-requantizer tensors; compute-slice repricings rebuild those, so
``Population`` drops its bundle on ``update_slice`` (backhaul repricings
are bandwidth-only and keep it).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import numpy as np

__all__ = ["QuantConsts", "quant_signature", "quant_signature_np",
           "quant_signature_jnp"]


@dataclass(frozen=True)
class QuantConsts:
    """The batch-invariant inputs of the fused requantizer: the proto
    plan's packed per-link tensors plus the quantizer parameterization.
    ``modes`` is ordered exactly like the population's quantizer passes
    (floor/round main pass first, ceil rescue second)."""

    bits_pack: np.ndarray          # (2L-1, N) float64
    C_pack: np.ndarray             # (2L-1, N) float64
    mask_pack: np.ndarray          # (2L-1, N) bool
    load_pack: np.ndarray          # (2L-1, N) float64
    modes: Tuple[str, ...]
    gamma: int
    delta: float

    @property
    def out_width(self) -> int:
        K2, N = self.bits_pack.shape
        return len(self.modes) * K2 * N


def quant_signature_np(vec: np.ndarray, c: QuantConsts) -> np.ndarray:
    """Host-numpy oracle: (Us, N) bandwidth rows -> (Us, M*K2*N) int16
    signature rows.  Elementwise identical to the historical float64
    requantize-then-encode pipeline (``plan.update_uplinks`` formulas)."""
    # deferred: repro.core.population imports this module at its own
    # module level, so a top-level core import here would be circular
    from repro.core.feasible_graph import _quant_raw
    Us, N = vec.shape
    K2 = c.bits_pack.shape[0]
    M = len(c.modes)
    G = c.gamma
    bwm = np.where(vec > 0, vec, np.nan)                 # (Us, N)
    sc = c.bits_pack[None] / bwm[:, None, :]             # (Us, K2, N)
    sc += c.C_pack[None]
    np.multiply(sc, G, out=sc)
    sc /= c.delta
    valid = np.isfinite(sc)
    valid &= c.mask_pack[None]
    valid &= c.load_pack[None] <= vec[:, None, :]
    enc = np.empty((Us, M, K2, N), dtype=np.int16)
    q = np.empty_like(sc)
    for mi, mode in enumerate(c.modes):
        _quant_raw(sc, mode, out=q)
        ok = q <= G
        ok &= valid
        e = enc[:, mi]
        np.copyto(e, q, casting="unsafe", where=ok)
        e[~ok] = -1
    return enc.reshape(Us, M * K2 * N)


# one jitted program per (modes, gamma, shapes) — the arrays are traced
# arguments so channel values never bake into the compiled executable
_JIT_CACHE: Dict[Tuple, object] = {}


def _jnp_program(modes: Tuple[str, ...], gamma: int):
    fn = _JIT_CACHE.get((modes, gamma))
    if fn is not None:
        return fn
    import jax
    import jax.numpy as jnp

    def run(vec, bits, C, maskp, loadp, delta):
        bwm = jnp.where(vec > 0, vec, jnp.nan)
        sc = bits[None] / bwm[:, None, :]
        sc = sc + C[None]
        sc = sc * gamma
        sc = sc / delta
        valid = jnp.isfinite(sc) & maskp[None] \
            & (loadp[None] <= vec[:, None, :])
        outs = []
        for mode in modes:
            if mode == "floor":
                q = jnp.floor(sc + 1e-12)
            elif mode == "ceil":
                q = jnp.ceil(sc - 1e-12)
            elif mode == "round":
                q = jnp.round(sc)
            else:
                raise ValueError(f"unknown quantize mode {mode!r}")
            ok = (q <= gamma) & valid
            outs.append(jnp.where(ok, q, -1.0).astype(jnp.int16))
        Us = vec.shape[0]
        return jnp.stack(outs, axis=1).reshape(Us, -1)

    fn = _JIT_CACHE[(modes, gamma)] = jax.jit(run)
    return fn


def quant_signature_jnp(vec: np.ndarray, c: QuantConsts) -> np.ndarray:
    """One fused XLA launch under a scoped x64 context — bit-exact vs the
    numpy oracle (asserted by tests and the bench's ``agree`` column)."""
    import jax
    fn = _jnp_program(c.modes, int(c.gamma))
    with jax.enable_x64(True):
        out = fn(np.asarray(vec, dtype=np.float64), c.bits_pack, c.C_pack,
                 c.mask_pack, c.load_pack, np.float64(c.delta))
        return np.asarray(out)


_BACKENDS = {"numpy": quant_signature_np, "jnp": quant_signature_jnp}


def quant_signature(vec: np.ndarray, c: QuantConsts, *,
                    backend: str = "numpy") -> np.ndarray:
    """Fused ingest gate over a batch of bandwidth rows (see module doc).

    Returns the (Us, M*K2*N) int16 signature rows the cohort-state table
    keys on; ``backend`` selects the host oracle or the jitted launch."""
    try:
        fn = _BACKENDS[backend]
    except KeyError:
        raise ValueError(f"unknown quant_signature backend {backend!r} "
                         f"(expected one of {sorted(_BACKENDS)})")
    return fn(vec, c)
