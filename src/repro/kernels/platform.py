"""Where Pallas kernels run: compiled on TPU, interpreted on CPU.

Every kernel entry point takes ``interpret=None`` and resolves it here, so
one rule decides for the whole package: the CPU backend (the test suite)
runs kernel bodies through the Pallas interpreter, a TPU compiles them with
Mosaic, and any other platform is an error rather than a silent fallback
to the interpreter or to the jnp oracles in ``ref.py``.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel's ``interpret`` argument.

    An explicit bool wins (the described-topology compile tests pass
    ``False`` from a CPU process); ``None`` follows the default backend."""
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels have no path on platform "
                       f"{platform!r} (expected 'tpu', or 'cpu' to "
                       f"interpret)")
