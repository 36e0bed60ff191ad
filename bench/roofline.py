"""Operations and bytes that each measured piece of work needs, from its
shapes: the work of the algorithm, not what an implementation happens to
read or pad.  ``memory_time`` turns bytes into the chip's lower bound from
``peaks.json``; a device that is not in the table is an error."""
from __future__ import annotations

from typing import Dict, Tuple

from bench import common


def peaks(device_kind: str) -> Dict[str, float]:
    table = common.load_json(common.BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def memory_time(nbytes: float, pk: Dict[str, float]) -> float:
    """Seconds the chip needs to move ``nbytes`` through HBM at its peak.

    The min-plus work runs on the vector units in float32, for which the
    table has no sourced peak, so no compute bound is taken: the memory
    bound alone is a lower bound on the time, and a share of it cannot
    pass 100% unless the bytes are overcounted."""
    return nbytes / pk["hbm_bytes_per_s"]


def minplus_chain(rows: int, layers: int, nodes: int, depths: int
                  ) -> Tuple[float, float]:
    """Banded (min,+) chain over ``rows`` scenarios: each of ``layers``
    layers relaxes ``nodes`` x ``depths`` target states over ``nodes``
    sources (one add and one min each).  Bytes: the float32 init grid,
    float32 energies and int32 steepness per layer in, the float32
    distance grid and int32 argmin per layer out."""
    ops = 2.0 * rows * layers * nodes * nodes * depths
    nbytes = rows * (4 * nodes * depths + layers * nodes * nodes * 8
                     + layers * nodes * depths * 8)
    return ops, nbytes


def fin_relax(cfg: dict, relaxed_by_app: Dict[str, float]
              ) -> Tuple[float, float]:
    """The relaxation work FIN needs for the cohort states relaxed, per app
    (``relaxed_by_app``): each state is one chain per quantization mode
    (the configured mode plus the ceil rescue) over the app's ``L - 1``
    block boundaries, at the configuration's node count and ``gamma + 1``
    depths.  Counts ``minplus_chain`` per app and sums them."""
    nodes = sum(int(c) for _t, c in cfg["network"]["layout"])
    depths = int(cfg["solver"]["gamma"]) + 1
    modes = 1 if cfg["solver"]["quantize"] == "ceil" else 2
    ops = nbytes = 0.0
    for app, n in relaxed_by_app.items():
        blocks = len(cfg["models"][cfg["apps"][app]["model"]]["blocks"])
        f, b = minplus_chain(n * modes, blocks - 1, nodes, depths)
        ops += f
        nbytes += b
    return ops, nbytes
