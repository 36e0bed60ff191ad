"""From a profiler trace to device metrics.

``load_xplane`` turns the ``.xplane.pb`` a ``jax.profiler`` trace writes
into plain data: ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}``.  ``reduce`` works on that data
alone, so the self-test feeds it a small recorded trace.

On a TPU the device planes are ``/device:TPU:<i>``; their ``XLA Ops`` line
holds one event per executed HLO op (the text of the op as its name, a
Pallas kernel as a ``custom-call`` named after its kernel) on the same
clock as the host plane ``/host:CPU``, whose thread lines hold the
harness's ``jax.profiler.TraceAnnotation`` spans (``bench.*``).  The
window is the harness's ``bench.window`` span.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def load_xplane(trace_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    planes = []
    for plane in pd.planes:
        keep_all = plane.name.startswith(DEVICE_PREFIX)
        lines = []
        for line in plane.lines:
            if keep_all:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events]
            elif plane.name == HOST_PLANE:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events
                       if e.name.startswith(SPAN_PREFIX)]
            else:
                continue
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_short_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``: the op's name
    without its ``%`` and numeric suffix."""
    head = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def _self_times(evs, w0: float, w1: float) -> Dict[str, float]:
    """Seconds per op short name inside [w0, w1), less the time of the ops
    each one encloses (nested events of one device's op line)."""
    own: Dict[str, float] = {}
    stack: List[Tuple[str, float]] = []        # (short name, end)
    for name, st, du in sorted(evs, key=lambda ev: (ev[1], -ev[2])):
        s, e = max(st, w0), min(st + du, w1)
        while stack and stack[-1][1] <= st:
            stack.pop()
        if e <= s:
            continue
        short = op_short_name(name)
        own[short] = own.get(short, 0.0) + (e - s) * 1e-9
        if stack and st + du <= stack[-1][1]:
            parent = stack[-1][0]
            own[parent] = own.get(parent, 0.0) - (e - s) * 1e-9
        stack.append((short, st + du))
    return own


def reduce(trace: dict, kernels: Sequence[str] = ()) -> Optional[dict]:
    """Device metrics over the harness's window, or None when the trace
    has no device plane or no window span.

    Returns seconds: ``window_s``; ``busy_s`` (union of op intervals,
    averaged over the devices); per kernel named in ``kernels`` (a
    custom-call op named after it) its device time, its number of calls
    and the count of each distinct op text (which carries the shapes);
    ``device_ops`` (op short names by total self time, summed over
    devices: an op that encloses others on its device, as a ``while``
    encloses its body's ops, counts only the time its children leave);
    ``idle_gaps`` (the longest gaps between device ops, each named by the
    ``bench.*`` host span that covers most of it)."""
    host = [p for p in trace["planes"] if p["name"] == HOST_PLANE]
    spans = [tuple(ev) for p in host for line in p["lines"]
             for ev in line["events"]]
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    devices = [p for p in trace["planes"]
               if p["name"].startswith(DEVICE_PREFIX)]
    if not win or not devices:
        return None
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    window_s = (w1 - w0) * 1e-9
    busy = []
    ops: Dict[str, float] = {}
    kern: Dict[str, Dict[str, object]] = {
        k: {"seconds": 0.0, "calls": 0, "ops": {}} for k in kernels}
    gaps: List[Tuple[float, float]] = []
    for dev in devices:
        evs = [ev for line in dev["lines"] if line["name"] == OPS_LINE
               for ev in line["events"]]
        iv = []
        for short, t in _self_times(evs, w0, w1).items():
            ops[short] = ops.get(short, 0.0) + t
        for name, st, du in evs:
            s, e = max(st, w0), min(st + du, w1)
            if e <= s:
                continue
            iv.append((s, e))
            short = op_short_name(name)
            for k in kernels:
                if short.startswith(k) and "custom-call" in name:
                    kern[k]["seconds"] += (e - s) * 1e-9
                    kern[k]["calls"] += 1
                    kern[k]["ops"][name] = kern[k]["ops"].get(name, 0) + 1
        u_all = _union(iv)
        busy.append(_length(u_all) * 1e-9)
        prev = w0
        for s, e in u_all:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if w1 > prev:
            gaps.append((prev, w1))
    nd = len(devices)
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    named = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        best, cover = "none", 0.0
        for name, st, du in inner:
            o = min(g1, st + du) - max(g0, st)
            if o > cover:
                best, cover = name, o
        named.append([best, (g1 - g0) * 1e-9])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / nd,
        "kernels": kern,
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": named,
    }
