"""The program's own spans in a profiler trace, over the harness's window.

The program under test annotates its population tick (``orch.*`` for the
orchestrator, ``pop.*`` for a cohort; ``src/repro/core/spans.py``) with
``jax.profiler.TraceAnnotation`` when its cohorts are built with
``timing=True``, as ``bench/drivers/population.py`` does in a ``--trace 1``
run.  Those spans sit on the profiler's ``/host:CPU`` plane, on the same
clock as the device's ``XLA Ops`` line.

``load`` reads a ``.xplane.pb`` into the plain data ``trace_reduce`` uses
(``{"planes": [{"name", "lines": [{"name", "events": [...]}]}]}``), keeping
the harness's ``bench.*`` spans, the program spans, each with its numeric
arguments as a fourth element, and the device op lines.  ``host_table``
works on that data alone, so the self-test feeds it a recorded trace.
``for_run`` finds the newest trace a run left under
``<checkout>/bench_out/trace/`` and reduces it; ``per_tick`` keeps the
table in the readers' context, so the readers in ``bench/layer_metrics``
share one reduction a run.  A program without these spans gives a table
without ``orch.tick``, and the readers then report nothing.
"""
from __future__ import annotations

import bisect
import glob
import os
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench import common
from bench.trace_reduce import (DEVICE_PREFIX, HOST_PLANE, OPS_LINE,
                                SPAN_PREFIX, WINDOW_SPAN, _union)

PROGRAM_PREFIXES = ("orch.", "pop.")
#: one orchestrator tick: the parent of every program span on its thread
TICK_SPAN = "orch.tick"

def load(path: str) -> dict:
    """Read one ``.xplane.pb``: host spans (harness and program) and the
    device op lines."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    keep = (SPAN_PREFIX,) + PROGRAM_PREFIXES
    planes = []
    for plane in pd.planes:
        lines = []
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = []
                for e in line.events:
                    if not e.name.startswith(keep):
                        continue
                    ev = [e.name, float(e.start_ns), float(e.duration_ns)]
                    args = {k: float(v) for k, v in e.stats
                            if isinstance(v, (int, float))}
                    if args:
                        ev.append(args)
                    evs.append(ev)
                if evs:
                    lines.append({"name": line.name, "events": evs})
        elif plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    lines.append({"name": line.name, "events": [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]})
        else:
            continue
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _subtract(a: float, b: float, holes: List[Tuple[float, float]]
              ) -> List[Tuple[float, float]]:
    """[a, b) less the union of ``holes``."""
    out, prev = [], a
    for s, e in _union(holes):
        if s > prev:
            out.append((prev, min(s, b)))
        prev = max(prev, e)
    if b > prev:
        out.append((prev, b))
    return [(s, e) for s, e in out if e > s]


class _Busy:
    """Covered length of a device's op union up to any instant."""

    def __init__(self, union: List[Tuple[float, float]]):
        self.starts = [s for s, _e in union]
        self.ends = [e for _s, e in union]
        self.before = [0.0]
        for s, e in union:
            self.before.append(self.before[-1] + (e - s))

    def covered(self, x: float) -> float:
        k = bisect.bisect_right(self.starts, x) - 1
        if k < 0:
            return 0.0
        return self.before[k] + min(x, self.ends[k]) - self.starts[k]

    def overlap(self, s: float, e: float) -> float:
        return self.covered(e) - self.covered(s)


def host_table(trace: dict) -> Optional[dict]:
    """Per program span name inside the ``bench.window`` span: ``count``,
    ``total_s``, ``self_s`` (duration less what its child program spans on
    the same thread line cover), ``idle_s`` (the self time during which the
    device ran no op, averaged over the devices; None without a device
    plane) and ``args`` (its numeric arguments, summed).  None without a
    window span."""
    host = [p for p in trace["planes"] if p["name"] == HOST_PLANE]
    win = [ev for p in host for line in p["lines"] for ev in line["events"]
           if ev[0] == WINDOW_SPAN]
    if not win:
        return None
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    busy = []
    for dev in trace["planes"]:
        if not dev["name"].startswith(DEVICE_PREFIX):
            continue
        iv = [(max(st, w0), min(st + du, w1))
              for line in dev["lines"] if line["name"] == OPS_LINE
              for _n, st, du in line["events"]]
        busy.append(_Busy(_union([(s, e) for s, e in iv if e > s])))
    table: Dict[str, dict] = {}
    for p in host:
        for line in p["lines"]:
            evs = sorted((ev for ev in line["events"]
                          if ev[0].startswith(PROGRAM_PREFIXES)),
                         key=lambda ev: (ev[1], -ev[2]))
            # (name, start, end, args, children) per span; spans on one
            # thread line nest, so the open ones form a stack
            spans: List[list] = []
            stack: List[list] = []
            for ev in evs:
                st, en = ev[1], ev[1] + ev[2]
                while stack and stack[-1][2] <= st:
                    stack.pop()
                node = [ev[0], st, en, ev[3] if len(ev) > 3 else {}, []]
                if stack:
                    stack[-1][4].append((max(st, w0), min(en, w1)))
                stack.append(node)
                spans.append(node)
            for name, st, en, args, kids in spans:
                s, e = max(st, w0), min(en, w1)
                if e <= s:
                    continue
                own = _subtract(s, e, [k for k in kids if k[1] > k[0]])
                self_s = sum(b - a for a, b in own)
                row = table.setdefault(name, {
                    "count": 0, "total_s": 0.0, "self_s": 0.0,
                    "idle_s": 0.0 if busy else None, "args": {}})
                row["count"] += 1
                row["total_s"] += (e - s) * 1e-9
                row["self_s"] += self_s * 1e-9
                if busy:
                    on = sum(b.overlap(a, z) for b in busy for a, z in own)
                    row["idle_s"] += (self_s - on / len(busy)) * 1e-9
                for k, v in args.items():
                    row["args"][k] = row["args"].get(k, 0.0) + v
    return table


def _row_text(name: str, row: dict, ticks: int) -> str:
    idle = row["idle_s"]
    return (f"{name} {row['count'] / ticks:.4g} "
            f"{row['self_s'] * 1e3 / ticks:.6g} "
            + ("-" if idle is None else f"{idle * 1e3 / ticks:.6g}"))


def for_run(root: Path, ticks: int) -> Optional[dict]:
    """The host table of the newest trace under ``<root>/bench_out/trace``,
    printed as an earlier line of output (calls, self and idle ms per tick
    of each program span)."""
    files = glob.glob(os.path.join(str(root), "bench_out", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    if not files:
        return None
    tab = host_table(load(max(files, key=os.path.getmtime)))
    if tab and ticks:
        common.info("program spans per tick (name, calls, self ms, idle "
                    "ms): " + "; ".join(
                        _row_text(n, r, ticks) for n, r in sorted(
                            tab.items(), key=lambda kv: -kv[1]["self_s"])))
    return tab


def per_tick(ctx: dict, reader_file: str, name: str, what: str = "total_s",
             scale: float = 1e3) -> Optional[float]:
    """A population reader's value: ``what`` of span ``name`` (seconds
    times ``scale``, or a summed argument ``args.<key>``) per tick of the
    window.  None for another kind of cell, or for a program whose trace
    holds no ``orch.tick``; 0.0 for a span the program has but did not
    enter."""
    if ctx.get("kind") != "population" or not ctx.get("ticks"):
        return None
    if "program_spans" not in ctx:
        # the reader's own checkout: <root>/bench/layer_metrics/<metric>.py
        ctx["program_spans"] = for_run(
            Path(reader_file).resolve().parents[2], ctx["ticks"])
    tab = ctx["program_spans"]
    if not tab or TICK_SPAN not in tab:
        return None
    row = tab.get(name)
    if row is None:
        return 0.0
    if what.startswith("args."):
        v = row["args"].get(what[5:], 0.0)
    else:
        v = row[what] * scale
    return v / ctx["ticks"]
