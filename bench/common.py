"""Shared plumbing of the benchmark: files, devices, compile cache, output.

Everything here belongs to the yardstick: the program under test is only
imported by the drivers (``bench/drivers``) and by ``compile_cache``.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from pathlib import Path
from typing import Dict, Mapping, Optional, Sequence

#: the checkout root: the directory that holds ``BENCHMARK.json``
ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: JAX's persistent compilation cache: a fixed path inside the checkout, so
#: every run after the first in a checkout finds its programs there
CACHE_DIR = ROOT / ".jax_cache"


def rng_for(seed: int, stream: int):
    """An independent numpy stream per purpose, for any whole-number seed."""
    import numpy as np
    return np.random.default_rng([int(seed) & (2**64 - 1), int(stream)])


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_named(entries: Sequence[Mapping], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return dict(e)
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def load_module(path: Path, name: Optional[str] = None):
    """Import a Python file by path (drivers, generators and layer-metric
    readers are found by name; a metric's name may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def use_checkout_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    and keep every program there, however short its compile (JAX's
    default skips programs that compiled in under a second, which would
    make every run recompile them inside its set-up).

    ``JAX_COMPILATION_CACHE_DIR`` is overridden on purpose: a directory
    handed down by the machine would be shared between checkouts."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    from repro.launch.compile_cache import use_compile_cache
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return use_compile_cache()


def accelerator_devices(chips: int) -> list:
    """The first ``chips`` TPU devices, or exit non-zero without a result.

    The benchmark never falls back to the CPU: a number from the CPU is not
    a device number."""
    import jax
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        kind = devs[0].platform if devs else "none"
        print(f"bench: needs a TPU, found {kind!r}", file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, found {len(devs)}",
              file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


def device_record(devs: list) -> Dict[str, object]:
    """``device`` of the result line: as JAX reports it, with the peak
    memory of the fullest chip."""
    peaks = []
    for d in devs:
        try:
            st = d.memory_stats() or {}
        except Exception:          # backends without memory statistics
            st = {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else 0}


class CompileCounter:
    """Counts XLA backend compiles (JAX's compile-duration events) while
    registered.  A persistent-cache hit records no such event."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def __call__(self, event: str, duration_secs: float, **kw) -> None:
        if event == self.EVENT:
            self.n += 1

    def __enter__(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks, over every value given."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def info(msg: str) -> None:
    """An earlier line of standard output (never the result line)."""
    print(msg, flush=True)


def emit(result: Dict[str, object], checks: Dict[str, Dict[str, float]]
         ) -> None:
    """Print every compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard output,
    with the same numbers under ``checks``, its last key."""
    for name, c in checks.items():
        ok = "ok" if c["ok"] else "FAIL"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} ({ok})",
              file=sys.stderr, flush=True)
    out = dict(result)
    out["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                     for k, v in checks.items()}
    print(json.dumps(out), flush=True)
