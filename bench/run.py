"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from anywhere inside a checkout that holds ``BENCHMARK.json``, on a
machine with the chips the cell asks for.  The cell names a configuration
(``bench/configs/<config>.json``, whose ``kind`` picks the driver under
``bench/drivers/``) and a traffic mix (``bench/traffic/<traffic>.json``,
whose ``generator`` picks the generator under ``bench/generators/``).
Set-up loads, builds and warms every shape the cell uses; the window then
measures for ``--seconds``; afterwards the plain reference judges what the
window produced.  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the profiler
and the result carries its per-layer metrics, each read by
``bench/layer_metrics/<metric>.py``.

Earlier lines of standard output say what the run did (compiles inside the
window, generator lag); the last lines of standard error give each number
compared beside its limit; the last line of standard output is the result:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"checks"}``.  Without a TPU, or with fewer chips than the cell needs, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Dict, Optional, Sequence  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common  # noqa: E402


def _process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``), so that
    ``setup_s`` counts the interpreter's own start too."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


class Tracer:
    """The harness's spans (``jax.profiler.TraceAnnotation``) and the
    profiler session of a ``--trace 1`` run; inert otherwise."""

    def __init__(self, on: bool, out_dir: Path):
        self.on = on
        self.dir = out_dir
        self.trace: Optional[dict] = None

    def start(self) -> None:
        if not self.on:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def stop(self) -> None:
        if not self.on:
            return
        import jax
        jax.profiler.stop_trace()
        from bench.trace_reduce import load_xplane
        self.trace = load_xplane(str(self.dir))


def _applies(entry: dict, cell: str, e2e_names: Sequence[str]) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry.get("moves") is None or entry["moves"] in e2e_names


def run_cell(argv: Optional[Sequence[str]] = None, *,
             require_chip: bool = True, root: Path = ROOT,
             overrides: Optional[dict] = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control's numbers (limit setting; "
                         "the benchmark's own runs leave it off)")
    args = ap.parse_args(argv)
    age0 = _process_age_s() if require_chip else 0.0
    t0 = T0 if require_chip else time.perf_counter()

    bench = common.load_benchmark(root)
    cell = common.find_named(bench["workloads"], args.workload, "workload")
    centry = common.find_named(bench["configs"], cell["config"],
                               "configuration")
    config = common.load_json(root / centry["file"])
    traffic = common.load_json(root / "bench" / "traffic"
                               / f"{cell['traffic']}.json")
    traffic.update((overrides or {}).get("traffic", {}))
    chips = int(cell["chips"])

    import jax
    if require_chip:
        devices = common.accelerator_devices(chips)
    else:
        devices = jax.devices()[:chips]
    from bench import roofline
    # an unknown device is an error before any work; a CPU rehearsal has
    # no peaks, and the shares of a peak stay silent there
    peaks = roofline.peaks(devices[0].device_kind) if require_chip else None
    cache = (common.use_checkout_compile_cache() if require_chip
             else "off (no chip)")
    common.info(f"device: {devices[0].platform} {devices[0].device_kind} "
                f"x{len(devices)}; compile cache {cache}")
    driver = common.load_module(root / "bench" / "drivers"
                                / f"{config['kind']}.py")
    generator = common.load_module(root / "bench" / "generators"
                                   / f"{traffic['generator']}.py")
    tracer = Tracer(bool(args.trace),
                    root / "bench_out" / "trace" / args.workload)
    # what a driver gets: the cell, its files, the devices, the clock
    ctx = SimpleNamespace(
        args=args, cell=cell, config=config, traffic=traffic,
        generator=generator, chips=chips,
        devices=devices, tracer=tracer, t0=t0 - age0,
        limits=config["limits"],
        overrides=dict(overrides or {}, control=bool(args.control)),
        root=root)
    out = driver.run(ctx)

    e2e_names = [m["name"] for m in bench["end_to_end"]
                 if _applies(m, args.workload, ())]
    result: Dict[str, object] = {}
    device = dict(out["device"]) or common.device_record(devices)
    metrics: Dict[str, Dict[str, object]] = {}
    breakdown = None
    if not args.trace:
        for m in bench["end_to_end"]:
            if _applies(m, args.workload, ()) and m["name"] in out["e2e"]:
                metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                      "unit": m["unit"]}
    else:
        from bench.trace_reduce import reduce
        lctx = dict(out["layer_ctx"])
        lctx["chips"] = chips
        lctx["peaks"] = peaks
        lctx["roofline"] = roofline
        lctx["trace"] = (reduce(tracer.trace, lctx.get("kernels", ()))
                         if tracer.trace is not None else None)
        tr = lctx["trace"]
        if tr is not None:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            breakdown = {"device_ops": tr["device_ops"],
                         "idle_gaps": tr["idle_gaps"]}
        for m in bench["per_layer"]:
            if not _applies(m, args.workload, e2e_names):
                continue
            reader = common.load_module(
                root / "bench" / "layer_metrics" / f"{m['name']}.py")
            v = reader.read(lctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = {}
    for name, c in out["checks"].items():
        checks[name] = dict(c, ok=bool(c["value"] <= c["limit"]))
    result["correct"] = all(c["ok"] for c in checks.values())
    result["attempted"] = int(out["attempted"])
    result["failed"] = int(out["failed"])
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    common.emit(result, checks)
    result["checks"] = checks
    result["control"] = out.get("control")
    return result


def main(argv: Optional[Sequence[str]] = None) -> int:
    run_cell(argv)
    return 0


if __name__ == "__main__":
    sys.exit(main())
