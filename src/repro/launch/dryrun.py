import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh ((16,16) single-pod or (2,16,16) multi-pod),
  2. builds the step function and ShapeDtypeStruct input specs (no data is
     ever allocated — 398B-parameter models lower fine on one CPU),
  3. jit(...).lower(...).compile() with explicit in/out shardings,
  4. records memory_analysis / cost_analysis / collective wire bytes and the
     derived roofline terms to experiments/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only-one]
"""
import argparse
import json
import pathlib
import sys
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, all_cells, get, runnable_cells
from repro.launch.mesh import HW, make_production_mesh
from repro.launch.roofline import analyze, model_flops
from repro.models import transformer as T
from repro.runtime.steps import input_specs, step_for
from repro.sharding import (batch_shardings, caches_shardings, dp_axes,
                            params_shardings, scalar_sharding)

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def shardings_for(cfg, mesh, shape, specs):
    """in_shardings pytree matching input_specs(cfg, shape)."""
    out = {}
    if "state" in specs:
        pshard = params_shardings(cfg, mesh, specs["state"]["params"])
        opt = specs["state"]["opt"]
        out["state"] = {
            "params": pshard,
            "opt": type(opt)(step=scalar_sharding(mesh),
                             mu=params_shardings(cfg, mesh, opt.mu),
                             nu=params_shardings(cfg, mesh, opt.nu)),
        }
    if "params" in specs:
        out["params"] = params_shardings(cfg, mesh, specs["params"])
    if "batch" in specs:
        out["batch"] = batch_shardings(cfg, mesh, specs["batch"])
    if "caches" in specs:
        out["caches"] = caches_shardings(cfg, mesh, specs["caches"])
    from repro.sharding.specs import _dp_if_divisible
    if "tokens" in specs:
        out["tokens"] = NamedSharding(
            mesh, P(_dp_if_divisible(mesh, specs["tokens"].shape[0]), None))
    if "pos" in specs:                 # one position a batch row
        out["pos"] = NamedSharding(
            mesh, P(_dp_if_divisible(mesh, specs["pos"].shape[0])))
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             *, cfg_overrides: dict = None, tag: str = "",
             optimized: bool = False) -> dict:
    t0 = time.time()
    cfg = get(arch, optimized=optimized)
    if cfg_overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    if (cfg.parallelism_mode == "pure_dp"
            and shape.global_batch % mesh.devices.size):
        # pure DP requires batch >= chips; fall back to TP + sequence
        # parallelism for small-batch cells (prefill/decode of small models)
        import dataclasses
        cfg = dataclasses.replace(cfg, parallelism_mode="tp",
                                  seq_parallel=True)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    chips = mesh.devices.size

    step, argnames = step_for(cfg, shape)
    specs = input_specs(cfg, shape)
    in_shards = shardings_for(cfg, mesh, shape, specs)

    args = tuple(specs[a] for a in argnames)
    shard_args = tuple(in_shards[a] for a in argnames)

    from repro.sharding.context import activation_sharding
    with mesh, activation_sharding(mesh):
        jitted = jax.jit(step, in_shardings=shard_args)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost_list = compiled.cost_analysis()
        cost = cost_list if isinstance(cost_list, dict) else cost_list[0]
        hlo = compiled.as_text()

    from repro.launch.analytic import analytic_cost
    mesh_axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    acost = analytic_cost(cfg, shape, chips, mesh_axes)
    rl = analyze(arch, shape_name, mesh_name, chips, cost, mem, hlo,
                 model_flops(cfg, shape), HW, analytic=acost)
    result = rl.to_json()
    result.update(
        compile_s=time.time() - t0,
        memory_analysis=dict(
            argument_gb=mem.argument_size_in_bytes / 1e9,
            output_gb=mem.output_size_in_bytes / 1e9,
            temp_gb=mem.temp_size_in_bytes / 1e9,
            alias_gb=mem.alias_size_in_bytes / 1e9,
        ),
        tag=tag,
    )
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = OUT_DIR / f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
    path.write_text(json.dumps(result, indent=1))
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every runnable cell (single-pod) sequentially")
    ap.add_argument("--optimized", action="store_true",
                    help="apply configs.registry.OPTIMIZED_OVERRIDES "
                         "(results tagged 'opt')")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        cells = [(a, s, args.multi_pod) for a, s in all_cells()]
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape, args.multi_pod)]

    failed = []
    tag = "opt" if args.optimized else ""
    for arch, shape, mp in cells:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        suffix = f"__{tag}" if tag else ""
        out = OUT_DIR / f"{arch}__{shape}__{mesh_name}{suffix}.json"
        if args.skip_existing and out.exists():
            print(f"skip {arch} {shape} {mesh_name}")
            continue
        try:
            r = run_cell(arch, shape, mp, optimized=args.optimized, tag=tag)
            print(f"OK {arch} {shape} {mesh_name}: "
                  f"flops/chip={r['flops_per_chip']:.3e} "
                  f"bytes/chip={r['bytes_per_chip']:.3e} "
                  f"wire/chip={r['wire_bytes_per_chip']:.3e} "
                  f"bottleneck={r['bottleneck']} "
                  f"mem={r['memory_per_chip_gb']:.2f}GB "
                  f"({r['compile_s']:.0f}s)")
        except Exception as e:
            failed.append((arch, shape, mesh_name))
            print(f"FAIL {arch} {shape} {mesh_name}: {e}")
            traceback.print_exc()
        sys.stdout.flush()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
