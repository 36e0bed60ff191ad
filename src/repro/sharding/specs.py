"""Partition-spec policies: map parameter / cache / batch pytrees to
PartitionSpecs for the production mesh.

Baseline policy (paper-faithful Megatron-style TP + DP):
  * attention: q/o heads on "model"; k/v heads on "model" iff divisible,
    else replicated (GQA with kv < mesh);
  * MLP: d_ff on "model" (column/row parallel);
  * MoE: experts on "model" when cfg.expert_parallel and divisible (EP),
    else expert d_ff on "model" (tensor-parallel experts);
  * SSM: in/out projections sharded on the contracting d_model/d_inner dim;
  * embedding / LM head: vocab on "model";
  * FSDP (cfg.fsdp): parameters and optimizer state additionally sharded on
    "data" along the largest remaining dim (ZeRO-3 — GSPMD inserts the
    per-layer all-gathers);
  * batch: global batch on ("pod",) "data";
  * KV caches: batch on "data" + kv_shard_mode in {heads, sequence, batch}.

Every rule keys off parameter path names, so new modules compose for free.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        elif hasattr(p, "name"):
            out.append(str(p.name))
    return "/".join(out)


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def _with_fsdp(spec: Tuple, shape: Tuple[int, ...], mesh: Mesh,
               enabled: bool) -> P:
    """Add "data" sharding on the largest unsharded, divisible dim."""
    spec = list(spec)
    if enabled:
        dsize = _axis_size(mesh, "data")
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in order:
            if spec[i] is None and shape[i] % dsize == 0 and shape[i] >= dsize:
                spec[i] = "data"
                break
    return P(*spec)


def param_spec(cfg: ArchConfig, mesh: Mesh, path: str,
               shape: Tuple[int, ...]) -> P:
    msize = _axis_size(mesh, "model")
    fsdp = cfg.fsdp
    nd = len(shape)

    if cfg.parallelism_mode == "pure_dp":
        # no tensor parallelism: the whole mesh is one DP domain; parameters
        # are ZeRO-3 sharded over ("data","model") on the largest divisible
        # dim (always, regardless of cfg.fsdp — replication would not fit).
        n = _axis_size(mesh, "data") * msize
        s = [None] * nd
        order = sorted(range(nd), key=lambda i: -shape[i])
        for i in order:
            if shape[i] % n == 0 and shape[i] >= n:
                s[i] = ("data", "model")
                break
        else:
            for i in order:   # fall back to data-only sharding
                if shape[i] % _axis_size(mesh, "data") == 0:
                    s[i] = "data"
                    break
        return P(*s)

    def base():
        return [None] * nd

    def div(dim: int) -> bool:
        # jit *input* shardings require exact divisibility (GSPMD pads only
        # intermediates) — every axis assignment must be guarded.
        return shape[dim] % msize == 0

    # --- embedding / lm head -------------------------------------------------
    if path.endswith("embed/table"):
        return P("model", "data" if fsdp and cfg.d_model % _axis_size(
            mesh, "data") == 0 else None)
    if path.endswith("lm_head/w"):
        return P(None, "model") if not fsdp else P("data", "model")

    # --- attention ------------------------------------------------------------
    if "/mix/" in path and path.endswith(("wq",)):
        s = base()
        if div(-2):
            s[-2] = "model"                  # [.., d, H, hd]: heads
        elif div(-3):
            s[-3] = "model"                  # fallback: row-parallel on d
        return _with_fsdp(tuple(s), shape, mesh, fsdp)
    if "/mix/" in path and path.endswith(("wk", "wv")):
        s = base()
        if cfg.n_kv_heads % msize == 0 and div(-2):
            s[-2] = "model"
        elif div(-3):
            s[-3] = "model"                  # row-parallel on d
        return _with_fsdp(tuple(s), shape, mesh, fsdp)
    if "/mix/" in path and path.endswith("wo"):
        s = base()
        if div(-3):
            s[-3] = "model"                  # [.., H, hd, d]: heads
        elif div(-1):
            s[-1] = "model"                  # fallback: column-parallel on d
        return _with_fsdp(tuple(s), shape, mesh, fsdp)

    # --- MoE -------------------------------------------------------------------
    if path.endswith("router"):
        return P(*base())
    if "/mlp/" in path and ("w_gate" in path or "w_up" in path
                            or "w_down" in path):
        is_expert = nd >= 3 and cfg.n_experts > 0 and \
            shape[-3] == cfg.n_experts if nd >= 3 else False
        if is_expert:
            s = base()
            if cfg.expert_parallel and cfg.n_experts % msize == 0:
                s[-3] = "model"              # EP: experts across model axis
            else:
                # TP experts: shard d_ff
                ff_dim = -1 if "w_gate" in path or "w_up" in path else -2
                s[ff_dim] = "model"
            return _with_fsdp(tuple(s), shape, mesh, fsdp)
        # dense MLP (or arctic dense residual)
        s = base()
        s[-1 if ("w_gate" in path or "w_up" in path) else -2] = "model"
        return _with_fsdp(tuple(s), shape, mesh, fsdp)

    # --- SSM --------------------------------------------------------------------
    if path.endswith("in_proj"):
        s = base()
        s[-2] = "model"                      # contracting d_model dim
        return _with_fsdp(tuple(s), shape, mesh, fsdp)
    if path.endswith("out_proj"):
        s = base()
        s[-2] = "model"                      # contracting d_inner dim
        return _with_fsdp(tuple(s), shape, mesh, fsdp)
    if "conv_w" in path or "conv_b" in path:
        return P(*base())

    # --- norms / scalars / exits -------------------------------------------------
    return P(*base())


def params_shardings(cfg: ArchConfig, mesh: Mesh, params_shapes):
    """Pytree of NamedShardings matching a params (shape) pytree."""
    def fn(path, leaf):
        return NamedSharding(mesh, param_spec(cfg, mesh, _path_str(path),
                                              leaf.shape))
    return jax.tree_util.tree_map_with_path(fn, params_shapes)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _dp_if_divisible(mesh: Mesh, batch: int, *, all_axes: bool = False):
    dp = dp_axes(mesh) + (("model",) if all_axes and "model" in
                          mesh.axis_names else ())
    n = 1
    for a in dp:
        n *= _axis_size(mesh, a)
    if n and batch % n == 0:
        return dp
    # try dropping the model axis, then give up
    dp = dp_axes(mesh)
    n = 1
    for a in dp:
        n *= _axis_size(mesh, a)
    return dp if (n and batch % n == 0) else None


def cache_spec(cfg: ArchConfig, mesh: Mesh, path: str,
               shape: Tuple[int, ...]) -> P:
    msize = _axis_size(mesh, "model")
    pure = cfg.parallelism_mode == "pure_dp"
    if pure:
        msize = 1  # no model-axis sharding of heads/seq in pure DP
    if path.endswith(("/k", "/v")):
        # [n_periods, B, T, KV, hd]
        dp = _dp_if_divisible(mesh, shape[1], all_axes=pure)
        mode = cfg.kv_shard_mode
        if mode == "auto":
            mode = "heads" if cfg.n_kv_heads % msize == 0 else "sequence"
        if mode == "heads" and cfg.n_kv_heads % msize == 0:
            return P(None, dp, None, "model", None)
        if mode == "sequence" and shape[2] % msize == 0:
            return P(None, dp, "model", None, None)
        return P(None, dp, None, None, None)
    if path.endswith(("k_scale", "v_scale")):
        # [n_periods, B, T, KV] — mirror the k/v sharding sans head_dim
        dp = _dp_if_divisible(mesh, shape[1], all_axes=pure)
        mode = cfg.kv_shard_mode
        if mode == "auto":
            mode = "heads" if cfg.n_kv_heads % msize == 0 else "sequence"
        if msize > 1 and mode == "heads" and cfg.n_kv_heads % msize == 0:
            return P(None, dp, None, "model")
        if msize > 1 and mode == "sequence" and shape[2] % msize == 0:
            return P(None, dp, "model", None)
        return P(None, dp, None, None)
    if path.endswith("/pos"):         # [n, B, T]: one position row a slot
        return P(None, _dp_if_divisible(mesh, shape[1], all_axes=pure), None)
    if path.endswith("/state"):        # [n, B, H, P, N]
        dp = _dp_if_divisible(mesh, shape[1], all_axes=pure)
        s = [None, dp, None, None, None]
        if msize > 1 and cfg.ssm_head_shard and shape[2] % msize == 0:
            s[2] = "model"
        return P(*s)
    if path.endswith("/conv"):         # [n, B, w-1, C]
        dp = _dp_if_divisible(mesh, shape[1], all_axes=pure)
        return P(None, dp, None, None)
    return P(*([None] * len(shape)))


def caches_shardings(cfg: ArchConfig, mesh: Mesh, cache_shapes):
    def fn(path, leaf):
        return NamedSharding(mesh, cache_spec(cfg, mesh, _path_str(path),
                                              leaf.shape))
    return jax.tree_util.tree_map_with_path(fn, cache_shapes)


# ---------------------------------------------------------------------------
# Batch / activations
# ---------------------------------------------------------------------------

def batch_shardings(cfg: ArchConfig, mesh: Mesh, batch_shapes):
    pure = cfg.parallelism_mode == "pure_dp"

    def fn(path, leaf):
        nd = len(leaf.shape)
        spec = [None] * nd
        spec[0] = _dp_if_divisible(mesh, leaf.shape[0], all_axes=pure)
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(fn, batch_shapes)


def scalar_sharding(mesh: Mesh):
    return NamedSharding(mesh, P())
