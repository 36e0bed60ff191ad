"""Composable LM backbone: pattern-tiled layers, scan-over-periods, early
exits, train/prefill/decode entry points.

Structure
---------
A model is ``n_periods`` repetitions of ``cfg.pattern`` (a tuple of
LayerSpecs).  Parameters of one period form a pytree; all periods are stacked
on a leading axis and executed with ``lax.scan`` (one compiled body per
segment, not per layer — essential for compile time at 72+ layers).

Early exits (the paper's technique) sit at period boundaries
(cfg.exit_layer_list), splitting the scan into segments:

    embed -> scan[0:e1] -> exit_1 -> scan[e1:e2] -> exit_2 -> ... -> final

Entry points:
  forward_train(params, cfg, batch)  -> {exit_name: [B,S,V]} logits
  loss_fn(params, cfg, batch)        -> scalar (BranchyNet joint CE)
  prefill(params, cfg, batch)        -> (logits_last, caches)
  decode_step(params, cfg, tokens, caches, pos) -> (logits, caches, exits)
  encode(params, cfg, batch)         -> final logits (encoder-only archs)
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerSpec

from . import attention as ATT
from . import moe as MOE
from . import ssm as SSM
from .early_exit import exit_head_apply, exit_head_init
from .layers import (F32, cross_entropy, dense_init, dtype_of, embed_apply,
                     embed_init, lm_head_apply, lm_head_init, mlp_apply,
                     mlp_init, rmsnorm, rmsnorm_init)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ArchConfig, spec: LayerSpec, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    p: dict = {"norm1": rmsnorm_init(cfg.d_model, dtype)}
    if spec.kind == "attn":
        p["mix"] = ATT.attn_init(k1, cfg, dtype)
    elif spec.kind == "ssm":
        p["mix"] = SSM.ssm_init(k1, cfg, dtype)
    else:
        raise ValueError(spec.kind)
    if spec.mlp != "none":
        p["norm2"] = rmsnorm_init(cfg.d_model, dtype)
        p["mlp"] = (mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)
                    if spec.mlp == "dense" else MOE.moe_init(k2, cfg, dtype))
    return p


def _period_init(key, cfg: ArchConfig, dtype) -> dict:
    keys = jax.random.split(key, len(cfg.pattern))
    return {f"l{i}": _layer_init(keys[i], cfg, spec, dtype)
            for i, spec in enumerate(cfg.pattern)}


def init_model(key, cfg: ArchConfig) -> dict:
    dtype = dtype_of(cfg.dtype)
    n = cfg.n_periods
    k_embed, k_head, k_layers, k_exits = jax.random.split(key, 4)
    period_keys = jax.random.split(k_layers, n)
    # vmapped over the period keys: the stack is built in place, never as
    # n separate period pytrees plus their stacked copy (2x the layer
    # weights at peak — what keeps a full-width init off a 16 GB chip)
    stacked = jax.vmap(lambda k: _period_init(k, cfg, dtype))(period_keys)
    params = {
        "embed": embed_init(k_embed, cfg.padded_vocab, cfg.d_model, dtype),
        "layers": stacked,
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
        "exits": {},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = lm_head_init(k_head, cfg.d_model,
                                         cfg.padded_vocab, dtype)
    exit_keys = jax.random.split(k_exits, max(1, len(cfg.exit_layer_list)))
    for j, p_idx in enumerate(cfg.exit_layer_list):
        params["exits"][f"exit_{p_idx}"] = exit_head_init(
            exit_keys[j], cfg, dtype, tied=True)
    return params


def _lm_head_params(params, cfg: ArchConfig) -> dict:
    if cfg.tie_embeddings:
        return {"w": params["embed"]["table"].T}
    return params["lm_head"]


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Period body (train / full-sequence)
# ---------------------------------------------------------------------------

def _sp_constraint(cfg: ArchConfig, h):
    """Sequence parallelism: hidden states sharded on (batch=dp, seq=model)
    at layer boundaries.  GSPMD turns the TP all-reduces into all-gather +
    reduce-scatter pairs and cuts resident activation memory by the model-
    axis size (Megatron-SP; see EXPERIMENTS.md §Perf).  No-op without an
    ``activation_sharding`` context (unit tests, single-device runs)."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.context import current
    ctx = current()
    if ctx is None or h.ndim != 3:
        return h
    if cfg.parallelism_mode == "pure_dp":
        # ZeRO pitfall: without an explicit batch constraint GSPMD keeps the
        # sharded weights in place and replicates the batch instead
        # (observed: 2 TB/chip temps on qwen3 — EXPERIMENTS §Perf).
        axes = ctx.dp_axes + ((ctx.model_axis,) if ctx.model_axis else ())
        n = ctx.dp_size * max(1, ctx.model_size)
        if not axes or h.shape[0] % n:
            return h
        return jax.lax.with_sharding_constraint(h, P(axes, None, None))
    if not cfg.seq_parallel:
        return h
    if not ctx.model_axis or h.shape[1] % ctx.model_size:
        return h
    return jax.lax.with_sharding_constraint(
        h, P(ctx.dp_axes, ctx.model_axis, None))


def _one_layer(cfg: ArchConfig, spec: LayerSpec, p: dict, h, positions):
    h = _sp_constraint(cfg, h)
    hn = rmsnorm(p["norm1"], h, cfg.norm_eps)
    if spec.kind == "attn":
        h = h + ATT.attn_apply(p["mix"], cfg, hn, positions)
    else:
        h = h + SSM.ssm_apply(p["mix"], cfg, hn)
    if spec.mlp != "none":
        h = _sp_constraint(cfg, h)
        hn = rmsnorm(p["norm2"], h, cfg.norm_eps)
        if spec.mlp == "dense":
            h = h + mlp_apply(p["mlp"], hn)
        else:
            h = h + MOE.moe_apply(p["mlp"], cfg, hn)
    return h


def _period_apply(cfg: ArchConfig, pp: dict, h, positions):
    for i, spec in enumerate(cfg.pattern):
        fn = functools.partial(_one_layer, cfg, spec)
        if cfg.remat == "layer" and len(cfg.pattern) > 1:
            # per-layer remat: the backward of a period keeps only ONE
            # layer's intermediates live (vs all 8 for period-level remat —
            # the jamba memory lever, EXPERIMENTS §Perf)
            fn = jax.checkpoint(
                fn, policy=jax.checkpoint_policies.nothing_saveable)
        h = fn(pp[f"l{i}"], h, positions)
    return _sp_constraint(cfg, h)


def _remat(cfg: ArchConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
    elif cfg.remat in ("full", "layer"):
        # "layer" adds inner per-layer checkpoints (see _period_apply) under
        # the same outer scan-body checkpoint
        policy = jax.checkpoint_policies.nothing_saveable
    else:
        raise ValueError(cfg.remat)
    return jax.checkpoint(fn, policy=policy)


def _run_segment(cfg: ArchConfig, stacked, h, positions):
    """Scan the period body over a slice of the stacked period params."""
    def body(carry, pp):
        return _period_apply(cfg, pp, carry, positions), None

    body = _remat(cfg, body)
    h, _ = jax.lax.scan(body, h, stacked)
    return h


def _segments(cfg: ArchConfig):
    bounds = [0] + list(cfg.exit_layer_list) + [cfg.n_periods]
    return list(zip(bounds[:-1], bounds[1:]))


def _slice_periods(stacked, a: int, b: int):
    return jax.tree.map(lambda x: x[a:b], stacked)


# ---------------------------------------------------------------------------
# Embedding / frontend
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg: ArchConfig, batch: dict) -> jnp.ndarray:
    if cfg.frontend == "audio":
        # stub: precomputed frame embeddings [B, S, d]
        return batch["frames"]
    h = embed_apply(params["embed"], batch["tokens"])
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        P = batch["patch_embeds"].shape[1]
        h = jnp.concatenate([batch["patch_embeds"].astype(h.dtype),
                             h[:, P:]], axis=1)
    return h


# ---------------------------------------------------------------------------
# Train / encode
# ---------------------------------------------------------------------------

def forward_train(params, cfg: ArchConfig, batch: dict
                  ) -> Dict[str, jnp.ndarray]:
    """Full forward; returns logits at every exit + final. [B,S,V_pad]."""
    h = _embed_inputs(params, cfg, batch)
    B, S = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    head = _lm_head_params(params, cfg)
    out: Dict[str, jnp.ndarray] = {}
    for (a, b) in _segments(cfg):
        h = _run_segment(cfg, _slice_periods(params["layers"], a, b),
                         h, positions)
        if b < cfg.n_periods:
            out[f"exit_{b}"] = exit_head_apply(params["exits"][f"exit_{b}"],
                                               cfg, h, head)
    hn = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    out["final"] = lm_head_apply(head, hn, cfg.vocab_size)
    return out


def encode(params, cfg: ArchConfig, batch: dict) -> jnp.ndarray:
    """Encoder-only forward (hubert): final-layer frame logits."""
    return forward_train(params, cfg, batch)["final"]


def forward_hiddens(params, cfg: ArchConfig, batch: dict
                    ) -> Dict[str, jnp.ndarray]:
    """Like forward_train but returns *normed hidden states* per head
    instead of logits — the memory-safe path for the training loss."""
    h = _embed_inputs(params, cfg, batch)
    B, S = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    out: Dict[str, jnp.ndarray] = {}
    for (a, b) in _segments(cfg):
        h = _run_segment(cfg, _slice_periods(params["layers"], a, b),
                         h, positions)
        if b < cfg.n_periods:
            ep = params["exits"][f"exit_{b}"]
            out[f"exit_{b}"] = rmsnorm(ep["norm"], h, cfg.norm_eps)
    out["final"] = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return out


def loss_fn(params, cfg: ArchConfig, batch: dict,
            *, exit_weight: float = 0.3, ce_chunk: int = 256) -> jnp.ndarray:
    """BranchyNet-style joint loss: CE at the final head + weighted exits.

    Uses sequence-chunked cross-entropy so full-sequence logits are never
    materialized (O(40 GB) at 150k vocab — see layers.chunked_cross_entropy).
    """
    from .layers import chunked_cross_entropy

    hiddens = forward_hiddens(params, cfg, batch)
    labels = batch["labels"]
    head = _lm_head_params(params, cfg)

    def head_w(name):
        if name == "final":
            return head["w"]
        ep = params["exits"][name]
        return ep["head"]["w"] if "head" in ep else head["w"]

    total = chunked_cross_entropy(hiddens["final"], head_w("final"), labels,
                                  cfg.vocab_size, chunk=ce_chunk)
    wsum = 1.0
    for name, hh in hiddens.items():
        if name != "final":
            total = total + exit_weight * chunked_cross_entropy(
                hh, head_w(name), labels, cfg.vocab_size, chunk=ce_chunk)
            wsum += exit_weight
    return total / wsum


# ---------------------------------------------------------------------------
# KV / SSM caches
# ---------------------------------------------------------------------------

def _layer_cache_init(cfg: ArchConfig, spec: LayerSpec, batch: int,
                      seq_len: int, dtype):
    if spec.kind == "attn":
        return ATT.cache_spec(cfg, batch, seq_len).init(dtype)
    return SSM.ssm_cache_init(cfg, batch, dtype)


def init_caches(cfg: ArchConfig, batch: int, seq_len: int) -> dict:
    """Zeroed decode caches, stacked per period (scan layout)."""
    dtype = dtype_of(cfg.dtype)
    per_period = {f"l{i}": _layer_cache_init(cfg, spec, batch, seq_len, dtype)
                  for i, spec in enumerate(cfg.pattern)}
    n = cfg.n_periods
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(),
                        per_period)


def cache_shape_dtypes(cfg: ArchConfig, batch: int, seq_len: int) -> dict:
    """ShapeDtypeStruct pytree mirroring init_caches (for the dry-run)."""
    dtype = dtype_of(cfg.dtype)
    per_period = {}
    for i, spec in enumerate(cfg.pattern):
        if spec.kind == "attn":
            per_period[f"l{i}"] = ATT.cache_spec(cfg, batch, seq_len
                                                 ).shape_dtype(dtype)
        else:
            shapes = SSM.ssm_cache_shape(cfg, batch)
            per_period[f"l{i}"] = {
                "state": jax.ShapeDtypeStruct(shapes["state"], F32),
                "conv": jax.ShapeDtypeStruct(shapes["conv"], dtype)}
    n = cfg.n_periods
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), per_period)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def _period_decode(cfg: ArchConfig, pp: dict, h, cache: dict, pos):
    new_cache = {}
    for i, spec in enumerate(cfg.pattern):
        p = pp[f"l{i}"]
        hn = rmsnorm(p["norm1"], h, cfg.norm_eps)
        if spec.kind == "attn":
            y, new_cache[f"l{i}"] = ATT.attn_decode_step(
                p["mix"], cfg, hn, cache[f"l{i}"], pos)
        else:
            y, new_cache[f"l{i}"] = SSM.ssm_decode_step(
                p["mix"], cfg, hn, cache[f"l{i}"])
        h = h + y
        if spec.mlp != "none":
            hn = rmsnorm(p["norm2"], h, cfg.norm_eps)
            h = h + (mlp_apply(p["mlp"], hn) if spec.mlp == "dense"
                     else MOE.moe_apply(p["mlp"], cfg, hn))
    return h, new_cache


def decode_step(params, cfg: ArchConfig, tokens, caches: dict, pos
                ) -> Tuple[jnp.ndarray, dict, Dict[str, jnp.ndarray]]:
    """One decode step.  tokens: [B,1] int32; pos: scalar int32 (0-based
    index of the position being generated); caches from init_caches/prefill.

    Returns (final logits [B,V_pad], new caches, exit logits {name: [B,V]}).
    """
    assert cfg.has_decoder, f"{cfg.name} is encoder-only"
    h = embed_apply(params["embed"], tokens)
    head = _lm_head_params(params, cfg)

    def body(carry, xs):
        pp, cache = xs
        hh, new_cache = _period_decode(cfg, pp, carry, cache, pos)
        return hh, (new_cache, hh)

    # one scan over the whole stack: the exits read the per-period hidden
    # states it emits, so no segment slices of the weights or caches (each
    # a full copy inside the step) are ever materialized
    h, (new_caches, hs) = jax.lax.scan(body, h, (params["layers"], caches))
    exits: Dict[str, jnp.ndarray] = {
        f"exit_{b}": exit_head_apply(params["exits"][f"exit_{b}"], cfg,
                                     hs[b - 1], head)[:, 0]
        for b in cfg.exit_layer_list}
    hn = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = lm_head_apply(head, hn, cfg.vocab_size)[:, 0]
    return logits, new_caches, exits


# ---------------------------------------------------------------------------
# Prefill (prompt -> caches), runtime-engine path
# ---------------------------------------------------------------------------

def prefill(params, cfg: ArchConfig, batch: dict, cache_len: int
            ) -> Tuple[jnp.ndarray, dict]:
    """Run the prompt, building decode caches.  Returns (last-position final
    logits [B,V_pad], caches).  Implemented by replaying the full-sequence
    forward and extracting K/V (exactness tested vs step-by-step decode)."""
    h = _embed_inputs(params, cfg, batch)
    B, S = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    dtype = dtype_of(cfg.dtype)
    head = _lm_head_params(params, cfg)

    def body(carry, pp):
        hh = carry
        new_cache = {}
        for i, spec in enumerate(cfg.pattern):
            p = pp[f"l{i}"]
            hn = rmsnorm(p["norm1"], hh, cfg.norm_eps)
            if spec.kind == "attn":
                q, k, v = ATT._project_qkv(p["mix"], cfg, hn, positions)
                o = ATT.chunked_attention(
                    q, k, v, positions[0], positions[0], causal=cfg.causal,
                    window=cfg.sliding_window, chunk=cfg.attn_chunk)
                y = jnp.einsum("bshk,hkd->bsd", o, p["mix"]["wo"],
                               preferred_element_type=F32).astype(hh.dtype)
                spec_c = ATT.cache_spec(cfg, B, cache_len)
                T = spec_c.max_len
                cache_i = spec_c.init(dtype)
                cpos = cache_i["pos"]
                take = min(S, T)
                src_pos = positions[0, S - take:]
                slots = src_pos % T
                k_tail, v_tail = k[:, S - take:], v[:, S - take:]
                if spec_c.quantized:
                    kq, ks = ATT._quantize_kv(k_tail)
                    vq, vs = ATT._quantize_kv(v_tail)
                    cache_i["k"] = cache_i["k"].at[:, slots].set(kq)
                    cache_i["v"] = cache_i["v"].at[:, slots].set(vq)
                    cache_i["k_scale"] = cache_i["k_scale"].at[:, slots].set(ks)
                    cache_i["v_scale"] = cache_i["v_scale"].at[:, slots].set(vs)
                else:
                    cache_i["k"] = cache_i["k"].at[:, slots].set(
                        k_tail.astype(dtype))
                    cache_i["v"] = cache_i["v"].at[:, slots].set(
                        v_tail.astype(dtype))
                cache_i["pos"] = cpos.at[slots].set(src_pos)
                new_cache[f"l{i}"] = cache_i
            else:
                y_full, state = SSM.ssm_apply_with_state(p["mix"], cfg, hn)
                y = y_full
                new_cache[f"l{i}"] = state
            hh = hh + y
            if spec.mlp != "none":
                hn = rmsnorm(p["norm2"], hh, cfg.norm_eps)
                hh = hh + (mlp_apply(p["mlp"], hn) if spec.mlp == "dense"
                           else MOE.moe_apply(p["mlp"], cfg, hn))
        return hh, new_cache

    h, caches = jax.lax.scan(body, h, params["layers"])
    hn = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = lm_head_apply(head, hn[:, -1:], cfg.vocab_size)[:, 0]
    return logits, caches
