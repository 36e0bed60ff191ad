"""Train / serve step builders + ``input_specs`` (the dry-run contract).

``build_train_step(cfg)``  -> step(state, batch) -> (state, metrics)
``build_serve_step(cfg)``  -> step(params, caches, tokens, pos [B]) ->
                              (logits, caches, exit_logits)
``build_encode_step(cfg)`` -> step(params, batch) -> logits   (encoder-only)

``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins for every
input of the corresponding step — weak-type-correct, shardable, and never
allocating (the multi-pod dry-run lowers against these).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, ArchConfig, ShapeSpec
from repro.models import transformer as T
from repro.models.layers import dtype_of
from repro.optim import AdamW, AdamWState, clip_by_global_norm


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------

def make_optimizer(cfg: ArchConfig) -> AdamW:
    return AdamW(lr=3e-4,
                 state_dtype=None if cfg.master_weights else "bfloat16")


def build_train_step(cfg: ArchConfig, *, clip_norm: float = 1.0):
    opt = make_optimizer(cfg)

    def train_step(state: dict, batch: dict) -> Tuple[dict, dict]:
        params, opt_state = state["params"], state["opt"]
        loss, grads = jax.value_and_grad(
            lambda p: T.loss_fn(p, cfg, batch))(params)
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        new_params, new_opt = opt.update(grads, opt_state, params)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "step": new_opt.step}
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def init_train_state(key, cfg: ArchConfig) -> dict:
    params = T.init_model(key, cfg)
    opt = make_optimizer(cfg)
    return {"params": params, "opt": opt.init(params)}


def train_state_shapes(cfg: ArchConfig):
    """ShapeDtypeStruct pytree of the train state — no allocation."""
    return jax.eval_shape(
        functools.partial(init_train_state, jax.random.key(0), cfg))


# ---------------------------------------------------------------------------
# Serve
# ---------------------------------------------------------------------------

def build_serve_step(cfg: ArchConfig):
    def serve_step(params, caches, tokens, pos):
        return T.decode_step(params, cfg, tokens, caches, pos)
    return serve_step


def build_encode_step(cfg: ArchConfig):
    def encode_step(params, batch):
        return T.encode(params, cfg, batch)
    return encode_step


def build_prefill_step(cfg: ArchConfig, cache_len: int):
    def prefill_step(params, batch):
        return T.prefill(params, cfg, batch, cache_len=cache_len)
    return prefill_step


def params_shapes(cfg: ArchConfig):
    return jax.eval_shape(functools.partial(T.init_model,
                                            jax.random.key(0), cfg))


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins per (arch x shape) cell
# ---------------------------------------------------------------------------

def batch_specs(cfg: ArchConfig, B: int, S: int) -> Dict[str, jax.ShapeDtypeStruct]:
    dt = dtype_of(cfg.dtype)
    specs: Dict[str, jax.ShapeDtypeStruct] = {}
    if cfg.frontend == "audio":
        specs["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
    else:
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        if cfg.frontend == "vision":
            specs["patch_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.n_patches, cfg.d_model), dt)
    specs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    return specs


def input_specs(cfg: ArchConfig, shape: ShapeSpec) -> dict:
    """All step inputs as ShapeDtypeStructs, keyed by step argument."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        return {"state": train_state_shapes(cfg),
                "batch": batch_specs(cfg, B, S)}
    if shape.kind == "prefill":
        b = batch_specs(cfg, B, S)
        b.pop("labels")
        return {"params": params_shapes(cfg), "batch": b}
    if shape.kind == "decode":
        assert cfg.has_decoder
        return {
            "params": params_shapes(cfg),
            "caches": T.cache_shape_dtypes(cfg, B, S),
            "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "pos": jax.ShapeDtypeStruct((B,), jnp.int32),
        }
    raise ValueError(shape.kind)


def step_for(cfg: ArchConfig, shape: ShapeSpec):
    """(callable, ordered argnames) for the cell's step function."""
    if shape.kind == "train":
        return build_train_step(cfg), ("state", "batch")
    if shape.kind == "prefill":
        if not cfg.has_decoder:
            return build_encode_step(cfg), ("params", "batch")

        def prefill_logits(params, batch):
            # lower prefill as pure forward (the cache write-back variant is
            # exercised by the runtime engine; shapes identical)
            return T.forward_train(params, cfg, batch)["final"][:, -1]
        return prefill_logits, ("params", "batch")
    if shape.kind == "decode":
        return build_serve_step(cfg), ("params", "caches", "tokens", "pos")
    raise ValueError(shape.kind)
