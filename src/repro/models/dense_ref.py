"""Plain float32 reference of the dense decoder (Qwen3's layer equations).

A straightforward ``jax.numpy`` forward pass of one sequence, written from
the published description and not from the program's modules: token
embedding; per layer RMSNorm, q/k/v projections, RMSNorm on each query and
key head (QK-norm), rotary position embedding (rotate-half form, base
``rope_theta``), causal grouped-query attention, output projection, a
residual, RMSNorm, a SwiGLU MLP and a residual; a final RMSNorm and the LM
head.  Every matrix product runs in float32 under
``jax.default_matmul_precision("highest")``.  No cache, no kernels, no
batching: positions ``[0, S)`` of one token sequence.

Departures from the published model:

* the early-exit heads are this system's own: after each layer in
  ``exits`` an RMSNorm with its own scale over the LM head (tied to the
  embedding table when the model ties it);
* the weights are the program's pytree (random, from a seed), upcast to
  float32 one layer at a time, so that at published widths the reference
  fits on the chip beside the program's bf16 weights;
* attention is computed in blocks of queries, so that the score matrix of a
  4,096-token sequence never exists whole (the same sums, in another
  order);
* a sequence is padded with token 0 to a power of two of at least ``PAD``
  positions, so that sequences of many lengths share one compile;
  attention is causal, so the padding changes no row before it.

``forward(params, dims, tokens, at=...)`` returns ``{"final": [R, V],
"exit_<l>": [R, V]}`` float32 logits over the unpadded vocabulary at the
positions ``at`` (every position by default).  ``cast`` runs the same
equations on weights rounded through another dtype first (float8 e4m3 in
the benchmark's control).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: sequences are padded to a power of two of at least this many positions
PAD = 512
#: queries per attention block
Q_BLOCK = 512
#: the rows read are padded to a multiple of this many (one compile each)
ROWS = 64


@dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab_size: int
    norm_eps: float
    rope_theta: float
    exits: Tuple[int, ...]       # layers after which an exit head reads
    tied: bool


def dims_of(cfg) -> Dims:
    """The reference's dimensions of an ``ArchConfig`` of a dense model."""
    assert [(s.kind, s.mlp) for s in cfg.pattern] == [("attn", "dense")]
    assert cfg.sliding_window == 0 and cfg.causal
    return Dims(cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.head_dim_, cfg.vocab_size, cfg.norm_eps, cfg.rope_theta,
                tuple(cfg.exit_layer_list), cfg.tie_embeddings)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    """x: [S, H, D]; rotate the two halves of each head by pos * freq."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None].astype(F32) * freq                  # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _up(w, cast):
    w = w if cast is None else w.astype(cast)
    return w.astype(F32)


def _layer(w, h, dims: Dims, cast):
    """One decoder layer on h [S, d] (float32); w: this layer's weights."""
    w = jax.tree.map(lambda a: _up(a, cast), w)
    a, eps = w["mix"], dims.norm_eps
    S = h.shape[0]
    pos = jnp.arange(S)
    x = _rms(h, w["norm1"]["scale"], eps)
    q = jnp.einsum("sd,dhk->shk", x, a["wq"])
    k = jnp.einsum("sd,dhk->shk", x, a["wk"])
    v = jnp.einsum("sd,dhk->shk", x, a["wv"])
    if "q_norm" in a:
        q = _rms(q, a["q_norm"]["scale"], eps)
        k = _rms(k, a["k_norm"]["scale"], eps)
    q, k = _rope(q, pos, dims.rope_theta), _rope(k, pos, dims.rope_theta)
    g = dims.n_heads // dims.n_kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)   # [S, H, D]

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhd,thd->hqt", qb, k) / np.sqrt(dims.head_dim)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqt,thd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(q.shape)
    h = h + jnp.einsum("shk,hkd->sd", o, a["wo"])
    x = _rms(h, w["norm2"]["scale"], eps)
    m = w["mlp"]
    return h + jnp.einsum(
        "sf,fd->sd", jax.nn.silu(x @ m["w_gate"]) * (x @ m["w_up"]),
        m["w_down"])


def _logits(x, norm, head, dims: Dims, cast):
    """x: [R, d] -> [R, V] over the unpadded vocabulary."""
    w = _up(head, cast)[:dims.vocab_size]          # [V, d] (table layout)
    return _rms(x, _up(norm, cast), dims.norm_eps) @ w.T


_layer_jit = jax.jit(_layer, static_argnums=(2, 3))
_logits_jit = jax.jit(_logits, static_argnums=(3, 4))


def forward(params, dims: Dims, tokens: Sequence[int],
            at: Optional[Sequence[int]] = None, *, cast=None
            ) -> Dict[str, np.ndarray]:
    """Logits of every head at positions ``at`` of ``tokens``."""
    n = len(tokens)
    S = max(PAD, 1 << (n - 1).bit_length())
    at = np.arange(n) if at is None else np.asarray(at, np.int64)
    assert at.size and 0 <= at.min() and at.max() < n
    R = at.size
    rows = jnp.asarray(np.resize(at, -(-R // ROWS) * ROWS))
    toks = np.zeros(S, np.int32)
    toks[:n] = tokens
    table = params["embed"]["table"]
    head = table if dims.tied else params["lm_head"]["w"].T
    out: Dict[str, np.ndarray] = {}
    with jax.default_matmul_precision("highest"):
        h = _up(table[jnp.asarray(toks)], cast)
        for layer in range(dims.n_layers):
            w = jax.tree.map(lambda a: a[layer], params["layers"]["l0"])
            h = _layer_jit(w, h, dims, cast)
            if layer + 1 in dims.exits:
                norm = params["exits"][f"exit_{layer + 1}"]["norm"]["scale"]
                out[f"exit_{layer + 1}"] = np.asarray(_logits_jit(
                    h[rows], norm, head, dims, cast))[:R]
        out["final"] = np.asarray(_logits_jit(
            h[rows], params["final_norm"]["scale"], head, dims, cast))[:R]
    return out
