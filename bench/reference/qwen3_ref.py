"""Plain float32 reference of the served dense decoder, and the comparison
that decides ``correct`` in the serving cells.

Imports nothing of the program under test.  ``forward`` is a
straightforward ``jax.numpy`` pass over one sequence, written from the
published description of Qwen3 (huggingface.co/Qwen/Qwen3-4B): token
embedding; per layer RMSNorm, q/k/v projections, RMSNorm on each query and
key head, rotary embedding (rotate-half form, base ``rope_theta``), causal
grouped-query attention, output projection and residual, RMSNorm, SwiGLU
MLP and residual; final RMSNorm and the LM head, tied to the embedding
table.  Every product runs in float32 under
``jax.default_matmul_precision("highest")``.

``weights`` draws the random weights both sides run, from the seed and
independent of the program's own initialiser: every matrix normal over
the square root of its fan-in, every RMSNorm scale ``1 + NORM_SD`` times a
normal draw, so that a scale never applied, or applied to the wrong
tensor, moves the logits.  They come in the layout of the program's
parameter pytree, which the driver checks against the program's shapes.

Departures: the early-exit heads (an RMSNorm over the tied head after each
layer in ``exits``) are the served system's own; the weights are random,
upcast one layer at a time so that the reference fits beside them on the
chip; attention runs in blocks of queries; sequences are padded to a power
of two of at least 512 positions (causal, so no earlier row changes) and
the rows read to a multiple of 64, so that many lengths share one compile.
``cast`` rounds every weight through another dtype first: float8 e4m3 is
the control.

``Judge`` compares the program's logits with the reference's, row by row
over every head: ``logit_rel_rms`` (the RMS of the difference over the RMS
of the reference, over every compared row) and ``top1_flips`` (rows whose
argmax differs although the reference's top-1 margin exceeds
``flip_margin``).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
PAD, Q_BLOCK, ROWS = 512, 512, 64
#: spread of the RMSNorm scales ``weights`` draws around 1
NORM_SD = 0.1


@dataclass(frozen=True)
class Dims:
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab_size: int
    norm_eps: float
    rope_theta: float
    exits: Tuple[int, ...]


def dims_from(model: dict, exits: Sequence[int]) -> Dims:
    """From a configuration file's ``model`` block (Hugging Face keys)."""
    return Dims(int(model["num_hidden_layers"]),
                int(model["num_attention_heads"]),
                int(model["num_key_value_heads"]), int(model["head_dim"]),
                int(model["vocab_size"]), float(model["rms_norm_eps"]),
                float(model["rope_theta"]), tuple(int(e) for e in exits))


@partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, fan_in, dtype):
    z = jax.random.normal(key, shape, F32)
    w = 1.0 + NORM_SD * z if fan_in is None else z / np.sqrt(fan_in)
    return w.astype(dtype)


def weights(model: dict, exits: Sequence[int], vocab_rows: int, seed: int,
            dtype) -> dict:
    """Random weights of the decoder a ``model`` block describes, drawn from
    ``seed`` in the program's layout: ``{"embed": {"table"}, "layers":
    {"l0": ...}, "final_norm", "exits": {"exit_<l>": {"norm"}}}``, each
    layer tensor stacked over the layers.  ``vocab_rows`` is the table's
    row count (the vocabulary padded as the program pads it)."""
    d, ff = int(model["hidden_size"]), int(model["intermediate_size"])
    L, H = int(model["num_hidden_layers"]), int(model["num_attention_heads"])
    KV, hd = int(model["num_key_value_heads"]), int(model["head_dim"])

    def norm(*shape):
        return (shape, None)
    layer = {
        "mix": {"wq": ((L, d, H, hd), d), "wk": ((L, d, KV, hd), d),
                "wv": ((L, d, KV, hd), d), "wo": ((L, H, hd, d), H * hd),
                "q_norm": {"scale": norm(L, hd)},
                "k_norm": {"scale": norm(L, hd)}},
        "mlp": {"w_gate": ((L, d, ff), d), "w_up": ((L, d, ff), d),
                "w_down": ((L, ff, d), ff)},
        "norm1": {"scale": norm(L, d)}, "norm2": {"scale": norm(L, d)}}
    spec = {"embed": {"table": ((vocab_rows, d), d)},
            "layers": {"l0": layer}, "final_norm": {"scale": norm(d)},
            "exits": {f"exit_{e}": {"norm": {"scale": norm(d)}}
                      for e in exits}}
    leaves, tree = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple))
    key = jax.random.PRNGKey(seed)
    return jax.tree.unflatten(tree, [
        _draw(jax.random.fold_in(key, i), shape, fan, dtype)
        for i, (shape, fan) in enumerate(leaves)])


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None].astype(F32) * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _up(w, cast):
    return (w if cast is None else w.astype(cast)).astype(F32)


def _layer(w, h, dims: Dims, cast):
    w = jax.tree.map(lambda a: _up(a, cast), w)
    a, eps = w["mix"], dims.norm_eps
    S = h.shape[0]
    pos = jnp.arange(S)
    x = _rms(h, w["norm1"]["scale"], eps)
    q = jnp.einsum("sd,dhk->shk", x, a["wq"])
    k = jnp.einsum("sd,dhk->shk", x, a["wk"])
    v = jnp.einsum("sd,dhk->shk", x, a["wv"])
    q = _rope(_rms(q, a["q_norm"]["scale"], eps), pos, dims.rope_theta)
    k = _rope(_rms(k, a["k_norm"]["scale"], eps), pos, dims.rope_theta)
    g = dims.n_heads // dims.n_kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)

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


def _logits(x, norm, table, dims: Dims, cast):
    w = _up(table, cast)[:dims.vocab_size]
    return _rms(x, _up(norm, cast), dims.norm_eps) @ w.T


_layer_jit = jax.jit(_layer, static_argnums=(2, 3))
_logits_jit = jax.jit(_logits, static_argnums=(3, 4))


def forward(params, dims: Dims, tokens: Sequence[int],
            at: Sequence[int], *, cast=None) -> Dict[str, np.ndarray]:
    """{"final": [R, V], "exit_<l>": [R, V]} at positions ``at``."""
    n = len(tokens)
    S = max(PAD, 1 << (n - 1).bit_length())
    at = np.asarray(at, np.int64)
    R = at.size
    rows = jnp.asarray(np.resize(at, -(-R // ROWS) * ROWS))
    toks = np.zeros(S, np.int32)
    toks[:n] = tokens
    table = params["embed"]["table"]
    out: Dict[str, np.ndarray] = {}
    with jax.default_matmul_precision("highest"):
        h = _up(table[jnp.asarray(toks)], cast)
        for layer in range(dims.n_layers):
            w = jax.tree.map(lambda a: a[layer], params["layers"]["l0"])
            h = _layer_jit(w, h, dims, cast)
            if layer + 1 in dims.exits:
                norm = params["exits"][f"exit_{layer + 1}"]["norm"]["scale"]
                out[f"exit_{layer + 1}"] = np.asarray(
                    _logits_jit(h[rows], norm, table, dims, cast))[:R]
        out["final"] = np.asarray(_logits_jit(
            h[rows], params["final_norm"]["scale"], table, dims, cast))[:R]
    return out


class Judge:
    """Running comparison of program logits against reference logits."""

    def __init__(self, flip_margin: float):
        self.flip_margin = float(flip_margin)
        self.sq_err = self.sq_ref = 0.0
        self.rows = self.flips = 0
        self.max_gap = 0.0             # largest |program - reference|
        self.examples = []

    def compare(self, prog: Dict[str, np.ndarray],
                ref: Dict[str, np.ndarray], what: str = "") -> None:
        if set(prog) != set(ref):
            raise ValueError(f"{what}: heads {sorted(prog)} against the "
                             f"reference's {sorted(ref)}")
        for head, r in ref.items():
            p = np.asarray(prog[head], np.float64)
            r = np.asarray(r, np.float64)
            d = p - r
            self.sq_err += float((d * d).sum())
            self.sq_ref += float((r * r).sum())
            self.rows += len(r)
            self.max_gap = max(self.max_gap, float(np.abs(d).max()))
            top2 = np.sort(r, axis=-1)[:, -2:]
            margin = top2[:, 1] - top2[:, 0]
            flip = (p.argmax(-1) != r.argmax(-1)) & (margin > self.flip_margin)
            self.flips += int(flip.sum())
            if flip.any() and len(self.examples) < 5:
                self.examples.append(f"{what} {head}: {int(flip.sum())} "
                                     f"flipped rows")

    @property
    def rel_rms(self) -> float:
        if not self.rows:
            return float("inf")          # nothing compared: never correct
        return float(np.sqrt(self.sq_err / max(self.sq_ref, 1e-300)))
