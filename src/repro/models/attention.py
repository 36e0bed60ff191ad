"""Attention: GQA/MQA with RoPE, optional qk-norm, causal / sliding-window /
bidirectional masks; chunked online-softmax for train/prefill (O(S*chunk)
memory instead of O(S^2)) and a KV-cache decode step (ring buffer for SWA).

The chunked formulation is the pure-JAX (lax.scan) flash-attention analogue.
At decode, attention runs the Pallas `decode_attn` kernel (kernels/decode_attn):
it reads the stacked [L, B, T, KV, D] cache in place, each row only to its
own depth, and is validated against `decode_attention` here, which int8
caches and partitioned programs keep (`reads_in_place`).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.decode_attn.ops import decode_attn
from repro.sharding import context as shard_ctx

from .layers import F32, apply_rope, dense_init, rmsnorm, rmsnorm_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ArchConfig, dtype) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params = {
        "wq": dense_init(k1, (d, H, hd), d, dtype),
        "wk": dense_init(k2, (d, KV, hd), d, dtype),
        "wv": dense_init(k3, (d, KV, hd), d, dtype),
        "wo": dense_init(k4, (H, hd, d), H * hd, dtype),
    }
    if cfg.qk_norm:
        params["q_norm"] = rmsnorm_init(hd, dtype)
        params["k_norm"] = rmsnorm_init(hd, dtype)
    return params


def _project_qkv(params, cfg: ArchConfig, x, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"], preferred_element_type=F32)
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"], preferred_element_type=F32)
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"],
                   preferred_element_type=F32).astype(x.dtype)
    q, k = q.astype(x.dtype), k.astype(x.dtype)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (train / prefill)
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, causal: bool, window: int):
    """[Sq, Sk] additive bias implementing causal / SWA / bidirectional."""
    dq = q_pos[:, None]
    dk = k_pos[None, :]
    ok = jnp.broadcast_to(dk >= 0, (dq.shape[0], dk.shape[1]))  # pad slots < 0
    if causal:
        ok &= dk <= dq
    if window > 0:
        ok &= dk > dq - window
    return jnp.where(ok, 0.0, NEG_INF)


def chunked_attention(q, k, v, q_pos, k_pos, *, causal: bool, window: int,
                      chunk: int) -> jnp.ndarray:
    """q: [B,Sq,H,D]; k/v: [B,Sk,KV,D]; returns [B,Sq,H,D].

    lax.scan over KV chunks with running (max, sum, acc) — flash-attention
    semantics with O(Sq * chunk) live memory.
    """
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    assert H % KV == 0
    G = H // KV
    scale = D ** -0.5
    if Sk % chunk:
        pad = chunk - Sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=-10**9)
        Sk += pad
    n_chunks = Sk // chunk
    kc = k.reshape(B, n_chunks, chunk, KV, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, KV, D).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(n_chunks, chunk)

    qg = q.reshape(B, Sq, KV, G, D)

    def step(carry, inp):
        m, l, acc = carry                       # [B,Sq,KV,G], [..], [B,Sq,KV,G,D]
        kci, vci, pci = inp                     # [B,chunk,KV,D], ..., [chunk]
        s = jnp.einsum("bqkgd,bckd->bqkgc", qg, kci,
                       preferred_element_type=F32) * scale
        s = s + _mask_bias(q_pos, pci, causal, window)[:, None, None, :]
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p.astype(q.dtype), vci,
            preferred_element_type=F32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, KV, G), NEG_INF, F32)
    l0 = jnp.zeros((B, Sq, KV, G), F32)
    a0 = jnp.zeros((B, Sq, KV, G, D), F32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def attn_apply(params, cfg: ArchConfig, x, positions) -> jnp.ndarray:
    """Full-sequence attention (train / prefill). x: [B,S,d]."""
    q, k, v = _project_qkv(params, cfg, x, positions)
    out = chunked_attention(q, k, v, positions[0], positions[0],
                            causal=cfg.causal, window=cfg.sliding_window,
                            chunk=cfg.attn_chunk)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"],
                      preferred_element_type=F32).astype(x.dtype)


# ---------------------------------------------------------------------------
# KV cache + decode step
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class KVCacheSpec:
    """Cache geometry for one attention layer (ring buffer if SWA).

    Every batch row (serving slot) has its own position row ``pos`` [B, T]:
    the absolute position held in each cache entry, -1 where empty, so
    rows at different depths share one cache.

    ``quantized=True`` stores K/V as int8 with a per-(slot, kv-head) f32
    scale — 2x less HBM traffic on the decode hot path (the memory-bound
    roofline term of every decode cell; EXPERIMENTS §Perf/granite)."""
    batch: int
    max_len: int          # = min(seq_len, window) for SWA
    n_kv: int
    head_dim: int
    quantized: bool = False

    def _kv_dtype(self, dtype):
        return jnp.int8 if self.quantized else dtype

    def shape_dtype(self, dtype):
        shape = (self.batch, self.max_len, self.n_kv, self.head_dim)
        out = {"k": jax.ShapeDtypeStruct(shape, self._kv_dtype(dtype)),
               "v": jax.ShapeDtypeStruct(shape, self._kv_dtype(dtype)),
               "pos": jax.ShapeDtypeStruct((self.batch, self.max_len),
                                           jnp.int32)}
        if self.quantized:
            sshape = (self.batch, self.max_len, self.n_kv)
            out["k_scale"] = jax.ShapeDtypeStruct(sshape, F32)
            out["v_scale"] = jax.ShapeDtypeStruct(sshape, F32)
        return out

    def init(self, dtype):
        return {k: jnp.full(s.shape, -1 if k == "pos" else 0, s.dtype)
                for k, s in self.shape_dtype(dtype).items()}


def cache_spec(cfg: ArchConfig, batch: int, seq_len: int) -> KVCacheSpec:
    max_len = seq_len if cfg.sliding_window == 0 else min(seq_len,
                                                          cfg.sliding_window)
    return KVCacheSpec(batch, max_len, cfg.n_kv_heads, cfg.head_dim_,
                       quantized=cfg.kv_cache_dtype == "int8")


def _quantize_kv(x):
    """x: [B, S, KV, D] -> (int8 [B,S,KV,D], scale f32 [B,S,KV])."""
    scale = jnp.max(jnp.abs(x.astype(F32)), axis=-1) / 127.0
    q = jnp.round(x.astype(F32) / jnp.maximum(scale, 1e-8)[..., None])
    return q.astype(jnp.int8), scale


def _dequantize_kv(q, scale, dtype):
    return (q.astype(F32) * scale[..., None]).astype(dtype)


def kv_entries(cache: dict, k, v) -> dict:
    """The cache entries that store ``k``/``v`` ([..., KV, D]): int8 values
    and their scales for a quantized cache, else the values in the cache's
    own dtype."""
    if "k_scale" in cache:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    return {"k": k.astype(cache["k"].dtype), "v": v.astype(cache["v"].dtype)}


def decode_attention(q, k_cache, v_cache, cache_pos, pos, *, window: int
                     ) -> jnp.ndarray:
    """One-token attention over the cache.

    q: [B,1,H,D]; caches: [B,T,KV,D]; cache_pos: [B,T] (or [T], shared)
    absolute position of each entry (-1 = empty); pos: [B] (or scalar) the
    position each row is decoding.  Reference implementation for the
    Pallas ``decode_attn`` kernel.
    """
    B, _, H, D = q.shape
    T, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = D ** -0.5
    cache_pos = jnp.broadcast_to(cache_pos, (B, T))
    pos = jnp.broadcast_to(pos, (B,))[:, None]
    qg = q.reshape(B, KV, G, D)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache,
                   preferred_element_type=F32) * scale
    ok = (cache_pos >= 0) & (cache_pos <= pos)
    if window > 0:
        ok &= cache_pos > pos - window
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p.astype(q.dtype), v_cache,
                     preferred_element_type=F32)
    return out.reshape(B, 1, H, D).astype(q.dtype)


def reads_in_place(cache: dict) -> bool:
    """Whether decode attention over ``cache`` (a stack of ``KVCacheSpec``
    entries) runs the Pallas ``decode_attn`` kernel, which reads each row's
    live blocks in place from the stack: a cache in the compute dtype in an
    unpartitioned program.  An int8 cache (dequantized on read) and a
    program under an activation-sharding mesh (GSPMD does not partition a
    ``pallas_call``) take ``decode_attention`` on the layer's slab."""
    return "k_scale" not in cache and shard_ctx.current() is None


def attn_decode_step(params, cfg: ArchConfig, x, cache: dict, pos,
                     layer) -> Tuple[jnp.ndarray, dict]:
    """x: [B,1,d]; cache: the whole stack {"k","v","pos"[,"k_scale",
    "v_scale"]}, each [L, B, T, ...], of which this is layer ``layer``;
    pos: [B] int32, each row's current position (a scalar is shared by
    every row).

    Returns (out [B,1,d], updated stack).  Row b's new K/V lands at entry
    ``pos[b] % T`` (a ring buffer under SWA); int8 caches quantize it and
    dequantize on read.  Only the new entries are written, so a donated
    stack is updated in place; attention then reads the stack in place
    too (``reads_in_place``), each row to its own depth.
    """
    B = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q, k, v = _project_qkv(params, cfg, x, pos[:, None])
    T = cache["k"].shape[2]
    at = (layer, jnp.arange(B), pos % T)
    new = kv_entries(cache, k[:, 0], v[:, 0])
    new["pos"] = pos
    cache = {name: cache[name].at[at].set(val) for name, val in new.items()}
    if reads_in_place(cache):
        out = decode_attn(q[:, 0], cache["k"], cache["v"], cache["pos"],
                          layer, pos, window=cfg.sliding_window)[:, None]
    else:
        mine = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0,
                                                   keepdims=False), cache)
        if "k_scale" in mine:
            k_read = _dequantize_kv(mine["k"], mine["k_scale"], x.dtype)
            v_read = _dequantize_kv(mine["v"], mine["v_scale"], x.dtype)
        else:
            k_read, v_read = mine["k"], mine["v"]
        out = decode_attention(q, k_read, v_read, mine["pos"], pos,
                               window=cfg.sliding_window)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"],
                   preferred_element_type=F32).astype(x.dtype)
    return y, cache


def attn_flops_per_token(cfg: ArchConfig, kv_len: int) -> float:
    """Projections + scores + AV per token (decode roofline helper)."""
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    eff = kv_len if cfg.sliding_window == 0 else min(kv_len, cfg.sliding_window)
    proj = 2 * d * hd * (2 * KV + 2 * H)
    scores = 2 * H * hd * eff * 2
    return proj + scores
