"""Core neural layers, functional style (pure JAX, no framework deps).

Parameters are pytrees of jnp arrays; every constructor returns
``(init_fn, logical_axes)`` compatible with layer stacking via
``jax.lax.scan``.  Activation sharding is annotated through
``repro.sharding.partition.constrain`` with logical axis names.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.sharding.partition import constrain


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def normal_init(key, shape, dtype, scale: float = 0.02):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def fan_in_init(key, shape, dtype):
    scale = 1.0 / math.sqrt(shape[0])
    return (scale * jax.random.normal(key, shape)).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: Optional[jax.Array], eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    return y.astype(dtype)


def layernorm(x: jax.Array, scale: Optional[jax.Array], bias: Optional[jax.Array],
              eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dtype)


def apply_norm(x: jax.Array, params: Dict[str, Any], kind: str) -> jax.Array:
    """kind in {rmsnorm, layernorm, nonparam_ln}."""
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"])
    if kind == "layernorm":
        return layernorm(x, params["scale"], params["bias"])
    if kind == "nonparam_ln":  # OLMo: no affine parameters
        return layernorm(x, None, None)
    raise ValueError(kind)


def init_norm(key, d: int, kind: str, dtype) -> Dict[str, Any]:
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    if kind == "nonparam_ln":
        return {}
    raise ValueError(kind)


def norm_axes(kind: str) -> Dict[str, Any]:
    if kind == "rmsnorm":
        return {"scale": ("embed_norm",)}
    if kind == "layernorm":
        return {"scale": ("embed_norm",), "bias": ("embed_norm",)}
    return {}


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    freqs = rope_frequencies(x.shape[-1], theta)          # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]                    # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (reference path; the Pallas flash kernel lives in repro.kernels)
# ---------------------------------------------------------------------------

def gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool, q_offset: jax.Array | int = 0,
                  sliding_window: Optional[int] = None,
                  kv_len: Optional[jax.Array] = None,
                  logit_softcap: Optional[float] = None) -> jax.Array:
    """Grouped-query attention.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D), Hq = G * Hkv.
    ``q_offset``: absolute position of q[0] (decode: cache length).
    ``kv_len``: number of valid kv entries (for padded caches).
    Returns (B, Sq, Hq, D).
    """
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B, Sq, Hkv, G, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if logit_softcap:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)

    Skv = k.shape[1]
    kv_pos = jnp.arange(Skv)
    q_pos = jnp.arange(Sq) + q_offset
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask &= kv_pos[None, :] <= q_pos[:, None]
    if sliding_window is not None:
        mask &= kv_pos[None, :] > (q_pos[:, None] - sliding_window)
    if kv_len is not None:
        mask &= kv_pos[None, :] < kv_len
    logits = jnp.where(mask[None, None, None], logits, -1e30)

    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    sliding_window: Optional[int] = None
    causal: bool = True
    use_rope: bool = True
    norm_eps: float = 1e-6


def init_attention(key, cfg: AttentionConfig, dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": fan_in_init(ks[0], (d, H * hd), dtype),
        "wk": fan_in_init(ks[1], (d, KV * hd), dtype),
        "wv": fan_in_init(ks[2], (d, KV * hd), dtype),
        "wo": fan_in_init(ks[3], (H * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attention_axes(cfg: AttentionConfig) -> Dict[str, Any]:
    p = {
        "wq": ("embed", "qkv_out"),
        "wk": ("embed", "kv_out"),
        "wv": ("embed", "kv_out"),
        "wo": ("qkv_out", "embed"),
    }
    if cfg.qkv_bias:
        p.update({"bq": ("qkv_out",), "bk": ("kv_out",), "bv": ("kv_out",)})
    if cfg.qk_norm:
        p.update({"q_norm": ("head_dim",), "k_norm": ("head_dim",)})
    return p


def project_qkv(params, x: jax.Array, cfg: AttentionConfig, *,
                positions: jax.Array,
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared QKV prologue for the dense and paged attention paths:
    projections (+ optional bias), head reshape, optional qk-norm,
    RoPE at ``positions``.  q: (B,S,H,hd); k, v: (B,S,KV,hd)."""
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, params["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_fwd(params, x: jax.Array, cfg: AttentionConfig, *,
                  positions: jax.Array,
                  kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
                  cache_index: Optional[jax.Array] = None,
                  kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
                  ) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """Attention with optional KV cache (decode) or KV override (cross-attn).

    x: (B, S, d).  kv_cache: (k, v) each (B, max_seq, KV, hd); new keys are
    inserted at ``cache_index`` and attention runs over the full cache.
    Returns (out, updated_cache).
    """
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    if kv_override is not None:
        # cross-attention: q-only projection, K/V precomputed elsewhere
        q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
        if cfg.qkv_bias:
            q = q + params["bq"]
        q = q.reshape(B, S, H, hd)
        if cfg.qk_norm:
            q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        if cfg.use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
        k, v = kv_override
        new_cache = None
        q_offset = 0
        kv_len = None
    else:
        q, k, v = project_qkv(params, x, cfg, positions=positions)
        if kv_cache is not None:
            ck, cv = kv_cache
            ck = lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_index, axis=1)
            cv = lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_index, axis=1)
            k, v = ck, cv
            new_cache = (ck, cv)
            q_offset = cache_index
            kv_len = cache_index + S
        else:
            new_cache = None
            q_offset = 0
            kv_len = None

    q = constrain(q, "batch", "seq_attn", "heads", "head_dim")
    k = constrain(k, "batch", "seq_kv", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq_kv", "kv_heads", "head_dim")

    out = gqa_attention(q, k, v, causal=cfg.causal, q_offset=q_offset,
                        sliding_window=cfg.sliding_window, kv_len=kv_len)
    out = constrain(out, "batch", "seq_attn", "heads", "head_dim")
    out = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * hd), params["wo"])
    return out, new_cache


def attention_fwd_paged(params, x: jax.Array, cfg: AttentionConfig, *,
                        positions: jax.Array,
                        k_pages: jax.Array, v_pages: jax.Array,
                        page_table: jax.Array, lengths: jax.Array,
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Decode attention over a *paged* KV pool (one layer's pages).

    x: (B, 1, d) — one new token per sequence.  k/v pages: (P, ps, KV, hd),
    the shared physical page pool for this layer.  page_table: (B, PMAX)
    int32 logical->physical ids.  lengths: (B,) current KV length per
    sequence — also the write position of this token (idle rows carry
    length 0 and a page table full of trash-page ids; their writes land
    in the trash page and their output is ignored by the caller).

    The new token's K/V is scattered into each row's current page, then
    the Pallas kernel gathers the whole prefix through the page table.
    Returns (out (B,1,d), k_pages, v_pages).
    """
    from repro.kernels.ops import paged_attention

    B, S, _ = x.shape
    assert S == 1, "paged attention serves decode (one token per step)"
    H, hd = cfg.n_heads, cfg.head_dim
    ps = k_pages.shape[1]

    q, k, v = project_qkv(params, x, cfg, positions=positions)

    # scatter this token's K/V into each row's current physical page
    phys = page_table[jnp.arange(B), lengths // ps]        # (B,)
    off = lengths % ps
    k_pages = k_pages.at[phys, off].set(k[:, 0].astype(k_pages.dtype))
    v_pages = v_pages.at[phys, off].set(v[:, 0].astype(v_pages.dtype))

    out = paged_attention(q, k_pages, v_pages, page_table, lengths + 1,
                          sliding_window=cfg.sliding_window)
    out = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * hd), params["wo"])
    return out, k_pages, v_pages


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MLPConfig:
    d_model: int
    d_ff: int
    activation: str = "silu"   # silu (SwiGLU-gated) | gelu (plain)
    gated: bool = True


def init_mlp(key, cfg: MLPConfig, dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 3)
    p = {"w_up": fan_in_init(ks[0], (cfg.d_model, cfg.d_ff), dtype),
         "w_down": fan_in_init(ks[1], (cfg.d_ff, cfg.d_model), dtype)}
    if cfg.gated:
        p["w_gate"] = fan_in_init(ks[2], (cfg.d_model, cfg.d_ff), dtype)
    return p


def mlp_axes(cfg: MLPConfig) -> Dict[str, Any]:
    p = {"w_up": ("embed", "ff"), "w_down": ("ff", "embed")}
    if cfg.gated:
        p["w_gate"] = ("embed", "ff")
    return p


def mlp_fwd(params, x: jax.Array, cfg: MLPConfig) -> jax.Array:
    up = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    up = constrain(up, "batch", "seq_q", "ff")
    if cfg.gated:
        gate = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
        act = jax.nn.silu(gate) if cfg.activation == "silu" else jax.nn.gelu(gate)
        h = act * up
    else:
        h = jax.nn.gelu(up) if cfg.activation == "gelu" else jax.nn.silu(up)
    return jnp.einsum("bsf,fd->bsd", h, params["w_down"])


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype):
    return {"table": normal_init(key, (vocab, d), dtype)}


def embedding_axes():
    return {"table": ("vocab", "embed")}


def embed(params, tokens: jax.Array) -> jax.Array:
    out = jnp.take(params["table"], tokens, axis=0)
    return constrain(out, "batch", "seq_q", "embed")


def unembed(params, x: jax.Array, vocab: Optional[int] = None) -> jax.Array:
    logits = jnp.einsum("bsd,vd->bsv", x, params["table"])
    logits = constrain(logits, "batch", "seq_q", "vocab")
    if vocab is not None and vocab != logits.shape[-1]:
        logits = logits[..., :vocab]
    return logits


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token cross entropy.  logits: (B,S,V), labels: (B,S)."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    # the gold logit as a masked sum over the (possibly model-sharded)
    # vocab axis, not a gather: XLA's SPMD partitioner CHECK-fails on the
    # batched gather when the pod axis is manual (hierarchical dp) and
    # the vocab axis is sharded
    vocab_ids = jnp.arange(logits.shape[-1], dtype=labels.dtype)
    gold = jnp.sum(jnp.where(labels[..., None] == vocab_ids, logits, 0.0),
                   axis=-1)
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
