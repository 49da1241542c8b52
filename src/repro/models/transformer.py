"""Dense decoder-only transformer LM (qwen1.5 / qwen3 / command-r / olmo /
pixtral-backbone) with scan-stacked layers, GQA, RoPE, and optional
QKV-bias / qk-norm / parallel-block / non-parametric-LN variants.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.unroll import scan_unroll
from repro.sharding.partition import constrain


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


def attn_config(cfg: ModelConfig, *, causal: bool = True,
                use_rope: bool = True) -> L.AttentionConfig:
    return L.AttentionConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm,
        rope_theta=cfg.rope_theta, sliding_window=cfg.sliding_window,
        causal=causal, use_rope=use_rope, norm_eps=cfg.norm_eps)


def mlp_config(cfg: ModelConfig) -> L.MLPConfig:
    return L.MLPConfig(d_model=cfg.d_model, d_ff=cfg.d_ff,
                       activation=cfg.mlp_activation, gated=cfg.mlp_gated)


# ---------------------------------------------------------------------------
# one transformer block
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    ka, km, k1, k2 = jax.random.split(key, 4)
    p = {
        "attn": L.init_attention(ka, attn_config(cfg), dtype),
        "mlp": L.init_mlp(km, mlp_config(cfg), dtype),
        "norm1": L.init_norm(k1, cfg.d_model, cfg.norm_type, dtype),
    }
    if not cfg.parallel_block:
        p["norm2"] = L.init_norm(k2, cfg.d_model, cfg.norm_type, dtype)
    return p


def block_axes(cfg: ModelConfig) -> Dict[str, Any]:
    p = {
        "attn": L.attention_axes(attn_config(cfg)),
        "mlp": L.mlp_axes(mlp_config(cfg)),
        "norm1": L.norm_axes(cfg.norm_type),
    }
    if not cfg.parallel_block:
        p["norm2"] = L.norm_axes(cfg.norm_type)
    return p


def block_fwd(params, x: jax.Array, cfg: ModelConfig, *,
              positions: jax.Array,
              kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
              cache_index: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    acfg = attn_config(cfg)
    h = L.apply_norm(x, params["norm1"], cfg.norm_type)
    attn_out, new_cache = L.attention_fwd(
        params["attn"], h, acfg, positions=positions,
        kv_cache=kv_cache, cache_index=cache_index)
    if cfg.parallel_block:
        # command-r style: MLP reads the same normed input, outputs add
        mlp_out = L.mlp_fwd(params["mlp"], h, mlp_config(cfg))
        x = x + attn_out + mlp_out
    else:
        x = x + attn_out
        h2 = L.apply_norm(x, params["norm2"], cfg.norm_type)
        x = x + L.mlp_fwd(params["mlp"], h2, mlp_config(cfg))
    x = constrain(x, "batch", "seq_q", "embed")
    return x, new_cache


def block_fwd_paged(params, x: jax.Array, cfg: ModelConfig, *,
                    positions: jax.Array, k_pages: jax.Array,
                    v_pages: jax.Array, layer: jax.Array,
                    page_table: jax.Array, lengths: jax.Array):
    """``block_fwd`` for decode over the stacked paged KV pool at
    ``layer`` (one token/row)."""
    acfg = attn_config(cfg)
    h = L.apply_norm(x, params["norm1"], cfg.norm_type)
    attn_out, k_pages, v_pages = L.attention_fwd_paged(
        params["attn"], h, acfg, positions=positions,
        k_pages=k_pages, v_pages=v_pages, layer=layer,
        page_table=page_table, lengths=lengths)
    if cfg.parallel_block:
        mlp_out = L.mlp_fwd(params["mlp"], h, mlp_config(cfg))
        x = x + attn_out + mlp_out
    else:
        x = x + attn_out
        h2 = L.apply_norm(x, params["norm2"], cfg.norm_type)
        x = x + L.mlp_fwd(params["mlp"], h2, mlp_config(cfg))
    return x, k_pages, v_pages


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = _dtype(cfg.param_dtype)
    ke, kl, kf = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_block(k, cfg, dtype))(layer_keys)
    p = {
        "embedding": L.init_embedding(ke, cfg.padded_vocab, cfg.d_model, dtype),
        "layers": stacked,
        "final_norm": L.init_norm(kf, cfg.d_model, cfg.norm_type, dtype),
    }
    return p


def param_axes(cfg: ModelConfig) -> Dict[str, Any]:
    def lift(tree):
        # stacked layers get a leading ("layers",) axis
        return jax.tree.map(lambda ax: ("layers",) + ax, tree,
                            is_leaf=lambda x: isinstance(x, tuple))
    return {
        "embedding": L.embedding_axes(),
        "layers": lift(block_axes(cfg)),
        "final_norm": L.norm_axes(cfg.norm_type),
    }


def cast_params(params, cfg: ModelConfig):
    """Cast float parameters to the compute dtype (master copies stay in
    the optimizer; norms/SSM scalars re-upcast internally where needed)."""
    dtype = _dtype(cfg.compute_dtype)
    return jax.tree.map(
        lambda w: w.astype(dtype) if jnp.issubdtype(w.dtype, jnp.floating) else w,
        params)


def _embed_inputs(params, cfg: ModelConfig, batch: Dict[str, jax.Array]) -> jax.Array:
    """Token embedding, or precomputed frontend embeddings (vlm stub)."""
    if "embeds" in batch:
        x = batch["embeds"].astype(_dtype(cfg.compute_dtype))
        return constrain(x, "batch", "seq_q", "embed")
    return L.embed(params["embedding"], batch["tokens"]).astype(
        _dtype(cfg.compute_dtype))


def forward(params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            cache: Optional[Dict[str, jax.Array]] = None,
            cache_index: Optional[jax.Array] = None,
            remat: bool = False) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Returns (hidden_states, updated_cache)."""
    params = cast_params(params, cfg)
    x = _embed_inputs(params, cfg, batch)
    B, S = x.shape[:2]
    if cache_index is None:
        positions = jnp.arange(S)[None, :].astype(jnp.int32)
    else:
        positions = (cache_index + jnp.arange(S))[None, :].astype(jnp.int32)

    def body(x, scanned):
        if cache is None:
            layer_params = scanned
            kv = None
        else:
            layer_params, ck, cv = scanned
            kv = (ck, cv)
        x, new_kv = block_fwd(layer_params, x, cfg, positions=positions,
                              kv_cache=kv, cache_index=cache_index)
        if cache is None:
            return x, None
        return x, new_kv

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)

    if cache is None:
        x, _ = lax.scan(body, x, params["layers"], unroll=scan_unroll())
        new_cache = None
    else:
        x, (nk, nv) = lax.scan(body, x, (params["layers"], cache["k"], cache["v"]),
                               unroll=scan_unroll())
        new_cache = {"k": nk, "v": nv}

    x = L.apply_norm(x, params["final_norm"], cfg.norm_type)
    return x, new_cache


def logits_fn(params, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    return L.unembed(params["embedding"], hidden, cfg.vocab)


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            remat: bool = True) -> jax.Array:
    hidden, _ = forward(params, cfg, batch, remat=remat)
    logits = logits_fn(params, cfg, hidden)
    return L.cross_entropy_loss(logits, batch["labels"], batch.get("mask"))


# ---------------------------------------------------------------------------
# KV cache management
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_axes() -> Dict[str, Any]:
    ax = ("layers", "batch", "seq_kv", "kv_heads", "head_dim")
    return {"k": ax, "v": ax}


def prefill(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            cache: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Run the prompt through the model, filling the cache; returns logits
    of the last position."""
    hidden, new_cache = forward(params, cfg, batch, cache=cache,
                                cache_index=jnp.int32(0), remat=True)
    logits = logits_fn(params, cfg, hidden[:, -1:, :])
    return logits, new_cache


def prefill_at(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
               cache: Dict[str, jax.Array], last_pos: jax.Array,
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Bucketed prefill: the prompt is right-padded to a bucket length,
    so the true next-token distribution sits at ``last_pos`` (the last
    *real* position), not at the padded end.  Causality keeps real
    positions blind to the trailing pads; pad K/V beyond ``last_pos``
    is garbage the consumer must mask (the paged engine never copies
    or attends past the real prompt length)."""
    hidden, new_cache = forward(params, cfg, batch, cache=cache,
                                cache_index=jnp.int32(0), remat=True)
    h_last = lax.dynamic_slice_in_dim(hidden, last_pos, 1, axis=1)
    return logits_fn(params, cfg, h_last), new_cache


def decode_step(params, cfg: ModelConfig, tokens: jax.Array,
                cache: Dict[str, jax.Array], cache_index: jax.Array,
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode: tokens (B, 1); cache_index: current length."""
    hidden, new_cache = forward(params, cfg, {"tokens": tokens},
                                cache=cache, cache_index=cache_index)
    logits = logits_fn(params, cfg, hidden)
    return logits, new_cache


def scan_layers_paged(block, layers, x: jax.Array,
                      pools: Dict[str, jax.Array], page_table: jax.Array,
                      lengths: jax.Array,
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Run ``block`` over the stacked ``layers`` for one decode token.

    The pool rides the layer loop as scan *carry* next to the layer
    index, so each layer writes its token into the one stacked pool and
    the kernel reads that layer in place: no layer of the pool is sliced
    out or stacked back, and under a jit that donates the pool the whole
    step updates it in place.  ``block(layer_params, x, positions=,
    k_pages=, v_pages=, layer=, page_table=, lengths=) -> (x, k, v)``.
    """
    positions = lengths[:, None].astype(jnp.int32)          # (B, 1)
    paged = dict(positions=positions, page_table=page_table,
                 lengths=lengths)

    def body(carry, scanned):
        x, kp, vp = carry
        layer_params, layer = scanned
        x, kp, vp = block(layer_params, x, k_pages=kp, v_pages=vp,
                          layer=layer, **paged)
        return (x, kp, vp), None

    n_layers = pools["k"].shape[0]
    (x, nk, nv), _ = lax.scan(
        body, (x, pools["k"], pools["v"]),
        (layers, jnp.arange(n_layers, dtype=jnp.int32)),
        unroll=scan_unroll())
    return x, {"k": nk, "v": nv}


def decode_paged(params, cfg: ModelConfig, tokens: jax.Array,
                 pools: Dict[str, jax.Array], page_table: jax.Array,
                 lengths: jax.Array,
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode over the shared paged KV pool.

    tokens: (B, 1); pools: {"k","v"} each (L, P, ps, KV, hd) — the
    device-side physical page pool shared by every sequence;
    page_table: (B, PMAX) int32 logical->physical; lengths: (B,) int32
    current KV length per row (idle rows: 0 + trash-page table entries).
    Returns (logits (B, 1, V), updated pools).
    """
    params = cast_params(params, cfg)
    x = _embed_inputs(params, cfg, {"tokens": tokens})
    x, pools = scan_layers_paged(
        partial(block_fwd_paged, cfg=cfg), params["layers"], x,
        pools, page_table, lengths)
    x = L.apply_norm(x, params["final_norm"], cfg.norm_type)
    return logits_fn(params, cfg, x), pools
