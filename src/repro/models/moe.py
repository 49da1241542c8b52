"""Token-choice top-k Mixture-of-Experts LM (mixtral-8x7b, olmoe-1b-7b).

Dispatch is sort-based with a capacity factor, performed within ``groups``
independent token groups.  Groups map 1:1 onto data shards of the mesh, so
dispatch compiles to shard-local sort/gather plus (for expert-parallel
layouts) a single all-to-all across the expert axis — the TPU-native
analogue of the paper's inter-cluster memory traffic consolidation.

Two expert sharding layouts (per-arch choice, see DESIGN.md):
  * ``ffn``    — every device holds all experts, each expert's d_ff is
                 tensor-sharded over the model axis (mixtral: 8 experts
                 don't divide a 16-way axis).
  * ``expert`` — experts sharded over the model axis (olmoe: 64 experts,
                 16-way EP, 4 experts per device).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.unroll import scan_unroll
from repro.sharding.partition import constrain


def init_moe_mlp(key, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    E, d, f = cfg.n_experts, cfg.d_model, (cfg.expert_d_ff or cfg.d_ff)
    kr, kg, ku, kd = jax.random.split(key, 4)
    scale = 1.0 / jnp.sqrt(d)
    return {
        "router": L.normal_init(kr, (d, E), jnp.float32, scale=0.02),
        "w_gate": (scale * jax.random.normal(kg, (E, d, f))).astype(dtype),
        "w_up": (scale * jax.random.normal(ku, (E, d, f))).astype(dtype),
        "w_down": ((1.0 / jnp.sqrt(f)) * jax.random.normal(kd, (E, f, d))).astype(dtype),
    }


def moe_mlp_axes() -> Dict[str, Any]:
    return {
        "router": ("embed", "expert_router"),
        "w_gate": ("expert", "embed", "expert_ff"),
        "w_up": ("expert", "embed", "expert_ff"),
        "w_down": ("expert", "expert_ff", "embed"),
    }


def moe_mlp_fwd(params, x: jax.Array, cfg: ModelConfig, *,
                groups: int = 1) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) → (out, aux_loss).

    Router in fp32; top-k gates renormalized (mixtral convention).
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T_total = B * S
    G = groups if T_total % groups == 0 else 1
    Tg = T_total // G

    xt = x.reshape(G, Tg, d)
    xt = constrain(xt, "moe_groups", None, "embed")

    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)                      # (G,Tg,E)
    gate_vals, expert_idx = lax.top_k(probs, k)                  # (G,Tg,k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch/Mixtral style)
    me = jnp.mean(probs, axis=1)                                  # (G,E)
    one_hot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)    # (G,Tg,k,E)
    ce = jnp.mean(jnp.sum(one_hot, axis=2), axis=1)               # (G,E)
    aux = E * jnp.mean(jnp.sum(me * ce, axis=-1))

    capacity = int(max(1, round(Tg * k / E * cfg.capacity_factor)))

    def dispatch_one(xg, eidx, gates):
        # xg: (Tg,d), eidx: (Tg,k), gates: (Tg,k)
        flat_e = eidx.reshape(-1)                                  # (Tg*k,)
        order = jnp.argsort(flat_e)
        sorted_e = flat_e[order]
        token_of = order // k
        counts = jnp.bincount(flat_e, length=E)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(Tg * k) - starts[sorted_e]
        keep = pos < capacity
        dest = jnp.where(keep, sorted_e * capacity + pos, E * capacity)
        # GATHER-based dispatch: scatter only the tiny int32 slot->token
        # map, then gather values.  A value scatter into the expert-
        # sharded buffer makes GSPMD all-reduce the FULL (Tg*k, d)
        # activation with a u32 companion (measured: 80% of this cell's
        # collective bytes); the value gather partitions cleanly because
        # xg is replicated across the expert axis (§Perf cell A, A6).
        slot_token = jnp.full((E * capacity + 1,), Tg, jnp.int32)
        slot_token = slot_token.at[dest].set(token_of)[:-1]        # (E*C,)
        xg_pad = jnp.concatenate([xg, jnp.zeros((1, d), xg.dtype)])
        buf = xg_pad[slot_token].reshape(E, capacity, d)
        return buf, (order, token_of, dest, keep)

    buf, meta = jax.vmap(dispatch_one)(xt, expert_idx, gate_vals)
    buf = constrain(buf, "moe_groups", "expert", None, "embed")

    # expert FFN (SwiGLU) — gecd,edf batched over experts
    gate = jnp.einsum("gecd,edf->gecf", buf, params["w_gate"])
    up = jnp.einsum("gecd,edf->gecf", buf, params["w_up"])
    h = jax.nn.silu(gate) * up
    h = constrain(h, "moe_groups", "expert", None, "expert_ff")
    out = jnp.einsum("gecf,efd->gecd", h, params["w_down"])
    out = constrain(out, "moe_groups", "expert", None, "embed")

    def combine_one(out_g, gates, m):
        # Accumulate in the COMPUTE dtype: the per-expert partial sums are
        # combined across the model axis by an all-reduce of the full
        # (Tg, d) activation -- fp32 accumulation would double the bytes on
        # the wire for a top-k sum that bf16 carries fine (see
        # EXPERIMENTS.md #Perf cell A, iteration A4).
        order, token_of, dest, keep = m
        flat = out_g.reshape(E * capacity, d).astype(x.dtype)
        gathered = flat[jnp.minimum(dest, E * capacity - 1)]
        w = (gates.reshape(-1)[order] * keep).astype(x.dtype)
        y = jnp.zeros((Tg, d), x.dtype)
        return y.at[token_of].add(gathered * w[:, None])

    y = jax.vmap(combine_one)(out, gate_vals, meta)
    y = constrain(y, "moe_groups", None, "embed")
    return y.reshape(B, S, d), aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# MoE transformer block / model (attention shared with dense transformer)
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    ka, km, k1, k2 = jax.random.split(key, 4)
    return {
        "attn": L.init_attention(ka, T.attn_config(cfg), dtype),
        "moe": init_moe_mlp(km, cfg, dtype),
        "norm1": L.init_norm(k1, cfg.d_model, cfg.norm_type, dtype),
        "norm2": L.init_norm(k2, cfg.d_model, cfg.norm_type, dtype),
    }


def block_axes(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "attn": L.attention_axes(T.attn_config(cfg)),
        "moe": moe_mlp_axes(),
        "norm1": L.norm_axes(cfg.norm_type),
        "norm2": L.norm_axes(cfg.norm_type),
    }


def block_fwd(params, x, cfg: ModelConfig, *, positions, kv_cache=None,
              cache_index=None, groups: int = 1):
    h = L.apply_norm(x, params["norm1"], cfg.norm_type)
    attn_out, new_cache = L.attention_fwd(
        params["attn"], h, T.attn_config(cfg), positions=positions,
        kv_cache=kv_cache, cache_index=cache_index)
    x = x + attn_out
    h2 = L.apply_norm(x, params["norm2"], cfg.norm_type)
    moe_out, aux = moe_mlp_fwd(params["moe"], h2, cfg, groups=groups)
    x = x + moe_out
    x = constrain(x, "batch", "seq_q", "embed")
    return x, new_cache, aux


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = T._dtype(cfg.param_dtype)
    ke, kl, kf = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_block(k, cfg, dtype))(layer_keys)
    return {
        "embedding": L.init_embedding(ke, cfg.padded_vocab, cfg.d_model, dtype),
        "layers": stacked,
        "final_norm": L.init_norm(kf, cfg.d_model, cfg.norm_type, dtype),
    }


def param_axes(cfg: ModelConfig) -> Dict[str, Any]:
    def lift(tree):
        return jax.tree.map(lambda ax: ("layers",) + ax, tree,
                            is_leaf=lambda x: isinstance(x, tuple))
    return {
        "embedding": L.embedding_axes(),
        "layers": lift(block_axes(cfg)),
        "final_norm": L.norm_axes(cfg.norm_type),
    }


def forward(params, cfg: ModelConfig, batch, *, cache=None, cache_index=None,
            remat: bool = False, groups: int = 1):
    params = T.cast_params(params, cfg)
    x = T._embed_inputs(params, cfg, batch)
    B, S = x.shape[:2]
    if cache_index is None:
        positions = jnp.arange(S)[None, :].astype(jnp.int32)
    else:
        positions = (cache_index + jnp.arange(S))[None, :].astype(jnp.int32)

    def body(carry, scanned):
        x, aux_sum = carry
        if cache is None:
            layer_params = scanned
            kv = None
        else:
            layer_params, ck, cv = scanned
            kv = (ck, cv)
        x, new_kv, aux = block_fwd(layer_params, x, cfg, positions=positions,
                                   kv_cache=kv, cache_index=cache_index,
                                   groups=groups)
        return (x, aux_sum + aux), (None if cache is None else new_kv)

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)

    if cache is None:
        (x, aux), _ = lax.scan(body, (x, jnp.float32(0.0)), params["layers"],
                               unroll=scan_unroll())
        new_cache = None
    else:
        (x, aux), (nk, nv) = lax.scan(
            body, (x, jnp.float32(0.0)), (params["layers"], cache["k"], cache["v"]),
            unroll=scan_unroll())
        new_cache = {"k": nk, "v": nv}

    x = L.apply_norm(x, params["final_norm"], cfg.norm_type)
    return x, new_cache, aux / cfg.n_layers


def loss_fn(params, cfg: ModelConfig, batch, *, remat: bool = True,
            groups: int = 1) -> jax.Array:
    hidden, _, aux = forward(params, cfg, batch, remat=remat, groups=groups)
    logits = L.unembed(params["embedding"], hidden, cfg.vocab)
    ce = L.cross_entropy_loss(logits, batch["labels"], batch.get("mask"))
    return ce + cfg.router_aux_coef * aux


init_cache = T.init_cache
cache_axes = T.cache_axes


def prefill(params, cfg: ModelConfig, batch, cache, *, groups: int = 1):
    hidden, new_cache, _ = forward(params, cfg, batch, cache=cache,
                                   cache_index=jnp.int32(0), remat=True,
                                   groups=groups)
    logits = L.unembed(params["embedding"], hidden[:, -1:, :], cfg.vocab)
    return logits, new_cache


def prefill_at(params, cfg: ModelConfig, batch, cache, last_pos, *,
               groups: int = 1):
    """Bucketed prefill: logits taken at ``last_pos`` (the last real
    position of a right-padded prompt) instead of the padded end."""
    hidden, new_cache, _ = forward(params, cfg, batch, cache=cache,
                                   cache_index=jnp.int32(0), remat=True,
                                   groups=groups)
    h_last = lax.dynamic_slice_in_dim(hidden, last_pos, 1, axis=1)
    return L.unembed(params["embedding"], h_last, cfg.vocab), new_cache


def decode_step(params, cfg: ModelConfig, tokens, cache, cache_index, *,
                groups: int = 1):
    hidden, new_cache, _ = forward(params, cfg, {"tokens": tokens},
                                   cache=cache, cache_index=cache_index,
                                   groups=groups)
    logits = L.unembed(params["embedding"], hidden, cfg.vocab)
    return logits, new_cache


def block_fwd_paged(params, x, cfg: ModelConfig, *, groups: int = 1,
                    **paged):
    """``block_fwd`` for decode over the stacked paged KV pool; ``paged``
    is ``transformer.block_fwd_paged``'s pool keywords."""
    h = L.apply_norm(x, params["norm1"], cfg.norm_type)
    attn_out, k_pages, v_pages = L.attention_fwd_paged(
        params["attn"], h, T.attn_config(cfg), **paged)
    x = x + attn_out
    h2 = L.apply_norm(x, params["norm2"], cfg.norm_type)
    moe_out, _ = moe_mlp_fwd(params["moe"], h2, cfg, groups=groups)
    return x + moe_out, k_pages, v_pages


def decode_paged(params, cfg: ModelConfig, tokens, pools, page_table,
                 lengths, *, groups: int = 1):
    """One-token decode over the shared paged KV pool (see
    ``transformer.decode_paged``); MoE blocks, the same carried pool."""
    params = T.cast_params(params, cfg)
    x = T._embed_inputs(params, cfg, {"tokens": tokens})
    x, pools = T.scan_layers_paged(
        functools.partial(block_fwd_paged, cfg=cfg, groups=groups),
        params["layers"], x, pools, page_table, lengths)
    x = L.apply_norm(x, params["final_norm"], cfg.norm_type)
    return L.unembed(params["embedding"], x, cfg.vocab), pools
