"""Plain reference forward of the dense decoder (Qwen1.5 / Qwen3), in
float32 ``jax.numpy``: no kernel, no cache, no batching.

It follows the published architecture: RMSNorm (eps from the
configuration) before attention and MLP; Q/K/V projections with bias
where ``qkv_bias``; per-head RMSNorm of q and k where ``qk_norm``
(Qwen3); rotary embedding on the two halves of each head (HF
``rotate_half``); grouped-query attention, query head ``h`` reading KV
head ``h // (H / KV)``; causal softmax; SwiGLU MLP; final RMSNorm; the
head is the embedding table (``tie_word_embeddings``), so an untied
configuration is refused.  It runs one layer at a time from the stacked
weights, so that it fits beside them.

``served_gaps`` is the contract a configuration file's ``reference``
module meets.  A reference for a variant of these decoders may call it
with its own ``mask``: which keys each query position reads.

``quant="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with a per-tensor (weights) or per-row (activations) scale,
the step below the bfloat16 the configuration serves in.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import BenchError

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
_FP8_MAX = 448.0
QBLOCK = 512                     # query rows per attention block


def _fp8(x, axis=None):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / _FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _op(x, quant, weight=False):
    if quant is None:
        return x
    return _fp8(x, axis=None if weight else -1)


def _mm(x, w, quant):
    return jnp.matmul(_op(x, quant), _op(w, quant, weight=True), precision=HI)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x (S, heads, D) at positions pos (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None].astype(F32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal(rows, cols, cfg):
    """Query position ``rows[:, None]`` reads key ``cols[None, :]``."""
    return cols[None, :] <= rows[:, None]


def attention(q, k, v, quant, cfg, mask=causal):
    """GQA under ``mask``. q (S, H, D); k, v (S, KV, D) -> (S, H, D)."""
    S, H, D = q.shape
    KV = k.shape[1]
    G = H // KV
    kg = jnp.repeat(k, G, axis=1)            # head h reads KV head h // G
    vg = jnp.repeat(v, G, axis=1)
    kq, vq = _op(kg, quant), _op(vg, quant)
    n_blocks = -(-S // QBLOCK)
    qp = jnp.pad(q, ((0, n_blocks * QBLOCK - S), (0, 0), (0, 0)))

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * QBLOCK, QBLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", _op(qb, quant), kq,
                       precision=HI) / jnp.sqrt(F32(D))
        rows = i * QBLOCK + jnp.arange(QBLOCK)
        s = jnp.where(mask(rows, jnp.arange(S), cfg)[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _op(p, quant), vq, precision=HI)

    out = jax.lax.map(block, jnp.arange(n_blocks))
    return out.reshape(n_blocks * QBLOCK, H, D)[:S]


def block(w, x, cfg, quant=None, mask=causal):
    """One decoder layer over ``x`` (S, d) with float32 weights ``w``."""
    S = x.shape[0]
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = jnp.arange(S)
    a = w["attn"]
    h = rmsnorm(x, w["norm1"]["scale"], cfg.norm_eps)
    q, k, v = _mm(h, a["wq"], quant), _mm(h, a["wk"], quant), _mm(h, a["wv"], quant)
    if cfg.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k, v = q.reshape(S, H, D), k.reshape(S, KV, D), v.reshape(S, KV, D)
    if cfg.qk_norm:
        q = rmsnorm(q, a["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, a["k_norm"], cfg.norm_eps)
    q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
    o = attention(q, k, v, quant, cfg, mask).reshape(S, H * D)
    x = x + _mm(o, a["wo"], quant)
    m = w["mlp"]
    h = rmsnorm(x, w["norm2"]["scale"], cfg.norm_eps)
    act = jax.nn.silu(_mm(h, m["w_gate"], quant)) * _mm(h, m["w_up"], quant)
    return x + _mm(act, m["w_down"], quant)


@partial(jax.jit, static_argnames=("cfg", "quant", "mask"))
def layer(layers, i, x, cfg, quant, mask=causal):
    """Layer ``i`` of the stacked weights, upcast to float32 here."""
    return block(jax.tree.map(lambda a: a[i].astype(F32), layers), x, cfg,
                 quant, mask)


@jax.jit
def embed(table, tokens):
    return table[tokens].astype(F32)


def unembed(params, x, cfg, quant=None):
    """Final norm, then logits over the vocabulary (tied head)."""
    h = rmsnorm(x, params["final_norm"]["scale"].astype(F32), cfg.norm_eps)
    table = params["embedding"]["table"][: cfg.vocab].astype(F32)
    return jnp.matmul(_op(h, quant), _op(table, quant, weight=True).T,
                      precision=HI)


@partial(jax.jit, static_argnames=("cfg", "quant"))
def head(params, x, rows, cfg, quant):
    """Logits (len(rows), vocab) at the given positions of ``x``."""
    return unembed(params, x[rows], cfg, quant)


def logits(params, cfg, tokens: Sequence[int], rows: Sequence[int],
           quant: Optional[str] = None, pad_to: Optional[int] = None,
           mask=causal):
    """Reference logits at positions ``rows`` of the sequence ``tokens``
    (padded at the end to ``pad_to``, which causality leaves unread)."""
    n = len(tokens)
    S = max(n, pad_to or n)
    toks = np.zeros(S, np.int32)
    toks[:n] = tokens
    x = embed(params["embedding"]["table"], jnp.asarray(toks))
    for i in range(cfg.n_layers):
        x = layer(params["layers"], jnp.int32(i), x, cfg, quant, mask)
    return head(params, x, jnp.asarray(np.asarray(rows, np.int32)), cfg,
                quant)


def served_gaps(params, cfg, served, pad_to: int, control: bool = False,
                mask=causal):
    """For each (prompt, tokens) served, the widest gap by which a served
    token's reference logit lies below the reference's best.  With
    ``control``, also the same gap for the token that the fp8 control
    puts first at each of those positions (the control's reading)."""
    if not cfg.tie_embeddings:
        raise BenchError(f"{cfg.name}: bench/reference.py reads the "
                         f"embedding table as the head and cannot check an "
                         f"untied model")
    prog, ctrl = [], []
    for prompt, toks in served:
        seq = list(prompt) + list(toks[:-1])
        rows = np.arange(len(prompt) - 1, len(seq))
        ref = logits(params, cfg, seq, rows, pad_to=pad_to, mask=mask)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, jnp.asarray(toks)[:, None], -1)[:, 0]
        prog.append(float(jnp.max(best - got)))
        if control:
            low = logits(params, cfg, seq, rows, quant="fp8", pad_to=pad_to,
                         mask=mask)
            pick = jnp.take_along_axis(ref, jnp.argmax(low, -1)[:, None], -1)
            ctrl.append(float(jnp.max(best - pick[:, 0])))
    return (prog, ctrl) if control else prog
