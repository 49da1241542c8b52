"""Operations and bytes that the algorithm needs, from shapes alone: the
work module of the dense decoders (Qwen1.5, Qwen3), whose layers all
attend over every key.

These count the work a correct implementation has to do, not what
today's code does: attention over each row's real length (causal, GQA
grouped), the K/V pages those lengths cover, the vocabulary head only
where logits are read.  A faster kernel that skips masked or padded
work is credited; no implementation that does the work can read above
its roofline.
"""

from __future__ import annotations

import math
from typing import Iterable


def layer_matmul_params(cfg) -> int:
    """Weights one token multiplies through in one decoder layer."""
    d, H, KV, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    attn = d * H * D + 2 * d * KV * D + H * D * d
    mlp = (3 if cfg.mlp_gated else 2) * d * cfg.d_ff
    return attn + mlp


def head_params(cfg) -> int:
    return cfg.vocab * cfg.d_model


def attn_flops(cfg, n_keys: int) -> int:
    """QK and PV of one query token against ``n_keys`` keys, one layer."""
    return 4 * cfg.n_heads * cfg.head_dim * n_keys


def prefill_flops(cfg, prompt_len: int) -> int:
    """Forward of a prompt (causal) with the head at its last position."""
    s = prompt_len
    attn = 4 * cfg.n_heads * cfg.head_dim * s * (s + 1) // 2
    return (cfg.n_layers * (2 * layer_matmul_params(cfg) * s + attn)
            + 2 * head_params(cfg))


def decode_flops(cfg, length: int) -> int:
    """One decoded token whose row holds ``length`` keys after its write."""
    return (cfg.n_layers * (2 * layer_matmul_params(cfg)
                            + attn_flops(cfg, length))
            + 2 * head_params(cfg))


def paged_attention_work(cfg, lengths: Iterable[int], page_size: int,
                         kv_itemsize: int, q_itemsize: int = 2):
    """(flops, bytes) of one decode step's paged-attention calls, over
    all layers: in each, every live row's grouped QK and PV over its
    length, the K and V pages that length covers, and the row's q and
    output."""
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    flops = 0
    nbytes = 0
    for n in lengths:
        if n <= 0:
            continue
        flops += attn_flops(cfg, n)
        pages = math.ceil(n / page_size)
        nbytes += 2 * pages * page_size * KV * D * kv_itemsize
        nbytes += 2 * H * D * q_itemsize
    return flops * cfg.n_layers, nbytes * cfg.n_layers
