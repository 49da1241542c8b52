"""Per-(architecture x shape) sharding profiles.

Derives the logical-axis → mesh-axis rule table from the model config and
the mesh, honoring divisibility (GSPMD pads non-divisible shardings, which
wastes compute — we avoid it structurally):

* attention: head-sharded over ``model`` when heads divide the axis,
  otherwise context-parallel (q sharded on sequence, K/V gathered — exact
  for GQA since KV is small);
* MLP: Megatron column→row on d_ff over ``model``;
* MoE: expert-parallel over ``model`` when n_experts divides it (olmoe),
  else per-expert d_ff tensor parallel (mixtral);
* parameters: FSDP over the ``data`` axes on the ``embed`` dim (ZeRO-3
  analogue; GSPMD inserts per-layer all-gathers inside the layer scan);
* decode: KV cache head-sharded when divisible, else sequence-sharded
  (flash-decode style partial-softmax reductions are GSPMD-native);
* ``long_500k`` (batch=1): batch unsharded, cache sequence spread over
  all axes.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

from jax.sharding import Mesh

from repro.models.config import ModelConfig, ShapeConfig
from repro.sharding.partition import Rules


def _prod(xs):
    out = 1
    for x in xs:
        out *= x
    return out


def make_rules(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
               fsdp: bool = True, dp_mode: str = "auto") -> Rules:
    """The rule table is the same for both ``dp_mode`` values: the
    hierarchical train step strips the manual ``pod`` axis from it
    inside its shard_map (``Rules.strip_axis``)."""
    ax = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_n = ax.get("model", 1)
    data_axes = tuple(a for a in ("pod", "data") if a in ax)
    n_data = _prod(ax[a] for a in data_axes)

    B = shape.global_batch
    if shape.kind == "train" and shape.microbatches > 1:
        B = B // shape.microbatches

    # ---- batch placement ----
    if B % n_data == 0:
        batch_axes: Optional[Tuple[str, ...]] = data_axes
    elif "data" in ax and B % ax["data"] == 0:
        batch_axes = ("data",)
    else:
        batch_axes = None  # e.g. long_500k batch=1

    heads_div = cfg.n_heads > 0 and cfg.n_heads % model_n == 0
    kv_div = cfg.n_kv_heads > 0 and cfg.n_kv_heads % model_n == 0

    t: Dict[str, object] = {
        "batch": batch_axes,
        "layers": None,
        "seq_q": None,
        "embed": "data" if (fsdp and "data" in ax) else None,
        "embed_norm": None,
        "vocab": "model",
        "ff": "model",
        "qkv_out": "model",
        "kv_out": "model" if kv_div else None,
        "head_dim": None,
        "heads": "model" if heads_div else None,
        "kv_heads": "model" if kv_div else None,
        # context-parallel fallback when heads don't divide the axis
        "seq_attn": None if heads_div else "model",
        "seq_kv": None,
        # MoE
        "moe_groups": batch_axes,
        "expert_router": None,
        "expert": ("model" if (cfg.n_experts and cfg.n_experts % model_n == 0)
                   else None),
        "expert_ff": ("model" if not (cfg.n_experts and cfg.n_experts % model_n == 0)
                      else None),
        # SSM
        "ssm_inner_proj": "model",
        "ssm_conv_ch": "model",
        "ssm_heads": ("model" if (cfg.family in ("ssm", "hybrid")
                                  and cfg.ssm_heads % model_n == 0) else None),
        "ssm_inner": "model",
        "ssm_inner_norm": None,
    }

    if shape.kind == "decode":
        # one-token queries: context parallelism is meaningless; spread the
        # KV cache instead.
        t["seq_attn"] = None
        if not kv_div:
            t["seq_kv"] = "model"
        if batch_axes is None:
            # long_500k: single sequence — put the cache sequence (and ssm
            # heads) across everything available.
            t["seq_kv"] = (("data", "model") if kv_div
                           else tuple(a for a in ("data", "model") if a in ax))
            if kv_div:
                t["kv_heads"] = None  # seq takes both axes
    return Rules(t)


def describe(rules: Rules) -> str:
    return ", ".join(f"{k}={v}" for k, v in sorted(rules.table.items())
                     if v is not None)
