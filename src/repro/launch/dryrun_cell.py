"""One dry-run cell: lowering, compiling, two-point cost extrapolation.

Split from dryrun.py so benchmarks/tests can import without re-setting
XLA_FLAGS (dryrun.py sets the 512-device flag at import).
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import compat
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_production_mesh
from repro.models.api import build_model, input_specs, layer_scan_trips
from repro.models.config import SHAPES, ShapeConfig, supports_shape
from repro.models.unroll import unroll_mode
from repro.optim.adamw import AdamW
from repro.runtime import serve as serve_rt
from repro.runtime import train as train_rt
from repro.sharding.partition import tree_shardings, use_rules
from repro.sharding.profiles import make_rules

# per-arch gradient-accumulation microbatch counts for train_4k: keeps the
# live activation footprint inside v5e HBM (16 GB) at global batch 256.
TRAIN_MICROBATCHES = {
    "command-r-plus-104b": 16,
    "qwen3-14b": 4,
    "pixtral-12b": 4,
    "mixtral-8x7b": 8,
    "zamba2-7b": 8,
    "olmoe-1b-7b": 2,
    "whisper-small": 2,
}


def _fix_divisibility(shape, sharding):
    """Drop partitioning on dims the sharding doesn't divide evenly
    (explicit in_shardings require exact divisibility, unlike internal
    GSPMD constraints which pad)."""
    mesh = sharding.mesh
    ax_size = dict(zip(mesh.axis_names, mesh.devices.shape))
    spec = list(sharding.spec) + [None] * (len(shape) - len(sharding.spec))
    changed = False
    for i, (dim, entry) in enumerate(zip(shape, spec)):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        n = 1
        for a in axes:
            n *= ax_size[a]
        if dim % n != 0:
            spec[i] = None
            changed = True
    if not changed:
        return sharding
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec(*spec))


def _attach(specs, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=_fix_divisibility(s.shape, sh)),
        specs, shardings)


def _lower_and_compile(cfg, shape, mesh, rules, model, optimizer, *,
                       dp_mode: str, donate: bool, compress_pod: bool = False):
    if shape.kind == "train":
        tcfg = train_rt.TrainStepConfig(
            dp_mode=dp_mode, microbatches=shape.microbatches, remat=True,
            compress_pod=compress_pod)
        step, state_sh = train_rt.make_train_step(
            model, optimizer, shape, mesh=mesh, rules=rules, tcfg=tcfg)
        state_specs = jax.eval_shape(
            lambda: train_rt.init_state(model, optimizer,
                                        jax.random.PRNGKey(0), tcfg))
        state_specs = _attach(state_specs, state_sh)
        b_specs = input_specs(cfg, shape)
        b_specs = _attach(b_specs, train_rt.batch_shardings(mesh, rules, b_specs))
        fn = jax.jit(step, donate_argnums=(0,) if donate else ())
        return fn.lower(state_specs, b_specs)
    if shape.kind == "prefill":
        pf = serve_rt.make_prefill_step(model)
        cache_specs = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                     dtype=jnp.bfloat16))
        cache_specs = _attach(cache_specs,
                              tree_shardings(mesh, rules, model.cache_axes()))
        p_specs = _attach(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))),
                          tree_shardings(mesh, rules, model.param_axes()))
        b_specs = input_specs(cfg, shape)
        b_specs = _attach(b_specs, train_rt.batch_shardings(mesh, rules, b_specs))
        fn = jax.jit(pf, donate_argnums=(2,) if donate else ())
        return fn.lower(p_specs, b_specs, cache_specs)
    dec = serve_rt.make_decode_step(model)
    carry_specs = serve_rt.decode_carry_specs(model, shape)
    carry_specs = _attach(carry_specs,
                          serve_rt.decode_carry_shardings(model, mesh, rules, shape))
    p_specs = _attach(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))),
                      tree_shardings(mesh, rules, model.param_axes()))
    fn = jax.jit(dec, donate_argnums=(1,) if donate else ())
    return fn.lower(p_specs, carry_specs)


def _measure(cfg, shape, mesh, rules, model, optimizer, pod_size, *,
             dp_mode, donate, mode, compress_pod=False):
    """Compile under one unroll mode; return (cost, coll_summary, mem, dt)."""
    t0 = time.time()
    with use_rules(rules, mesh), unroll_mode(mode):
        lowered = _lower_and_compile(cfg, shape, mesh, rules, model,
                                     optimizer, dp_mode=dp_mode, donate=donate,
                                     compress_pod=compress_pod)
        compiled = lowered.compile()
    dt = time.time() - t0
    cost = compat.cost_analysis(compiled)
    colls = H.parse_collectives(compiled.as_text(), pod_size=pod_size)
    csum = H.collective_summary(colls)
    mem = compiled.memory_analysis()
    return cost, csum, mem, dt


_COST_KEYS = ("flops", "bytes accessed", "transcendentals")


def _affine_combine(m1: Dict, m2: Dict, trips: int) -> Dict:
    """cost(k) = outside + k*body  →  outside + trips*body."""
    out = {}
    for k in set(m1) | set(m2):
        a, b = float(m1.get(k, 0.0)), float(m2.get(k, 0.0))
        body = b - a
        if k.endswith("_count") or k == "n_ops":
            out[k] = a + (trips - 1) * body
        else:
            out[k] = a + (trips - 1) * body
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool, *,
               smoke: bool = False, dp_mode: str = "auto",
               fsdp: bool = True, donate: bool = True,
               mode: str = "extrapolate",
               cfg_patch: Optional[Dict] = None,
               rules_patch: Optional[Dict] = None,
               micro_override: Optional[int] = None,
               compress_pod: bool = False) -> Dict:
    """Lower+compile one cell; returns the result record.

    Cost-analysis fidelity (XLA counts while bodies once):
      mode="extrapolate" — compile at unroll=1 and unroll=2; per-layer
        cost = difference; total = outside + trips*body.  Exact for the
        layer-homogeneous scans used by every family (inner heterogenous
        scans are fully unrolled in both).
      mode="full" — fully unroll layer scans (validation path).
    Train cells lower ONE gradient microbatch (global_batch/microbatches)
    and scale flops/bytes/collectives by ``flops_scale``.
    """
    cfg = get_config(arch, smoke=smoke)
    if cfg_patch:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, **cfg_patch)
    shape = SHAPES[shape_name]
    ok, reason = supports_shape(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "SKIP", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    ax = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_data = ax.get("pod", 1) * ax.get("data", 1)
    pod_size = n_chips // ax.get("pod", 1)

    flops_scale = 1
    if shape.kind == "train":
        micro = TRAIN_MICROBATCHES.get(arch, 1) if not smoke else 1
        if micro_override:
            micro = micro_override
        # per-microbatch batch must still cover the data shards
        micro = max(1, min(micro, shape.global_batch // n_data))
        flops_scale = micro
        shape = ShapeConfig(shape.name, shape.kind, shape.seq_len,
                            max(1, shape.global_batch // micro), microbatches=1)

    rules = make_rules(cfg, shape, mesh, fsdp=fsdp, dp_mode=dp_mode)
    if rules_patch:
        rules = rules.override(**rules_patch)
    model = build_model(cfg, moe_groups=n_data)
    optimizer = AdamW()
    trips = layer_scan_trips(cfg)

    if mode == "full":
        cost, csum, mem, dt1 = _measure(cfg, shape, mesh, rules, model,
                                        optimizer, pod_size, dp_mode=dp_mode,
                                        donate=donate, mode="full",
                                        compress_pod=compress_pod)
        dt2 = 0.0
    else:
        def pair(ka, kb):
            ca, sa, mem, dta = _measure(cfg, shape, mesh, rules, model,
                                        optimizer, pod_size, dp_mode=dp_mode,
                                        donate=donate, mode=ka,
                                        compress_pod=compress_pod)
            cb, sb, _, dtb = _measure(cfg, shape, mesh, rules, model,
                                      optimizer, pod_size, dp_mode=dp_mode,
                                      donate=donate, mode=kb,
                                      compress_pod=compress_pod)
            # cost(k) = outside + k*body; solve from (ka, kb)
            def fit(ma, mb):
                out = {}
                for key in set(ma) | set(mb):
                    a, b = float(ma.get(key, 0.0)), float(mb.get(key, 0.0))
                    body = (b - a) / (kb - ka)
                    out[key] = a + (trips - ka) * body
                return out
            return fit(ca, cb), fit(sa, sb), mem, dta + dtb

        cost, csum, mem, dtp = pair(1, 2)
        dt1, dt2 = dtp, 0.0
        bad = (cost.get("flops", 0) <= 0 or cost.get("bytes accessed", 0) < 0
               or csum.get("total_moved_bytes", 0) < 0)
        if bad:
            # cross-body CSE broke the k=1->2 affine fit (the partitioner
            # hoists shared subexpressions only once bodies repeat); the
            # (2,3) pair is affine again.
            cost, csum, mem, dt2 = pair(2, 3)

    # MODEL_FLOPS: 6·N·D for train (N active for MoE), 2·N·D forward-only
    tokens = (flops_scale * shape.global_batch
              * (shape.seq_len if shape.kind != "decode" else 1))
    n_active = cfg.active_param_count()
    model_flops = (6.0 if shape.kind == "train" else 2.0) * n_active * tokens

    cost_scaled = {k: (v * flops_scale if k in _COST_KEYS else v)
                   for k, v in cost.items()}
    csum_scaled = {k: v * flops_scale for k, v in csum.items()}
    roof = H.roofline_terms(cost_scaled, csum_scaled, n_chips, model_flops)
    return {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "status": "OK", "n_chips": n_chips,
        "dp_mode": dp_mode, "fsdp": fsdp, "mode": mode,
        "flops_scale": flops_scale, "layer_trips": trips,
        "n_params": cfg.param_count(),
        "n_active_params": cfg.active_param_count(),
        "microbatches": flops_scale if shape.kind == "train" else 0,
        "compile_s": round(dt1 + dt2, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "cost": {k: v for k, v in cost_scaled.items() if k in _COST_KEYS},
        "collectives": csum_scaled,
        "roofline": roof,
        "model_flops_total": model_flops,
    }
