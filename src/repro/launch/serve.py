"""Serving driver over the ``repro.serve`` engine.

Request-level modes (continuous batching + budgeted KV tiering):

    # synthetic request trace through the engine
    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
        --requests 16 --max-new 16 --slots 4

    # trace file (JSONL: prompt_tokens / max_new_tokens / arrival_time)
    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
        --trace /path/to/trace.jsonl --tier2-kv-gb 1

    # lease-backed: the pool grants the tier-2 KV budget
    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
        --requests 16 --pool scalepool --pool-accels 4 --tier2-kv-gb 1

    # multi-tenant: N engines fair-sharing ONE physical page pool
    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
        --requests 16 --tenants 2 --tier1-pages 12 --tier2-kv-gb 1

    # disaggregated: prefill tier + decode tier, KV streamed over the
    # routed fabric (direct pod-to-pod or staged through tier-2 memory)
    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
        --requests 16 --disagg --disagg-staging tier2 --min-ready-pages 1

Legacy fixed-batch mode (pre-engine path, kept for encdec archs):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
        --batch 4 --prompt 64 --generate 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.tiering import KVBudget
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_smoke_mesh
from repro.models.api import build_model
from repro.models.config import ShapeConfig
from repro.obs import Tracer, write_chrome_trace
from repro.obs.console import emit_json, warn
from repro.runtime import serve as serve_rt
from repro.sharding.partition import use_rules
from repro.sharding.profiles import make_rules


def _flush_trace(tracer, transports, path: str) -> dict:
    """Drain every transport's in-flight transfers (their spans land at
    completion) and write the Perfetto-loadable trace file."""
    for tx in {id(t): t for t in transports if t is not None}.values():
        tx.quiesce()
    write_chrome_trace(tracer, path)
    return {"path": path, "events": len(tracer),
            "dropped": tracer.dropped}


def _engine_mode(args, cfg, model) -> int:
    from repro.serve import (Engine, EngineConfig, latency_summary,
                             load_trace, run_trace, synthetic_trace)

    ecfg = EngineConfig(max_slots=args.slots, max_seq=args.max_seq,
                        page_size=args.page_size)
    tracer = Tracer(args.trace_capacity) if args.trace_out else None
    budget = None
    if args.tier1_pages or args.tier2_kv_gb:
        budget = KVBudget(
            tier1_pages=args.tier1_pages or None,
            tier2_bytes=args.tier2_kv_gb * 1e9,
            page_size=args.page_size)

    if args.tenants > 1:
        return _multitenant_mode(args, cfg, model, ecfg, tracer)

    if args.pool != "none":
        from repro.pool import smoke_pool
        pool = smoke_pool(args.pool)
        lease = pool.lease("cli-serve", args.pool_accels,
                           tier2_gb=max(args.pool_tier2_gb, args.tier2_kv_gb),
                           kv_gb=args.tier2_kv_gb,
                           model_parallel=args.pool_model_parallel)
        engine = Engine.from_lease(model, lease, ecfg, budget=budget,
                                   tracer=tracer)
    else:
        engine = Engine.local(model, ecfg, budget=budget, tracer=tracer)

    if args.trace:
        trace = load_trace(args.trace, vocab=cfg.vocab)
    else:
        trace = synthetic_trace(
            args.requests, mean_interarrival_s=args.interarrival,
            prompt_lens=tuple(int(x) for x in args.prompt_lens.split(",")),
            max_new_tokens=args.max_new, vocab=cfg.vocab, seed=args.seed)

    t0 = time.time()
    handles = run_trace(engine, trace)
    wall = time.time() - t0
    stats = engine.stats()
    out = {
        "arch": cfg.name, "mode": "engine",
        "lease": args.pool if args.pool != "none" else None,
        "requests": len(handles),
        "short_requests": sum(len(h.tokens) < h.request.max_new_tokens
                              for h in handles),
        "modeled_latency": latency_summary(handles),
        "stats": stats,
        "host_wall_s": wall,
        "sample_tokens": handles[0].tokens[:8] if handles else [],
    }
    if tracer is not None:
        out["trace_out"] = _flush_trace(tracer, [engine.transport],
                                        args.trace_out)
    emit_json(out)
    return 0 if stats["failed_oom"] == 0 else 1


def _disagg_mode(args, cfg, model) -> int:
    """--disagg: prefill tier + decode tier on separate pods of one
    routed fabric, KV pages streamed between them (repro.disagg)."""
    from repro.core import fabric as fb
    from repro.disagg import DisaggCluster, DisaggConfig, PrefillWorker
    from repro.fabric import Topology, Transport
    from repro.serve import (Engine, EngineConfig, latency_summary,
                             load_trace, synthetic_trace)

    ecfg = EngineConfig(max_slots=args.slots, max_seq=args.max_seq,
                        page_size=args.page_size)
    tracer = Tracer(args.trace_capacity) if args.trace_out else None
    budget = None
    if args.tier1_pages or args.tier2_kv_gb:
        budget = KVBudget(
            tier1_pages=args.tier1_pages or None,
            tier2_bytes=args.tier2_kv_gb * 1e9,
            page_size=args.page_size)

    params = model.init(jax.random.PRNGKey(0))
    n_pre, n_dec = args.prefill_pods, args.decode_pods
    workers = [PrefillWorker(Engine.local(model, ecfg, params=params,
                                          tracer=tracer), name=f"p{i}")
               for i in range(n_pre)]
    dengines = [Engine.local(model, ecfg, params=params, budget=budget,
                             tracer=tracer, tenant=f"d{k}")
                for k in range(n_dec)]

    # a two-tier estate graph: every pod hangs off one leaf switch, the
    # staging memory node too; capacities default to ~50 page-transfers
    # per modeled second so handoffs are visible but not dominant
    pb = dengines[0].kv.page_bytes
    bw = args.kv_gbps * 1e9 if args.kv_gbps > 0 else 50.0 * pb
    lat = fb.tier2_memory_fabric(8).latency()
    topo = Topology("disagg-cli")
    topo.add_node("leaf", "switch")
    topo.add_node("mem:0", "memory")
    topo.connect("mem:0", "leaf", fb.CXL_CAPACITY, capacity=2.0 * bw,
                 latency=lat / 4)
    for i in range(n_pre + n_dec):
        topo.add_node(f"pod:{i}", "pod")
        topo.connect(f"pod:{i}", "leaf", fb.CXL3, capacity=bw,
                     latency=lat / 4)
    tx = Transport(topo, tracer=tracer)
    kw = dict(route=topo.route("pod:0", f"pod:{n_pre}"))
    if args.disagg_staging == "tier2":
        kw["stage_in"] = topo.route("pod:0", "mem:0")
        kw["stage_out"] = topo.route("mem:0", f"pod:{n_pre}")
    cluster = DisaggCluster(
        workers, dengines, transport=tx, tenant="cli",
        config=DisaggConfig(
            staging=args.disagg_staging,
            min_ready_pages=args.min_ready_pages or None,
            max_transit_s=args.max_transit_s or None), **kw)

    if args.trace:
        trace = load_trace(args.trace, vocab=cfg.vocab)
    else:
        trace = synthetic_trace(
            args.requests, mean_interarrival_s=args.interarrival,
            prompt_lens=tuple(int(x) for x in args.prompt_lens.split(",")),
            max_new_tokens=args.max_new, vocab=cfg.vocab, seed=args.seed)

    t0 = time.time()
    handles = cluster.run(trace)
    wall = time.time() - t0
    failed = sum(e.stats()["failed_oom"] for e in dengines)
    transits = sorted(h.kv_transit_s for h in handles)
    out = {
        "arch": cfg.name, "mode": "disagg",
        "staging": args.disagg_staging,
        "prefill_pods": n_pre, "decode_pods": n_dec,
        "requests": len(handles),
        "handoffs": cluster.handoffs, "colocated": cluster.colocated,
        "modeled_latency": latency_summary(handles),
        "modeled_kv_transit_s": {
            "mean": sum(transits) / max(1, len(transits)),
            "max": transits[-1] if transits else 0.0,
        },
        "host_wall_s": wall,
        "sample_tokens": handles[0].tokens[:8] if handles else [],
    }
    if tracer is not None:
        out["trace_out"] = _flush_trace(
            tracer, [tx] + [e.transport for e in dengines]
            + [w.engine.transport for w in workers], args.trace_out)
    emit_json(out)
    return 0 if failed == 0 else 1


def _multitenant_mode(args, cfg, model, ecfg, tracer=None) -> int:
    """--tenants N: N engines over ONE shared page pool (PoolArbiter),
    traffic (synthetic or --trace JSONL) split round-robin across
    tenants."""
    from repro.serve import (Engine, PoolArbiter, latency_summary,
                             load_trace, run_multi_trace, synthetic_trace)

    if args.pool != "none" and args.tier2_kv_gb <= 0:
        warn("--tenants with --pool shares one KV grant across the "
             "tenants — pass --tier2-kv-gb > 0 so the lease has kv "
             "bytes to share")
        return 2

    names = [f"t{i}" for i in range(args.tenants)]
    tier1 = args.tier1_pages or args.tenants * args.slots * ecfg.pages_per_slot
    arb = PoolArbiter(tier1, page_size=args.page_size, tracer=tracer)
    per_tenant = KVBudget(tier2_bytes=args.tier2_kv_gb * 1e9 / args.tenants,
                          page_size=args.page_size)
    if args.pool != "none":
        from repro.pool import smoke_pool
        pool = smoke_pool(args.pool)
        lease = pool.lease("cli-serve", args.pool_accels,
                           tier2_gb=max(args.pool_tier2_gb, args.tier2_kv_gb),
                           kv_gb=args.tier2_kv_gb,
                           model_parallel=args.pool_model_parallel,
                           tenants=tuple(names))
        engines = {n: Engine.from_lease(model, lease, ecfg,
                                        arbiter=arb, tenant=n,
                                        tracer=tracer)
                   for n in names}
    else:
        engines = {n: Engine.local(model, ecfg, budget=per_tenant,
                                   arbiter=arb, tenant=n, tracer=tracer)
                   for n in names}

    if args.trace:
        trace = load_trace(args.trace, vocab=cfg.vocab)
    else:
        trace = synthetic_trace(
            args.requests, mean_interarrival_s=args.interarrival,
            prompt_lens=tuple(int(x) for x in args.prompt_lens.split(",")),
            max_new_tokens=args.max_new, vocab=cfg.vocab, seed=args.seed)
    split = {n: [r for j, r in enumerate(trace)
                 if j % args.tenants == i]
             for i, n in enumerate(names)}

    t0 = time.time()
    results = run_multi_trace([(engines[n], split[n]) for n in names])
    wall = time.time() - t0
    out = {"arch": cfg.name, "mode": "multitenant",
           "tenants": args.tenants, "tier1_pages": tier1,
           "host_wall_s": wall, "arbiter": arb.stats(), "per_tenant": {}}
    failed = 0
    for n, handles in zip(names, results):
        st = engines[n].stats()
        failed += st["failed_oom"]
        out["per_tenant"][n] = {
            "requests": len(handles),
            "modeled_latency": latency_summary(handles),
            "swaps": st["preempt_swaps"],
            "recomputes": st["preempt_recomputes"],
            "tput_busy_tok_s": st["throughput_busy_tok_s"],
        }
    if tracer is not None:
        out["trace_out"] = _flush_trace(
            tracer, [e.transport for e in engines.values()],
            args.trace_out)
    emit_json(out)
    return 0 if failed == 0 else 1


def _legacy_batch_mode(args, cfg, model) -> int:
    max_seq = args.prompt + args.generate
    shape = ShapeConfig("cli", "decode", max_seq, args.batch)
    mesh = make_smoke_mesh()
    rules = make_rules(cfg, shape, mesh, fsdp=False)

    rng = jax.random.PRNGKey(0)
    params = model.init(rng)
    prompts = jax.random.randint(rng, (args.batch, args.prompt), 1, cfg.vocab)

    decode_fn = jax.jit(serve_rt.make_decode_step(model),
                        donate_argnums=(1,))

    with use_rules(rules, mesh), jax.set_mesh(mesh):
        cache = model.init_cache(args.batch, max_seq, dtype=jnp.float32)
        t0 = time.time()
        if cfg.family == "encdec":
            frames = jax.random.normal(rng, (args.batch, cfg.enc_seq,
                                             cfg.d_model), jnp.bfloat16)
            logits, cache, enc = model.prefill(
                params, {"frame_embeds": frames, "tokens": prompts}, cache)
        else:
            logits, cache = model.prefill(params, {"tokens": prompts}, cache)
            enc = None
        jax.block_until_ready(logits)
        t_prefill = time.time() - t0

        carry = {"tokens": jnp.argmax(logits[:, -1:, :], -1).astype(jnp.int32),
                 "cache": cache, "index": jnp.int32(args.prompt)}
        if enc is not None:
            carry["enc_states"] = enc
        generated = [np.asarray(carry["tokens"])]
        t0 = time.time()
        for _ in range(args.generate - 1):
            logits, carry = decode_fn(params, carry)
            generated.append(np.asarray(carry["tokens"]))
        jax.block_until_ready(carry["tokens"])
        t_decode = time.time() - t0

    toks = np.concatenate(generated, axis=1)
    tokens_per_s = args.batch * (args.generate - 1) / max(t_decode, 1e-9)
    emit_json({
        "arch": cfg.name, "mode": "batch",
        "batch": args.batch, "prompt": args.prompt,
        "generated": toks.shape[1],
        "host_prefill_s": t_prefill,
        "host_decode_tok_per_s": tokens_per_s,
        "sample_tokens": toks[0, :8].tolist(),
    })
    return 0


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="qwen1.5-0.5b")
    p.add_argument("--smoke", action="store_true")
    # engine (request-level) mode
    p.add_argument("--requests", type=int, default=0,
                   help="serve N synthetic requests through the engine")
    p.add_argument("--trace", default=None,
                   help="JSONL request trace driven through the engine")
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-seq", type=int, default=256)
    p.add_argument("--page-size", type=int, default=32)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--prompt-lens", default="16,32,64")
    p.add_argument("--interarrival", type=float, default=0.05)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tier1-pages", type=int, default=0,
                   help="tier-1 KV page quota (0 = full slot capacity)")
    p.add_argument("--tier2-kv-gb", type=float, default=0.0,
                   help="tier-2 KV byte budget (spill target)")
    p.add_argument("--tenants", type=int, default=1,
                   help="N>1: N tenant engines over ONE shared page pool "
                        "(PoolArbiter fair shares), traffic split "
                        "round-robin")
    p.add_argument("--disagg", action="store_true",
                   help="disaggregated serving: prefill tier + decode "
                        "tier on separate pods, KV pages streamed over "
                        "the routed fabric (repro.disagg)")
    p.add_argument("--disagg-staging", default="direct",
                   choices=["direct", "tier2"],
                   help="handoff path: direct pod-to-pod, or staged "
                        "through a tier-2 memory node (two priced legs)")
    p.add_argument("--prefill-pods", type=int, default=1)
    p.add_argument("--decode-pods", type=int, default=1)
    p.add_argument("--min-ready-pages", type=int, default=0,
                   help="admit a handed-off request once this many KV "
                        "pages landed (0 = wait for all)")
    p.add_argument("--max-transit-s", type=float, default=0.0,
                   help="route a request colocated when its predicted "
                        "KV transit exceeds this (0 = never)")
    p.add_argument("--kv-gbps", type=float, default=0.0,
                   help="fabric pod-uplink capacity for KV handoffs "
                        "(0 = auto-scale to ~50 pages/s)")
    p.add_argument("--pool", default="none",
                   choices=["none", "scalepool", "baseline"])
    p.add_argument("--pool-accels", type=int, default=4)
    p.add_argument("--pool-tier2-gb", type=float, default=0.0)
    p.add_argument("--pool-model-parallel", type=int, default=1)
    p.add_argument("--trace-out", default=None,
                   help="write a Chrome/Perfetto trace_event JSON of the "
                        "run's modeled timeline (open in ui.perfetto.dev)")
    p.add_argument("--trace-capacity", type=int, default=1 << 16,
                   help="flight-recorder ring size (events); oldest "
                        "events drop beyond this")
    # legacy fixed-batch mode
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt", type=int, default=64)
    p.add_argument("--generate", type=int, default=32)
    args = p.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    if args.requests or args.trace:
        if not model.supports_paged_kv:
            warn(f"the request-level engine serves paged-KV families "
                 f"(dense/moe); {cfg.family!r} is not supported yet — "
                 f"use the fixed-batch mode (--batch/--prompt/"
                 f"--generate) instead")
            return 2
        if args.disagg:
            return _disagg_mode(args, cfg, model)
        return _engine_mode(args, cfg, model)
    return _legacy_batch_mode(args, cfg, model)


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
