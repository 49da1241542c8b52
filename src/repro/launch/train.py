"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
        --steps 100 --batch 8 --seq 128 --dp-mode hierarchical

Runs the full stack: data pipeline → sharded train step (GSPMD + optional
hierarchical cross-pod phase) → AdamW → async checkpointing → fault-
tolerant loop with straggler monitoring.  On real hardware the same
driver runs under jax.distributed with the production mesh; on CPU it
uses whatever devices exist (force more with XLA_FLAGS).
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.tiering import TieringPolicy, offload_state_shardings
from repro.data.pipeline import DataConfig, DataPipeline
from repro.ckpt import checkpoint as ckpt
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_smoke_mesh
from repro.models.api import build_model
from repro.models.config import ShapeConfig
from repro.obs.console import emit_json, warn
from repro.optim.adamw import AdamW
from repro.runtime import train as train_rt
from repro.runtime.ft import FaultTolerantLoop, StragglerMonitor
from repro.sharding.partition import use_rules
from repro.sharding.profiles import make_rules


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="olmo-1b")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--dp-mode", default="auto", choices=["auto", "hierarchical"])
    p.add_argument("--compress-pod", action="store_true")
    p.add_argument("--offload-optimizer", action="store_true")
    # ---- pool-orchestrated resources (repro.pool) ----
    p.add_argument("--pool", default="none",
                   choices=["none", "scalepool", "baseline", "contention"],
                   help="obtain mesh + tiering from a resource-pool lease "
                        "(contention = scalepool estate with overlap-"
                        "aware placement for co-resident jobs)")
    p.add_argument("--pool-accels", type=int, default=8)
    p.add_argument("--pool-tier2-gb", type=float, default=0.0)
    p.add_argument("--pool-model-parallel", type=int, default=1)
    p.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--log-every", type=int, default=10)
    args = p.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    optimizer = AdamW(lr=args.lr)
    shape = ShapeConfig("cli", "train", args.seq, args.batch,
                        microbatches=args.microbatches)

    lease = None
    tier_policy = TieringPolicy() if args.offload_optimizer else None
    if args.pool != "none":
        # the orchestrator decides mesh shape AND tiering: a lease with a
        # tier-2 reservation trains with optimizer state in the capacity
        # tier; one without keeps everything in HBM.
        from repro.pool import smoke_pool
        pool = smoke_pool(args.pool)
        lease = pool.lease("cli-train", args.pool_accels,
                           tier2_gb=args.pool_tier2_gb,
                           model_parallel=args.pool_model_parallel)
        mesh, tier_policy = lease.materialize()
        if args.offload_optimizer and not tier_policy.offload_optimizer:
            # explicit flag without a tier-2 reservation: honor it (host
            # memory stands in for the capacity tier) but say so.
            warn("--offload-optimizer with a 0-byte tier-2 lease; "
                 "offloading to host memory (pass --pool-tier2-gb to "
                 "reserve pool capacity)")
            tier_policy = dataclasses.replace(tier_policy,
                                              offload_optimizer=True)
    else:
        mesh = make_smoke_mesh()
    multi_pod = "pod" in mesh.axis_names
    dp_mode = args.dp_mode if multi_pod else "auto"
    rules = make_rules(cfg, shape, mesh, fsdp=False, dp_mode=dp_mode)
    tcfg = train_rt.TrainStepConfig(dp_mode=dp_mode,
                                    compress_pod=args.compress_pod,
                                    microbatches=args.microbatches)

    rng = jax.random.PRNGKey(0)
    state = train_rt.init_state(model, optimizer, rng, tcfg)
    step_fn, state_sh = train_rt.make_train_step(
        model, optimizer, shape, mesh=mesh, rules=rules, tcfg=tcfg)
    if state_sh is not None:
        # start on the mesh, where the step leaves the state: from device
        # 0 alone, the second step would compile the step again
        state = jax.device_put(state, state_sh)
    if state_sh is not None and tier_policy is not None \
            and tier_policy.offload_optimizer:
        state_sh = offload_state_shardings(state_sh, tier_policy)

    pipe = DataPipeline(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                   global_batch=args.batch))

    jit_step = jax.jit(step_fn, donate_argnums=(0,))

    def train_step(state, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        with use_rules(rules, mesh), jax.set_mesh(mesh):
            return jit_step(state, batch)

    ckpt_dir = Path(args.ckpt_dir)
    last = {"state": state, "step": 0}

    def save_fn(s, step):
        last["state"], last["step"] = s, step
        ckpt.save(ckpt_dir / f"step{step}",
                  {"params": s.params, "mu": s.opt.mu, "nu": s.opt.nu},
                  step=step, extra={"pipeline": pipe.state.to_dict()},
                  asynchronous=True)

    def restore_fn():
        return last["state"], last["step"]

    loop = FaultTolerantLoop(train_step, save_fn, restore_fn, pipe,
                             ckpt_every=args.ckpt_every,
                             monitor=StragglerMonitor())

    t0 = time.time()
    state = loop.run(state, args.steps)
    dt = time.time() - t0

    losses = [h["loss"] for h in loop.history]
    step_s = [h["host_step_s"] for h in loop.history]
    emit_json({
        "arch": cfg.name, "steps": args.steps,
        "devices": len(jax.devices()), "mesh": dict(zip(mesh.axis_names,
                                                        mesh.devices.shape)),
        "dp_mode": dp_mode,
        "state_devices": len({d for leaf in jax.tree.leaves(state.params)
                              for d in leaf.devices()}),
        "lease": (None if lease is None else {
            "pods": list(lease.allocation.pod_ids),
            "accels": lease.n_accels,
            "tier2_gb": lease.tier2_bytes / 1e9,
            "offload_optimizer": tier_policy.offload_optimizer}),
        "loss_first": losses[0], "loss_last": losses[-1],
        "loss_drop": losses[0] - losses[-1],
        # host wall clock; each step is timed after block_until_ready,
        # and the first one includes compilation
        "host_wall_s": dt,
        "host_first_step_s": step_s[0],
        "host_steady_step_s": (float(np.median(step_s[1:]))
                               if len(step_s) > 1 else None),
        "straggler_events": len(loop.monitor.events),
        "restarts": loop.restarts,
    })
    return 0 if losses[-1] < losses[0] else 1


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
