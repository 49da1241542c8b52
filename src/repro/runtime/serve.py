"""Serving-step factories: prefill and one-token decode over a sharded
KV/state cache.  These are the functions the decode_* / long_* dry-run
cells lower (``serve_step``, not ``train_step``, per the assignment).

.. deprecated::
    The request-level serving API now lives in ``repro.serve``: build an
    ``Engine`` (``Engine.from_lease`` / ``Engine.local``), ``submit``
    ``Request`` objects, and drive ``engine.step()`` — continuous
    batching, slot recycling, and lease-budgeted paged-KV tiering
    (``KVBudget``) are handled there.  The step factories below remain
    as the engine's building blocks and for the dry-run lowering cells;
    ``make_lease_session`` remains for encdec models and single-batch
    deployments but new code should prefer the engine."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.tiering import TieringPolicy
from repro.models.api import Model
from repro.models.config import ModelConfig, ShapeConfig
from repro.sharding.partition import Rules, tree_shardings
from repro.sharding.profiles import make_rules


def make_prefill_step(model: Model):
    """prefill_step(params, batch, cache) -> (next_token_logits, cache)."""

    def prefill_step(params, batch, cache):
        out = model.prefill(params, batch, cache)
        return out  # (logits, cache[, enc_states])

    return prefill_step


def make_decode_step(model: Model):
    """decode_step(params, carry) -> (logits, new_carry).

    carry = {tokens (B,1), cache, index ()} (+ enc_states for enc-dec).
    Greedy-samples the next token into the carry so the step is
    self-contained for a generation loop.
    """
    cfg = model.cfg

    def decode_step(params, carry):
        tokens, cache, index = carry["tokens"], carry["cache"], carry["index"]
        if cfg.family == "encdec":
            logits, new_cache = model.decode(params, tokens, cache, index,
                                             carry["enc_states"])
        else:
            logits, new_cache = model.decode(params, tokens, cache, index)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        new_carry = dict(carry)
        new_carry.update(tokens=next_tok[:, None], cache=new_cache,
                         index=index + 1)
        return logits, new_carry

    return decode_step


def decode_carry_specs(model: Model, shape: ShapeConfig,
                       cache_dtype=jnp.bfloat16) -> Dict[str, Any]:
    """ShapeDtypeStructs for the decode carry (no allocation)."""
    cfg = model.cfg
    B, S = shape.global_batch, shape.seq_len
    cache = jax.eval_shape(
        lambda: model.init_cache(B, S, dtype=cache_dtype))
    carry = {
        "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
        "cache": cache,
        "index": jax.ShapeDtypeStruct((), jnp.int32),
    }
    if cfg.family == "encdec":
        carry["enc_states"] = jax.ShapeDtypeStruct(
            (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
    return carry


@dataclasses.dataclass(frozen=True)
class LeaseServeSession:
    """Everything a serving worker needs from its pool lease.

    .. deprecated:: superseded by ``repro.serve.Engine.from_lease`` for
       request-level serving; kept for encdec and fixed-batch loops."""

    mesh: Mesh
    rules: Rules
    policy: TieringPolicy
    prefill_step: Any          # jitted
    decode_step: Any           # jitted

    @property
    def kv_spill(self) -> bool:
        return self.policy.kv_spill


def make_lease_session(model: Model, shape: ShapeConfig,
                       lease) -> LeaseServeSession:
    """Bind a ``repro.pool.Lease`` to a runnable serving session.

    The lease's allocation determines the mesh shape (pod span → mesh
    axes) and its tier-2 reservation determines the KV spill policy —
    serving capacity and KV paging are composed by the orchestrator, not
    hard-coded per deployment.  The returned steps run scoped to the
    lease's mesh/rules so GSPMD honors the leased model parallelism.
    """
    from repro.sharding.partition import use_rules

    mesh, policy = lease.materialize()
    rules = make_rules(model.cfg, shape, mesh, fsdp=False)

    def scoped(fn, donate=()):
        jitted = jax.jit(fn, donate_argnums=donate)

        def call(*args):
            with use_rules(rules, mesh), jax.set_mesh(mesh):
                return jitted(*args)
        return call

    return LeaseServeSession(
        mesh=mesh, rules=rules, policy=policy,
        prefill_step=scoped(make_prefill_step(model)),
        # donate the decode carry (the KV cache dominates it) so the
        # token loop updates in place instead of copying the cache
        decode_step=scoped(make_decode_step(model), donate=(1,)))


def decode_carry_shardings(model: Model, mesh: Mesh, rules: Rules,
                           shape: ShapeConfig) -> Dict[str, Any]:
    cfg = model.cfg
    cache_ax = model.cache_axes()
    out = {
        "tokens": NamedSharding(mesh, rules.spec("batch", None)),
        "cache": tree_shardings(mesh, rules, cache_ax),
        "index": NamedSharding(mesh, rules.spec()),
    }
    if cfg.family == "encdec":
        out["enc_states"] = NamedSharding(
            mesh, rules.spec("batch", None, "embed"))
    return out
