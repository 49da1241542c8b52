"""The engine's decode step carries the paged KV pool through the layer
loop as scan carry, and the kernel reads each layer out of the stacked
pool; where the head dim is a multiple of 128
(``serve.engine.donates_pool``) the jitted step donates the pool and so
updates it in place.  Pinned here: tokens and the final pool are
bit-identical to a decode that moves the pool through the layer scan as
scanned inputs and outputs (dense and MoE, donated or not), the pre-step
pool is consumed by the step (solo and on a shared ``PoolArbiter``
pool) and the compiled step aliases the whole pool; a pool that is not
donated is left intact."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.configs import SMOKE_ARCHS
from repro.core.tiering import KVBudget
from repro.models import layers as L
from repro.models import moe
from repro.models import transformer as T
from repro.models.api import build_model
from repro.serve import (Engine, EngineConfig, PoolArbiter, Request,
                         RequestStatus, burst_trace, run_trace)


def _cfg():
    return EngineConfig(max_slots=3, max_seq=64, page_size=8)


def _model(arch, head_dim=128):
    """A smoke model; at head dim 128 the engine donates its pool."""
    return build_model(dataclasses.replace(SMOKE_ARCHS[arch],
                                           head_dim=head_dim))


def _scanned_pool_decode(model):
    """The decode step with the pool as the layer scan's scanned input
    and stacked output: each layer's pool is sliced out, written, and
    stacked into a new pool (one layer axis of 1 for the block)."""
    cfg = model.cfg
    if cfg.family == "moe":
        block = partial(moe.block_fwd_paged, cfg=cfg)
    else:
        block = partial(T.block_fwd_paged, cfg=cfg)

    def paged_decode(params, toks, pool, table, lengths):
        params = T.cast_params(params, cfg)
        x = T._embed_inputs(params, cfg, {"tokens": toks})
        positions = lengths[:, None].astype(jnp.int32)

        def body(x, scanned):
            layer_params, kp, vp = scanned
            x, kp, vp = block(layer_params, x, positions=positions,
                              k_pages=kp[None], v_pages=vp[None],
                              layer=jnp.int32(0), page_table=table,
                              lengths=lengths)
            return x, (kp[0], vp[0])

        x, (nk, nv) = lax.scan(body, x, (params["layers"], pool["k"],
                                         pool["v"]))
        x = L.apply_norm(x, params["final_norm"], cfg.norm_type)
        if cfg.family == "moe":
            logits = L.unembed(params["embedding"], x, cfg.vocab)
        else:
            logits = T.logits_fn(params, cfg, x)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt[:, None], {"k": nk, "v": nv}

    return jax.jit(paged_decode)


@pytest.mark.parametrize("arch,head_dim",
                         [("qwen1.5-0.5b", 128), ("mixtral-8x7b", 128),
                          ("qwen1.5-0.5b", 16)],
                         ids=["qwen1.5-0.5b", "mixtral-8x7b",
                              "qwen1.5-0.5b-not-donated"])
def test_inplace_decode_bit_identical_to_scanned_pool(arch, head_dim):
    """Same trace, same weights: the engine's carried pool (donated at
    head dim 128, not at 16) and a scanned-pool decode give the same
    tokens and a bit-equal pool (trash page included), through tier-2
    spills and fetches."""
    model = _model(arch, head_dim)
    params = model.init(jax.random.PRNGKey(0))
    budget = KVBudget(tier1_pages=6, tier2_bytes=1e9, page_size=8)
    trace = burst_trace(5, prompt_len=12, max_new_tokens=10,
                        vocab=model.cfg.vocab, seed=0)
    inplace = Engine.local(model, _cfg(), params=params, budget=budget)
    scanned = Engine.local(model, _cfg(), params=params, budget=budget)
    scanned._decode_fn = scanned._scoped(_scanned_pool_decode(model))
    h_in = run_trace(inplace, trace)
    h_sc = run_trace(scanned, trace)
    assert inplace.stats()["preempt_swaps"] > 0, "tier-2 not exercised"
    assert all(h.status is RequestStatus.DONE for h in h_in)
    assert [h.tokens for h in h_in] == [h.tokens for h in h_sc]
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), inplace._pool, scanned._pool)


@pytest.mark.parametrize("mode", ["solo", "arbiter", "head-dim-16"])
def test_decode_step_donates_pool_where_in_place(mode):
    """After a step that decodes, every pool leaf held before it is
    deleted (donated to the step) where the pool is updated in place,
    and the engine holds the live pool; on a shared arbiter pool the
    other tenant sees the new arrays.  A pool of head dim 16 is not
    donated: the step leaves it intact."""
    model = _model("qwen1.5-0.5b", 16 if mode == "head-dim-16" else 128)
    params = model.init(jax.random.PRNGKey(0))
    if mode == "arbiter":
        arb = PoolArbiter(12, page_size=8)
        eng, other = (Engine.local(model, _cfg(), params=params,
                                   budget=KVBudget(page_size=8),
                                   arbiter=arb, tenant=t)
                      for t in ("a", "b"))
    else:
        eng = Engine.local(model, _cfg(), params=params)
    h = eng.submit(Request(tuple(range(1, 13)), 4))
    eng.step()                                  # admission
    assert h.status is RequestStatus.RUNNING
    before, n = jax.tree.leaves(eng._pool), len(h.tokens)
    eng.step()
    assert len(h.tokens) == n + 1, "the step did not decode"
    donated = mode != "head-dim-16"
    assert all(leaf.is_deleted() == donated for leaf in before)
    live = jax.tree.leaves(eng._pool)
    assert not any(leaf.is_deleted() for leaf in live)
    if mode == "arbiter":
        assert all(a is b for a, b in zip(jax.tree.leaves(other._pool),
                                          live))
    eng.run_until_idle()
    assert h.status is RequestStatus.DONE


@pytest.mark.parametrize("head_dim", [128, 16])
def test_decode_alias_bytes(head_dim):
    """The compiled step aliases the whole pool where it donates it,
    and nothing where it is not donated."""
    model = _model("qwen1.5-0.5b", head_dim)
    eng = Engine.local(model, _cfg(), params=model.init(
        jax.random.PRNGKey(0)))
    pool_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(eng._pool))
    assert eng.decode_alias_bytes() == (pool_bytes if head_dim == 128
                                        else 0)
