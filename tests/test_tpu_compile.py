"""Compile rehearsal: the serving path's Pallas kernel and the engine's
decode program, compiled by the TPU compiler for a described (not
attached) v5e chip at real model widths.

Nothing runs; the compile alone catches what interpret mode cannot
(block shapes the (8, 128) tiling refuses, VMEM overruns).  The topology
is described inside a fixture, never at import, so that under several
pytest workers only the worker given this file loads the TPU library.
"""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention import paged_decode_attention
from repro.models.api import build_model
from repro.serve.engine import decode_program

# engine geometry of the chip smoke run: 8 slots x 1024 tokens, 32-token
# pages, one trash page
SLOTS, PAGE, PMAX = 8, 32, 32
POOL_PAGES = SLOTS * PMAX + 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "qwen3-14b",
                                  "mixtral-8x7b"])
def test_paged_attention_compiles_for_v5e(arch, one_chip,
                                          no_persistent_cache):
    cfg = get_config(arch)
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = jnp.bfloat16

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(q, k, v, pt, ln):
        return paged_decode_attention(q, k, v, pt, ln,
                                      sliding_window=cfg.sliding_window,
                                      interpret=False)

    compiled = jax.jit(call).lower(
        spec((SLOTS, H, D), dt),
        spec((POOL_PAGES, PAGE, KV, D), dt),
        spec((POOL_PAGES, PAGE, KV, D), dt),
        spec((SLOTS, PMAX), jnp.int32),
        spec((SLOTS,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_decode_updates_pool_in_place_on_v5e(one_chip, monkeypatch,
                                                    no_persistent_cache):
    """The engine's decode program at the decode-heavy cell's geometry
    (Qwen3-4B widths, 12 layers, 16 slots x 4096 tokens, 32-token pages)
    moves no layer of the pool: no pool-sized slice, update or copy, and
    the donated pool is aliased whole from input to output."""
    cfg = dataclasses.replace(
        get_config("qwen3-14b"), n_layers=12, d_model=2560, n_heads=32,
        n_kv_heads=8, head_dim=128, d_ff=9728, vocab=151936,
        rope_theta=1e6, norm_eps=1e-6, tie_embeddings=True,
        param_dtype="bfloat16", compute_dtype="bfloat16")
    slots, seq, page = 16, 4096, 32
    model = build_model(cfg)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: spec(a.shape, a.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    leaf = (cfg.n_layers, slots * seq // page + 1, page, cfg.n_kv_heads,
            cfg.head_dim)
    pool = {"k": spec(leaf, jnp.bfloat16), "v": spec(leaf, jnp.bfloat16)}
    # the kernel picks interpret mode from the default backend, which
    # here is the CPU; the program is lowered for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lowered = decode_program(model).lower(
        params, spec((slots, 1), jnp.int32), pool,
        spec((slots, seq // page), jnp.int32), spec((slots,), jnp.int32))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text

    layer_elems = math.prod(leaf[1:])
    moved = [line.strip() for line in text.splitlines()
             if (m := re.search(r"= \w+\[([\d,]*)\]\S* "
                                r"(dynamic-slice|dynamic-update-slice|copy)\(",
                                line))
             and math.prod(int(d) for d in m.group(1).split(",") if d)
             >= layer_elems]
    assert not moved, moved[:4]
    pool_bytes = 2 * math.prod(leaf) * jnp.dtype(jnp.bfloat16).itemsize
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes
