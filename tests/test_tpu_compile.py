"""Compile rehearsal: the serving path's Pallas kernel, compiled by the TPU
compiler for a described (not attached) v5e chip at real model widths.

Nothing runs; the compile alone catches what interpret mode cannot
(block shapes the (8, 128) tiling refuses, VMEM overruns).  The topology
is described inside a fixture, never at import, so that under several
pytest workers only the worker given this file loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention import paged_decode_attention

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
