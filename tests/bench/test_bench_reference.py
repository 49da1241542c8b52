"""The plain reference forward agrees with the program's bucketed
prefill and its paged decode (Pallas kernel, interpret mode) at test
size on the CPU, in float32 so that only rounding separates them."""

from benchtools import REPO  # noqa: F401  (puts the repository on sys.path)

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.weights import make_params
from repro.configs import get_config
from repro.models.api import build_model


def _f32(arch):
    return dataclasses.replace(get_config(arch, smoke=True), tie_embeddings=True,
                               param_dtype="float32", compute_dtype="float32")


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "qwen3-14b"])
def test_reference_matches_prefill_and_paged_decode(arch):
    cfg = _f32(arch)
    model = build_model(cfg)
    params = make_params(model, 2**31 + 5, jnp.float32)
    rng = np.random.default_rng(0)
    plen, bucket, ps = 21, 32, 8
    toks = rng.integers(0, cfg.vocab, plen + 1).astype(np.int32)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :plen] = toks[:plen]
    logits, cache = model.prefill_at(params, {"tokens": jnp.asarray(padded)},
                                     model.init_cache(1, bucket, dtype=jnp.float32),
                                     jnp.int32(plen - 1))
    ref = reference.logits(params, cfg, toks, [plen - 1, plen], pad_to=48)
    np.testing.assert_allclose(np.asarray(logits)[0, 0], np.asarray(ref[0]),
                               atol=2e-4, rtol=2e-4)
    # the prompt's pages, scattered into a pool out of order, then one decode
    n_pages = bucket // ps
    phys = np.array([3, 0, 2, 1], np.int32)
    pool = {}
    for name in ("k", "v"):
        leaf = cache[name][:, 0].reshape(cfg.n_layers, n_pages, ps,
                                         cfg.n_kv_heads, cfg.head_dim)
        pool[name] = jnp.zeros((cfg.n_layers, 5) + leaf.shape[2:],
                               jnp.float32).at[:, phys].set(leaf)
    table = jnp.asarray(np.append(phys, 4)[None, :])
    dec, _ = model.decode_paged(params, jnp.asarray(toks[None, plen:plen + 1]),
                                pool, table, jnp.asarray([plen], jnp.int32))
    np.testing.assert_allclose(np.asarray(dec)[0, 0], np.asarray(ref[1]),
                               atol=2e-4, rtol=2e-4)
