"""Paged decode-attention kernel parity: the Pallas kernel vs the dense
gather reference, across page sizes / dtypes / GQA groupings, and the
layout-invariance contract (same logical KV in different physical page
layouts -> bitwise-identical output)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import (paged_decode_attention,
                                           paged_decode_attention_layered)
from repro.kernels.ops import paged_attention
from repro.kernels.ref import paged_attention_ref


def _case(seed, *, B, KV, G, D, P, ps, PMAX, dtype, max_len=None):
    """Random pool + page table + lengths.  Table entries beyond a
    sequence's live pages point at arbitrary (trash-like) pages — the
    kernel must never read them."""
    rng = np.random.RandomState(seed)
    H = KV * G
    q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
    kp = jnp.asarray(rng.standard_normal((P, ps, KV, D)), dtype)
    vp = jnp.asarray(rng.standard_normal((P, ps, KV, D)), dtype)
    pt = jnp.asarray(rng.randint(0, P, size=(B, PMAX)), jnp.int32)
    hi = max_len if max_len is not None else PMAX * ps
    lengths = jnp.asarray(rng.randint(0, hi + 1, size=(B,)), jnp.int32)
    return q, kp, vp, pt, lengths


@pytest.mark.parametrize("ps", [4, 8, 16])
def test_kernel_matches_ref_across_page_sizes(ps):
    q, kp, vp, pt, lengths = _case(0, B=4, KV=2, G=2, D=16, P=9, ps=ps,
                                   PMAX=5, dtype=jnp.float32)
    got = paged_decode_attention(q, kp, vp, pt, lengths)
    want = paged_attention_ref(q, kp, vp, pt, lengths)
    assert jnp.allclose(got, want, atol=1e-5, rtol=1e-5), (
        f"ps={ps}: max err {jnp.max(jnp.abs(got - want))}")


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_kernel_matches_ref_across_dtypes(dtype, atol):
    q, kp, vp, pt, lengths = _case(1, B=3, KV=2, G=1, D=8, P=7, ps=8,
                                   PMAX=4, dtype=dtype)
    got = paged_decode_attention(q, kp, vp, pt, lengths)
    want = paged_attention_ref(q, kp, vp, pt, lengths)
    assert jnp.allclose(got.astype(jnp.float32), want.astype(jnp.float32),
                        atol=atol, rtol=atol)


@pytest.mark.parametrize("layer", [None, 0, 1, 2],
                         ids=["one-layer", "layer0", "layer1", "layer2"])
def test_kernel_gqa_and_sliding_window(layer):
    """GQA and the sliding window; with ``layer`` set, the layered kernel
    reads that layer of a stacked 3-layer pool and must equal the 4-D
    kernel on that layer's slice bit for bit."""
    q, kp, vp, pt, lengths = _case(2, B=3, KV=2, G=4, D=16, P=8, ps=8,
                                   PMAX=4, dtype=jnp.float32)
    if layer is not None:
        rng = np.random.RandomState(7)
        k_stack = jnp.asarray(rng.standard_normal((3,) + kp.shape),
                              jnp.float32).at[layer].set(kp)
        v_stack = jnp.asarray(rng.standard_normal((3,) + vp.shape),
                              jnp.float32).at[layer].set(vp)
    for win in (None, 10):
        got = paged_decode_attention(q, kp, vp, pt, lengths,
                                     sliding_window=win)
        want = paged_attention_ref(q, kp, vp, pt, lengths,
                                   sliding_window=win)
        assert jnp.allclose(got, want, atol=1e-5, rtol=1e-5)
        if layer is not None:
            layered = paged_decode_attention_layered(
                q, k_stack, v_stack, pt, lengths,
                jnp.asarray([layer], jnp.int32), sliding_window=win)
            np.testing.assert_array_equal(np.asarray(layered),
                                          np.asarray(got))


def test_kernel_zero_length_rows_emit_zeros():
    q, kp, vp, pt, _ = _case(3, B=3, KV=2, G=2, D=8, P=6, ps=4, PMAX=3,
                             dtype=jnp.float32)
    lengths = jnp.asarray([0, 5, 0], jnp.int32)
    got = paged_decode_attention(q, kp, vp, pt, lengths)
    assert jnp.all(got[0] == 0) and jnp.all(got[2] == 0)
    assert jnp.all(jnp.isfinite(got))


def test_kernel_layout_invariance_bitwise():
    """The same logical KV scattered into two different physical page
    layouts must produce BITWISE-identical attention — the engine's
    token-fidelity-under-preemption contract rests on this."""
    rng = np.random.RandomState(4)
    B, KV, G, D, ps, PMAX = 2, 2, 2, 16, 8, 4
    P = PMAX * B + 3
    H = KV * G
    S = PMAX * ps
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k_log = rng.standard_normal((B, S, KV, D)).astype(np.float32)
    v_log = rng.standard_normal((B, S, KV, D)).astype(np.float32)
    lengths = jnp.asarray([S - 3, ps + 1], jnp.int32)

    def layout(perm_seed):
        prng = np.random.RandomState(perm_seed)
        kp = prng.standard_normal((P, ps, KV, D)).astype(np.float32)
        vp = prng.standard_normal((P, ps, KV, D)).astype(np.float32)
        ids = prng.permutation(P)[:B * PMAX].reshape(B, PMAX)
        for b in range(B):
            for j in range(PMAX):
                kp[ids[b, j]] = k_log[b, j * ps:(j + 1) * ps]
                vp[ids[b, j]] = v_log[b, j * ps:(j + 1) * ps]
        return (jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(ids, jnp.int32))

    kp1, vp1, pt1 = layout(10)
    kp2, vp2, pt2 = layout(11)
    out1 = paged_decode_attention(q, kp1, vp1, pt1, lengths)
    out2 = paged_decode_attention(q, kp2, vp2, pt2, lengths)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_kernel_matches_dense_softmax():
    """Paged gather == plain dense GQA softmax over the logical prefix
    (independent oracle, not the paged ref)."""
    rng = np.random.RandomState(5)
    B, KV, G, D, ps, PMAX = 2, 2, 2, 16, 8, 3
    P, H, S = 11, KV * G, PMAX * ps
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((P, ps, KV, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, ps, KV, D)), jnp.float32)
    pt = jnp.asarray(rng.randint(0, P, size=(B, PMAX)), jnp.int32)
    lengths = jnp.asarray([S, 13], jnp.int32)
    got = paged_decode_attention(q, kp, vp, pt, lengths)

    k = np.asarray(kp)[np.asarray(pt)].reshape(B, S, KV, D)
    v = np.asarray(vp)[np.asarray(pt)].reshape(B, S, KV, D)
    for b in range(B):
        n = int(lengths[b])
        qg = np.asarray(q[b]).reshape(KV, G, D)
        s = np.einsum("hgd,khd->hgk", qg, k[b, :n]) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        o = np.einsum("hgk,khd->hgd", p, v[b, :n]).reshape(H, D)
        np.testing.assert_allclose(np.asarray(got[b]), o, atol=1e-5,
                                   rtol=1e-5)


def test_ops_wrapper_model_layout():
    """ops.paged_attention takes/returns the model's (B, 1, H, D), over
    the stacked pool read at a layer."""
    q, kp, vp, pt, lengths = _case(6, B=3, KV=2, G=2, D=8, P=6, ps=4,
                                   PMAX=3, dtype=jnp.float32)
    out = paged_attention(q[:, None], kp[None], vp[None], pt, lengths,
                          jnp.int32(0))
    assert out.shape == (3, 1, 4, 8)
    want = paged_attention_ref(q, kp, vp, pt, lengths)
    assert jnp.allclose(out[:, 0], want, atol=1e-5, rtol=1e-5)
