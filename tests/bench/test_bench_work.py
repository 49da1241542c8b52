"""bench/work.py against counts made by hand at published widths, and
the roofline share the readers take from such counts."""

from benchtools import REPO  # noqa: F401  (puts the repository on sys.path)

import dataclasses

import pytest

from bench import readers, work
from repro.configs import get_config


def qwen15():
    return get_config("qwen1.5-0.5b")


def qwen3_stage():
    return dataclasses.replace(get_config("qwen3-14b"), n_layers=8)


def test_layer_params_by_hand():
    # qwen1.5-0.5b: q,k,v,o 4 x 1024 x 1024; gated MLP 3 x 1024 x 2816
    assert work.layer_matmul_params(qwen15()) == 4 * 1024 * 1024 + 3 * 1024 * 2816
    # qwen3-14b: q,o 2 x 5120 x 5120; k,v 2 x 5120 x 1024; MLP 3 x 5120 x 17408
    assert work.layer_matmul_params(qwen3_stage()) == 330_301_440


def test_decode_and_prefill_flops_by_hand():
    cfg = qwen15()
    layer = 2 * 12_845_056 + 4 * 16 * 64 * 100
    assert work.decode_flops(cfg, 100) == 24 * layer + 2 * 151_936 * 1024
    # causal prefill of 3 tokens attends to 1 + 2 + 3 keys
    pre = 24 * (2 * 12_845_056 * 3 + 4 * 16 * 64 * 6) + 2 * 151_936 * 1024
    assert work.prefill_flops(cfg, 3) == pre


def test_paged_attention_work_by_hand():
    cfg = qwen3_stage()                     # 8 layers, each attends fully
    flops, nbytes = work.paged_attention_work(cfg, [33, 0, 64], 32, 2)
    assert flops == 8 * 4 * 40 * 128 * (33 + 64)
    page = 32 * 8 * 128 * 2                 # one page of K (or V), bf16
    qo = 2 * 40 * 128 * 2                   # q and out of one row
    assert nbytes == 8 * ((2 * 2 * page + qo) + (2 * 2 * page + qo))


@pytest.mark.parametrize("flops,nbytes,bound", [(197e12, 1.0, "compute"),
                                                (1.0, 819e9, "memory")])
def test_roofline_share(flops, nbytes, bound):
    share, which = readers.roofline_share(flops, nbytes, 2.0, 197e12, 819e9)
    assert which == bound
    assert share == pytest.approx(50.0)
