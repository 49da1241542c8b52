"""The traffic generators: deterministic under the seed, and every seed
gets the same work in the same order."""

from benchtools import REPO

import json
from collections import Counter

import numpy as np

from bench import generators as traffic

CHAT = json.loads((REPO / "bench" / "traffic" / "chat.json").read_text())
HEAVY = json.loads((REPO / "bench" / "traffic" / "decode-heavy.json").read_text())
BIG_SEED = 2**31 + 12345


def _key(reqs):
    return [(r.due_s, r.max_new, r.prompt.tobytes()) for r in reqs]


def test_open_loop_is_deterministic_under_the_seed():
    a = traffic.open_loop(CHAT, BIG_SEED, 30.0, 151936)
    b = traffic.open_loop(CHAT, BIG_SEED, 30.0, 151936)
    c = traffic.open_loop(CHAT, BIG_SEED + 1, 30.0, 151936)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_every_seed_gets_the_same_block_of_work():
    k = CHAT["block"]
    shapes = []
    for seed in (1, 2, BIG_SEED):
        reqs = traffic.open_loop(CHAT, seed, 120.0, 151936)[:k]
        assert len(reqs) == k
        shapes.append((Counter(len(r.prompt) for r in reqs),
                       Counter(r.max_new for r in reqs),
                       [(r.due_s, len(r.prompt), r.max_new) for r in reqs]))
    assert shapes[0] == shapes[1] == shapes[2]


def test_lengths_stay_in_their_clip_and_ids_in_the_vocab():
    for r in traffic.open_loop(CHAT, 7, 60.0, 151936):
        assert CHAT["prompt"]["min"] <= len(r.prompt) <= CHAT["prompt"]["max"]
        assert CHAT["output"]["min"] <= r.max_new <= CHAT["output"]["max"]
        assert 0 <= r.prompt.min() and r.prompt.max() < 151936
    med = np.median(traffic.lognormal_lengths(CHAT["prompt"], 1001))
    assert med == CHAT["prompt"]["median"]


def test_closed_loop_clients_are_deterministic_and_distinct():
    a = [next(s) for s in traffic.closed_loop(HEAVY, BIG_SEED, 151936)]
    b = [next(s) for s in traffic.closed_loop(HEAVY, BIG_SEED, 151936)]
    assert _key(a) == _key(b)
    assert len(a) == HEAVY["clients"]
    assert len({r.prompt.tobytes() for r in a}) == len(a)
