"""The trace reduction: busy and idle time, per-program device time and
exposed collective time, on a hand-built trace in the form ``extract``
gives and on a short extract recorded on the chip; and the readings of
``mfu`` and ``paged_attn_roofline`` on those traces, pinned."""

from benchtools import REPO

import json

import pytest

from bench import common, readers, trace

MS = 1_000_000


def _hand():
    # window 0..100 ms; chip 0 runs a decode program 10..40 ms (a kernel
    # 15..25 inside) and an eager write 60..70; chip 1 runs compute
    # 0..30 ms and an all-reduce 20..50 ms (exposed 30..50)
    return {
        "devices": {
            "0": {"ops": [["fusion.1", 10 * MS, 5 * MS, "jit_paged_decode"],
                          ["paged_decode_attention", 15 * MS, 10 * MS, "jit_paged_decode"],
                          ["fusion.2", 25 * MS, 15 * MS, "jit_paged_decode"],
                          ["scatter.3", 60 * MS, 10 * MS, "jit_scatter"]],
                  "modules": [["jit_paged_decode(7)", 10 * MS, 30 * MS],
                              ["jit_scatter(9)", 60 * MS, 10 * MS]]},
            "1": {"ops": [["fusion.4", 0, 30 * MS, "jit_step"],
                          ["all-reduce.5", 20 * MS, 30 * MS, "jit_step"]],
                  "modules": [["jit_step(3)", 0, 50 * MS]]},
        },
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.step", 5 * MS, 40 * MS],
                 ["bench.wait_arrival", 70 * MS, 30 * MS]],
    }


def test_busy_programs_and_collectives_by_hand():
    red = trace.reduce(_hand())
    assert red["window_s"] == pytest.approx(0.1)
    c0, c1 = red["chips"][0], red["chips"][1]
    assert c0["busy_s"] == pytest.approx(0.040)
    assert c0["programs"]["jit_paged_decode"] == {"calls": 1,
                                                  "seconds": pytest.approx(0.030)}
    assert c0["ops_s"]["jit_paged_decode/paged_decode_attention"] == pytest.approx(0.010)
    assert c1["busy_s"] == pytest.approx(0.050)
    assert c1["collective_s"] == pytest.approx(0.030)
    assert c1["collective_exposed_s"] == pytest.approx(0.020)
    # chip 0's longest gap, 70..100 ms, fell while the host waited for a request
    assert red["idle_gaps"][0] == ["bench.wait_arrival", pytest.approx(0.030)]
    assert red["device_ops"][0][1] == pytest.approx(0.015)


def test_readers_on_the_hand_trace():
    rec = {"trace": trace.reduce(_hand()), "traced": {"steps": [0, 1]},
           "steps": [{"rows": 2, "lengths": [40, 8], "prefills": []}],
           "chips": 2}
    assert readers.idle_frac(rec) == pytest.approx(60.0)        # worst chip
    assert readers.decode_step_ms(rec) == pytest.approx(30.0)
    assert readers.program_share(rec, "other") == pytest.approx(25.0)
    assert readers.program_share(rec, "prefill") is None        # none ran


def test_interval_helpers():
    u = trace.union([(5, 8), (0, 3), (2, 4)])
    assert u == [(0, 4), (5, 8)]
    assert trace.subtract(u, [(1, 6)]) == 1 + 2


def test_recorded_chip_trace_has_the_names_the_readers_look_for():
    # 80 ms of a chat-cell trace from a TPU v5 lite (one decode step, one
    # prefill, two page writes), op names as ``extract`` keeps them
    ext = json.loads((REPO / "tests" / "bench" / "data"
                      / "chip_trace_chat_v5e.json").read_text())
    tr = trace.reduce(ext)
    chip = tr["chips"][0]
    assert 0 < chip["busy_s"] <= tr["window_s"]
    rec = {"trace": tr}
    shares = {w: readers.program_share(rec, w)
              for w in ("prefill", "decode", "other")}
    assert all(v is not None and v > 0 for v in shares.values()), shares
    assert sum(shares.values()) == pytest.approx(100.0, abs=0.5)
    assert readers.decode_step_ms(rec) == pytest.approx(
        1e3 * chip["programs"]["jit_paged_decode"]["seconds"])
    kernel = [k for k in chip["ops_s"] if readers.KERNEL in k]
    assert kernel and all(k.startswith("jit_paged_decode/") for k in kernel)
    assert not any(trace.CONTAINER.match(k.split("/", 1)[1])
                   for k, _ in tr["device_ops"])


def test_op_names_from_hlo_text():
    assert trace.op_name("%copy.54 = bf16[1,1025]{1,0} copy(bf16[1,1025] "
                         "%x)") == "copy.54"
    assert trace.op_name("all-reduce.5") == "all-reduce.5"
    assert trace.CONTAINER.match("while.5")
    assert not trace.CONTAINER.match("copy.5")


# three decode steps and their prefills, as ``bench/serve.py`` records them
STEPS = [{"rows": 3, "lengths": [300, 1201, 33], "prefills": [256]},
         {"rows": 2, "lengths": [301, 1202], "prefills": []},
         {"rows": 4, "lengths": [302, 1203, 35, 2048], "prefills": [1536, 32]}]


@pytest.mark.parametrize("source,config,mfu,roofline", [
    ("chip_trace_chat_v5e.json", "qwen1.5-0.5b",
     7.97722495852485, 17.150152396053),
    ("chip_trace_chat_spans_v5e.json", "qwen1.5-0.5b",
     7.956027776649746, 18.109645086686175),
    ("hand", "qwen3-4b", 23.803046127106597, 4.092999267399267)])
def test_mfu_and_roofline_read_as_pinned(source, config, mfu, roofline):
    # the readings of both cells' configurations through the work module
    # their files name; pinned to the last digit, as they read when the
    # readers counted with ``bench.work`` directly
    ext = (_hand() if source == "hand" else json.loads(
        (REPO / "tests" / "bench" / "data" / source).read_text()))
    cfg = json.loads((REPO / "bench" / "configs" / f"{config}.json").read_text())
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    rec = {"trace": trace.reduce(ext), "traced": {"steps": [0, 3]},
           "steps": STEPS, "model": common.model_config(cfg),
           "work": common.load_module(REPO / cfg["work"]),
           "page_size": 32, "kv_itemsize": 2,
           "peaks": peaks["TPU v5 lite"], "chips": 1}
    assert readers.mfu(rec) == mfu
    assert readers.paged_attn_roofline(rec) == roofline
