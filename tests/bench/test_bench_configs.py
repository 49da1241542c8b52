"""A configuration's own files say how its model is built, checked and
counted: unknown keys are errors, the reference and the work module come
from the configuration file, and a cell whose model the dense reference
does not compute (a sliding window in every layer) runs correct through
the unchanged harness with its own reference and work module, and not
correct against the dense reference."""

from benchtools import REPO, cell_root  # noqa: F401  (fixture)

import json
import time

import pytest

from bench import common, readers, reference, run, serve, trace

SEED = 2**31 + 1234
SWA = "tiny-gqa-swa.cl"
MS = 1_000_000


def _config(root, name="tiny-gqa"):
    return json.loads((root / "bench" / "configs" / f"{name}.json").read_text())


def _write(root, config, name="tiny-gqa"):
    (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(config))


def _run(root, cell, seconds=3.0, trace_on=0):
    args = run.parse(["--workload", cell, "--seed", str(SEED),
                      "--seconds", str(seconds), "--trace", str(trace_on)])
    return run.run(args, root=root, require_chip=False,
                   t_start=time.perf_counter())


@pytest.mark.parametrize("edit", ["top_level", "missing_reference",
                                  "missing_work"])
def test_a_key_the_harness_would_not_read_fails_the_cell(cell_root, edit):
    config = _config(cell_root)
    if edit == "top_level":
        config["num_experts"] = 64
    else:
        del config[edit.split("_", 1)[1]]
    _write(cell_root, config)
    with pytest.raises(common.BenchError):
        common.load_cell("tiny-gqa.ol", cell_root)
    with pytest.raises(common.BenchError):
        common.model_config(config)


def test_a_published_key_the_file_maps_is_applied(cell_root):
    # a catalog model keeps its published keys; one that HF_KEYS does not
    # name is applied through the file's own map, or the file is refused
    config = _config(cell_root)
    config["num_experts"] = 4
    config["map"] = {"num_experts": "n_experts"}
    _write(cell_root, config)
    common.load_cell("tiny-gqa.ol", cell_root)
    assert common.model_config(config).n_experts == 4
    for own, match in (({"num_experts": "n_experts",                # absent
                          "moe_intermediate_size": "expert_d_ff"}, "map"),
                       ({"num_experts": "n_experts",                # in HF_KEYS
                         "tie_word_embeddings": "tie_embeddings"}, "map"),
                       ({"num_experts": "experts"}, "not ModelConfig"),
                       ({"num_experts": "tie_embeddings"}, "more than once")):
        config["map"] = own
        with pytest.raises(common.BenchError, match=match):
            common.model_config(config)


def test_an_unknown_program_field_fails_the_configuration(cell_root):
    config = _config(cell_root)
    config["program"] = {"sliding_window": 16, "layer_kinds": ["full"]}
    with pytest.raises(common.BenchError, match="layer_kinds"):
        common.model_config(config)


def test_program_fields_apply_last(cell_root):
    # program sets what no published key states, and nothing that one does
    config = _config(cell_root, "tiny-gqa-swa")
    config["program"]["n_layers"] = 1
    cfg = common.model_config(config)
    assert (cfg.sliding_window, cfg.n_layers) == (16, 1)
    assert common.model_config(_config(cell_root)).sliding_window is None
    for field in ("n_layers", "n_experts"):
        config = _config(cell_root, "tiny-gqa-swa")
        config.update(num_hidden_layers=3, num_experts=4,
                      map={"num_experts": "n_experts"})
        config["program"][field] = 1
        with pytest.raises(common.BenchError, match="program sets"):
            common.model_config(config)
    config = _config(cell_root, "tiny-gqa-swa")
    config["program"]["param_dtype"] = "float32"
    with pytest.raises(common.BenchError, match="serving dtypes"):
        common.model_config(config)


def test_an_untied_model_is_refused_by_the_dense_reference(cell_root):
    config = _config(cell_root)
    config["tie_word_embeddings"] = False
    cfg = common.model_config(config)          # the harness builds it
    assert not cfg.tie_embeddings
    with pytest.raises(common.BenchError, match="untied"):
        reference.served_gaps({}, cfg, [], pad_to=128)


def test_window_cell_runs_correct_with_its_own_reference(cell_root):
    result, checks = _run(cell_root, SWA)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"]["serve_tok_s"]["value"] > 0


def test_window_cell_is_not_correct_against_the_dense_reference(cell_root):
    config = _config(cell_root, "tiny-gqa-swa")
    config["reference"] = "bench/reference.py"
    _write(cell_root, config, "tiny-gqa-swa")
    result, checks = _run(cell_root, SWA)
    assert not result["correct"], checks
    gap = {c["name"]: c for c in checks}["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_counters_are_the_engines_monotone_ones():
    from repro.obs.metrics import MetricsRegistry

    class Engine:
        def metrics(self):
            reg = MetricsRegistry()
            for name, value in (("clock_s", 2.5), ("busy_s", 1.5),
                                ("running", 3), ("steps", 7)):
                reg.set(f"serve/t0/{name}", value)
            reg.counter("serve/t0/tokens_routed").inc(5)
            return reg
    assert serve._counters(Engine()) == {"steps": 7, "tokens_routed": 5}


def _kernel_trace(seconds):
    # one decode program whose paged kernel ran ``seconds`` on chip 0
    ns = int(seconds * 1e9)
    return {"devices": {"0": {
                "ops": [["paged_decode_attention", MS, ns, "jit_paged_decode"]],
                "modules": [["jit_paged_decode(1)", MS, ns]]}},
            "host": [["bench.window", 0, ns + 2 * MS]]}


def test_window_record_counts_its_own_work_and_the_engine_counters(cell_root):
    cell = common.load_cell(SWA, cell_root)
    engine, mcfg, _ = serve.build(cell, SEED)
    serve.warm_up(engine, cell)
    rec = serve.summarize(serve.run_window(engine, cell, SEED, 3.0),
                          mcfg, cell)
    # the engine's counters over the window, against the harness's records
    ps = rec["page_size"]
    pages = sum(-(-n // ps) for s in rec["steps"] for n in s["prefills"])
    assert pages > 0 and rec["counters"]["pages_written"] == pages
    assert rec["counters"]["tokens_decoded"] == sum(
        s["rows"] for s in rec["steps"])
    assert rec["counters"]["steps"] == len(rec["steps"])
    assert not {"clock_s", "busy_s", "queue_depth"} & set(rec["counters"])
    # the roofline reads what the configuration's work module counts
    rec.update(trace=trace.reduce(_kernel_trace(0.002)),
               traced={"steps": [0, len(rec["steps"])]}, chips=1,
               peaks=common.peaks("TPU v5 lite", cell_root))
    lengths = [s["lengths"] for s in rec["steps"]]
    assert max(n for ls in lengths for n in ls) > mcfg.sliding_window
    work = cell.module("work")
    flops = nbytes = 0
    for ls in lengths:
        f, b = work.paged_attention_work(mcfg, ls, ps, 2)
        flops, nbytes = flops + f, nbytes + b
    pk = rec["peaks"]
    want, _ = readers.roofline_share(flops, nbytes, 0.002,
                                     pk["bf16_flops_per_s"],
                                     pk["hbm_bytes_per_s"])
    assert readers.paged_attn_roofline(rec) == pytest.approx(want, rel=1e-12)
    dense = dict(rec, work=common.load_module(cell_root / "bench" / "work.py"))
    assert readers.paged_attn_roofline(dense) > want      # every key counted


def test_traced_record_carries_the_engine_spans(cell_root, monkeypatch):
    # the CPU has no device plane: the run reads a profile recorded on the
    # chip in its place, and everything else of a traced run is its own
    ext = json.loads((REPO / "tests" / "bench" / "data"
                      / "chip_trace_chat_spans_v5e.json").read_text())
    monkeypatch.setattr(trace, "extract", lambda out_dir: ext)
    seen = {}
    real = common.read_metrics

    def read_metrics(cell, metrics, rec):
        seen.update(rec)
        return real(cell, metrics, rec)
    monkeypatch.setattr(common, "read_metrics", read_metrics)
    result, checks = _run(cell_root, "tiny-gqa.ol", trace_on=1)
    assert result["correct"], checks
    assert seen["trace"]["host_spans"]["spans"]["engine.step"]["count"] > 0
    assert seen["counters"]["pages_written"] > 0
    assert result["device"]["busy_s"] > 0
