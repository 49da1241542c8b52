"""The harness end to end at test size on the CPU, with its look for a
chip skipped: cells defined by data alone run and come out correct; with
the timed path broken underneath, or the fp8 control in the program's
place, they come out not correct."""

from benchtools import REPO, cell_root  # noqa: F401  (fixture)

import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import common, reference, run, serve
from repro.serve import engine as engine_mod

SEED = 2**31 + 99


def _run(root, cell, seconds=3.0):
    args = run.parse(["--workload", cell, "--seed", str(SEED),
                      "--seconds", str(seconds)])
    return run.run(args, root=root, require_chip=False,
                   t_start=time.perf_counter())


@pytest.mark.parametrize("cell,metric", [("tiny-gqa.ol", "ttft_p90_s"),
                                         ("tiny-gqa.cl", "serve_tok_s")])
def test_data_defined_serving_cell_runs_correct(cell_root, cell, metric):
    result, checks = _run(cell_root, cell)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {metric, "setup_s"} <= set(result["metrics"])
    assert result["metrics"][metric]["value"] > 0


def test_altered_token_is_not_correct(cell_root, monkeypatch):
    real = engine_mod.Engine._emit

    def altered(self, st, tok, at):
        return real(self, st, (tok + 1) % self.model.cfg.vocab, at)
    monkeypatch.setattr(engine_mod.Engine, "_emit", altered)
    result, checks = _run(cell_root, "tiny-gqa.ol")
    assert not result["correct"], checks


def test_decode_state_left_unchanged_is_not_correct(cell_root, monkeypatch):
    real_build = serve.build

    def build(cell, seed):
        engine, mcfg, params = real_build(cell, seed)
        decode = engine._decode_fn

        def unchanged(params, toks, pool, table, lengths):
            new_toks, _ = decode(params, toks, pool, table, lengths)
            return new_toks, pool
        engine._decode_fn = unchanged
        return engine, mcfg, params
    monkeypatch.setattr(serve, "build", build)
    result, checks = _run(cell_root, "tiny-gqa.cl")
    assert not result["correct"], checks


def test_serving_control_reads_above_the_limit(cell_root):
    cell = common.load_cell("tiny-gqa.cl", cell_root)
    engine, mcfg, params = serve.build(cell, SEED)
    serve.warm_up(engine, cell)
    window = serve.run_window(engine, cell, SEED, 2.0)
    served = serve.sample_finished(window, SEED, 64)
    prog, ctrl = reference.served_gaps(params, mcfg, served, pad_to=128,
                                       control=True)
    limit = cell.config["serve"]["check"]["max_logit_gap"]
    assert max(prog) <= limit < max(ctrl)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_no_result_without_a_chip_or_without_the_program(tmp_path, where):
    cwd = REPO
    if where == "alone":
        cwd = tmp_path / "checkout"
        shutil.copytree(REPO / "bench", cwd / "bench")
        shutil.copytree(REPO / "tests" / "bench", cwd / "tests" / "bench")
        shutil.copy(REPO / "BENCHMARK.json", cwd / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen1.5-0.5b.chat",
         "--seed", "1", "--seconds", "1"], capture_output=True, text=True,
        env=env, cwd=cwd, timeout=300)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
