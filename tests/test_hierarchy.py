"""Hierarchical-collective correctness + train-step integration.

Multi-device tests run in a subprocess with forced host devices (the main
pytest process stays at 1 device so smoke tests see a plain CPU)."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_hierarchical_allreduce_equals_flat():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import hierarchy as h
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        x = jnp.arange(32.0).reshape(8, 4)
        flat = h.flat_allreduce(x, mesh, ("pod", "data"))
        hier = h.hierarchical_allreduce(x, mesh, intra_axis="data",
                                        inter_axis="pod")
        np.testing.assert_allclose(np.asarray(flat), np.asarray(hier),
                                   rtol=1e-6)
        # against the literal sum over the sharded axis groups
        ref = np.asarray(x).reshape(4, 2, 4).sum(0, keepdims=True)
        ref = np.tile(ref, (4, 1, 1)).reshape(8, 4)
        np.testing.assert_allclose(np.asarray(flat), ref, rtol=1e-6)
        print("OK")
    """)
    assert "OK" in out


def test_hierarchical_reduces_cross_pod_bytes():
    """The paper's claim, structurally: the pod-crossing collective moves
    1/|data| of the bytes a flat all-reduce moves."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, re
        from repro.core import hierarchy as h
        from repro.launch import hlo_analysis as H
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("pod", "data"))
        x = jnp.zeros((1024, 64))

        def coll_report(fn):
            c = jax.jit(fn).lower(x).compile()
            ops = H.parse_collectives(c.as_text(), pod_size=4)
            return H.collective_summary(ops)

        flat = coll_report(lambda x: h.flat_allreduce(x, mesh, ("pod", "data")))
        hier = coll_report(lambda x: h.hierarchical_allreduce(
            x, mesh, intra_axis="data", inter_axis="pod"))
        print("flat", flat["cross_pod_moved_bytes"],
              "hier", hier["cross_pod_moved_bytes"])
        assert hier["cross_pod_moved_bytes"] < 0.5 * flat["cross_pod_moved_bytes"]
        print("OK")
    """)
    assert "OK" in out


def test_int8_compression_error_feedback():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import hierarchy as h
        # quantize/dequantize roundtrip error is bounded by scale/2
        x = jax.random.normal(jax.random.PRNGKey(0), (128,))
        q, s = h.quantize_int8(x)
        err = np.abs(np.asarray(h.dequantize_int8(q, s) - x))
        assert err.max() <= float(s) * 0.51 + 1e-9
        # error feedback: mean of compressed reductions converges to true mean
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,), ("pod",))
        from repro.core.compat import shard_map
        from jax.sharding import PartitionSpec as P
        def step(x, r):
            return h.compressed_cross_pod_mean(x, "pod", r)
        f = shard_map(step, mesh=mesh, in_specs=(P("pod"), P("pod")),
                      out_specs=(P("pod"), P("pod")), check=False)
        xs = jax.random.normal(jax.random.PRNGKey(1), (2, 64))
        true_mean = jnp.mean(xs, axis=0)
        r = jnp.zeros((2, 64))
        acc = jnp.zeros((2, 64))
        for i in range(20):
            out, r = f(xs, r)
            acc = acc + out
        # time-averaged output approaches the true mean (EF property)
        avg = np.asarray(acc / 20)
        np.testing.assert_allclose(avg[0], np.asarray(true_mean), atol=0.02)
        print("OK")
    """)
    assert "OK" in out


def test_train_step_hierarchical_matches_auto():
    """dp_mode=hierarchical must produce the same loss/params as auto
    (same math, different collective schedule)."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import SMOKE_ARCHS
        from repro.models.api import build_model, input_specs
        from repro.models.config import ShapeConfig
        from repro.launch.mesh import make_mesh
        from repro.optim.adamw import AdamW
        from repro.runtime import train as tr
        from repro.sharding.partition import use_rules
        from repro.sharding.profiles import make_rules

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = SMOKE_ARCHS["olmo-1b"]
        shape = ShapeConfig("train_4k", "train", 32, 8)
        rules = make_rules(cfg, shape, mesh, fsdp=False)
        model = build_model(cfg)
        opt = AdamW(lr=1e-3)
        rng = jax.random.PRNGKey(0)
        batch = {"tokens": jax.random.randint(rng, (8, 32), 0, cfg.vocab),
                 "labels": jax.random.randint(rng, (8, 32), 0, cfg.vocab)}

        results = {}
        for mode in ("auto", "hierarchical"):
            tcfg = tr.TrainStepConfig(dp_mode=mode)
            state = tr.init_state(model, opt, rng, tcfg)
            step, _ = tr.make_train_step(model, opt, shape, mesh=mesh,
                                         rules=rules, tcfg=tcfg)
            with use_rules(rules, mesh), jax.set_mesh(mesh):
                new_state, metrics = jax.jit(step)(state, batch)
            results[mode] = (float(metrics["loss"]),
                             np.asarray(jax.tree.leaves(new_state.params)[0],
                                        np.float32))
        la, pa = results["auto"]
        lh, ph = results["hierarchical"]
        # identical math, different reduction order: bf16-level agreement;
        # Adam normalizes near-zero grads so params may differ by ~2*lr.
        assert abs(la - lh) < 5e-4, (la, lh)
        np.testing.assert_allclose(pa, ph, atol=3e-3)
        print("OK")
    """)
    assert "OK" in out


def test_train_cli_on_two_pod_lease():
    """launch/train.py on a two-pod lease (mesh pod=2, data=2, model=1),
    as chip_smoke.py --four-chips runs it: both dp modes keep the state on
    all 4 devices, agree on the loss, and compile the step once (a state
    that starts off the mesh makes the second step compile again)."""
    out = run_with_devices("""
        import contextlib, io, json, logging
        import jax
        from repro.launch import train

        compiles = []

        class CountStepCompiles(logging.Handler):
            def emit(self, record):
                if "compilation of jit(step)" in record.getMessage():
                    compiles.append(record)

        logging.getLogger("jax").addHandler(CountStepCompiles())
        jax.config.update("jax_log_compiles", True)
        res = {}
        for mode in ("auto", "hierarchical"):
            compiles.clear()
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                rc = train.main([
                    "--arch", "qwen1.5-0.5b", "--smoke", "--steps", "3",
                    "--batch", "8", "--seq", "32", "--pool", "scalepool",
                    "--pool-accels", "16", "--dp-mode", mode,
                    "--ckpt-every", "1000000", "--ckpt-dir", "/dev/null"])
            res[mode] = dict(json.loads(buf.getvalue()), rc=rc,
                             step_compiles=len(compiles))
        print(json.dumps(res))
    """, n_devices=4)
    res = json.loads(out.strip().splitlines()[-1])
    for mode, r in res.items():
        assert r["dp_mode"] == mode
        assert r["mesh"] == {"pod": 2, "data": 2, "model": 1}
        assert r["state_devices"] == 4
        assert r["step_compiles"] == 1, r
        assert r["rc"] == 0 and r["loss_last"] < r["loss_first"]
    # chip_smoke.py's tolerance: same math, another reduction order
    a, h = res["auto"], res["hierarchical"]
    for key in ("loss_first", "loss_last"):
        assert abs(a[key] - h[key]) <= 2e-3 * a[key], (key, a[key], h[key])


def test_tied_parametric_norm_arch_refused_not_crashed():
    """Hierarchical dp is offered to every arch: the tied-embedding,
    parametric-norm qwen family gets hierarchical sharding rules just as
    olmo (non-parametric LN) does, and both match their auto tables."""
    out = run_with_devices("""
        from repro.configs import SMOKE_ARCHS
        from repro.launch.mesh import make_mesh
        from repro.models.config import ShapeConfig
        from repro.sharding.profiles import make_rules

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        shape = ShapeConfig("t", "train", 32, 8)
        for name in ("qwen1.5-0.5b", "olmo-1b"):
            cfg = SMOKE_ARCHS[name]
            hier = make_rules(cfg, shape, mesh, fsdp=False,
                              dp_mode="hierarchical")
            auto = make_rules(cfg, shape, mesh, fsdp=False, dp_mode="auto")
            assert hier.table == auto.table, name
            assert hier.table["batch"] == ("pod", "data"), name
        print("OK")
    """)
    assert "OK" in out


def test_dryrun_smoke_cells():
    """End-to-end dry-run on reduced configs for one arch per family."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    for arch in ("qwen1.5-0.5b", "mixtral-8x7b", "mamba2-780m"):
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
             "--shape", "train_4k", "--mesh", "single", "--smoke",
             "--tag", "pytest", "--out", "/tmp/dryrun_pytest"],
            capture_output=True, text=True, env=env, timeout=580,
            cwd=REPO)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "[FAIL" not in out.stdout, out.stdout
        assert "1 OK" in out.stdout, out.stdout
