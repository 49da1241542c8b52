"""Chip smoke test: the quickest proof that the system still runs on a TPU.

    python chip_smoke.py               # one chip: phases (a)-(c)
    python chip_smoke.py --four-chips  # four chips: hierarchical dp only

One process drives every phase through the entry points a user calls, at
the published widths of qwen1.5-0.5b (24 layers, d=1024, 16/16 heads,
vocab 151936) with random weights from a fixed seed:

  (a) kernel  — the paged-attention Pallas kernel against
                ``kernels/ref.paged_attention_ref`` in f32 and bf16, at
                qwen1.5-0.5b's heads (16/16, D=64) and qwen3-14b's
                (40/8, D=128, grouped query heads);
  (b) serve   — ``launch/serve.py`` engine mode, 16 synthetic requests,
                then the engine's decode program is compiled and must
                hold the kernel as a ``tpu_custom_call``;
  (c) train   — ``launch/train.py`` for 5 steps.

``--four-chips`` runs only the cross-chip path: ``launch/train.py`` on a
two-pod lease (mesh pod=2, data=2, model=1) with ``--dp-mode
hierarchical`` against ``--dp-mode auto``, and ``hierarchical_allreduce``
against ``flat_allreduce`` on a 4-device mesh.

Each phase prints one JSON line.  Times are host wall-clock seconds taken
after the device finished (``block_until_ready`` or a host read); the
serving engine's modeled clock is never reported here.  The last line is
``{"ok": true, "device": {...}}``; any failed check exits non-zero before
it.  With no TPU, or without the repository next to it, the script exits
non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"
ARCH = "qwen1.5-0.5b"


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def device_bytes():
    """HBM of chip 0: in use now, and the peak since the process began."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def run_cli(main, argv, name: str):
    """Run a CLI ``main`` in-process; returns (exit code, its JSON, host
    wall seconds).  The full JSON is kept under chiprun_out/."""
    buf = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(buf):
        rc = main(argv)
    wall = time.perf_counter() - t0
    text = buf.getvalue()
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / f"{name}.json").write_text(text)
    return rc, json.loads(text), wall


# ---------------------------------------------------------------------------
# (a) paged-attention kernel vs the reference
# ---------------------------------------------------------------------------

# qwen1.5-0.5b (H=KV=16, D=64: one query head per KV head) at 32 pages a
# row, and qwen3-14b (40/8, D=128: five query heads share a KV head, so
# the kernel's group mask does real work) at a few pages a row
KERNEL_WIDTHS = ((ARCH, 32), ("qwen3-14b", 4))


def phase_kernel():
    for arch, pages_per_row in KERNEL_WIDTHS:
        kernel_check(arch, pages_per_row)


def kernel_check(arch: str, PMAX: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.kernels.paged_attention import paged_decode_attention
    from repro.kernels.ref import paged_attention_ref

    cfg = get_config(arch)
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    B, ps = 8, 32
    P = B * PMAX + 1
    rng = np.random.default_rng(0)
    # each row owns a random, non-contiguous set of physical pages;
    # lengths cover an idle row, a one-token row and a full row
    table = rng.permutation(P)[:B * PMAX].reshape(B, PMAX).astype(np.int32)
    lengths = rng.integers(1, PMAX * ps, size=B).astype(np.int32)
    lengths[0], lengths[1], lengths[2] = 0, 1, PMAX * ps
    kernel = jax.jit(lambda *a: paged_decode_attention(*a, interpret=False))
    ref = jax.jit(paged_attention_ref)

    # |got - want| <= tol * (1 + |want|): f32 math in both, so f32 agrees
    # to accumulation order; bf16 outputs differ by up to one bf16 ulp
    for dtype, tol in ((jnp.float32, 1e-4), (jnp.bfloat16, 1e-2)):
        q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
        kp = jnp.asarray(rng.standard_normal((P, ps, KV, D)), dtype)
        vp = jnp.asarray(rng.standard_normal((P, ps, KV, D)), dtype)
        args = (q, kp, vp, jnp.asarray(table), jnp.asarray(lengths))
        t0 = time.perf_counter()
        got = kernel(*args).block_until_ready()
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(10):
            out = kernel(*args)
        out.block_until_ready()
        steady = (time.perf_counter() - t0) / 10
        with jax.default_matmul_precision("highest"):
            want = ref(*args)
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
        hlo = kernel.lower(*args).compile().as_text()
        report("kernel", arch=arch, dtype=jnp.dtype(dtype).name,
               B=B, H=H, KV=KV, D=D,
               page_size=ps, pages_per_row=PMAX,
               max_scaled_err=err, tol=tol,
               tpu_custom_call="tpu_custom_call" in hlo,
               host_first_call_s=first, host_steady_call_s=steady)
        what = f"{arch} {jnp.dtype(dtype).name}"
        check("tpu_custom_call" in hlo, f"{what}: kernel compiled without "
              "the Pallas custom call")
        check(np.all(np.isfinite(got)), f"{what}: non-finite kernel output")
        check(np.all(got[0] == 0), f"{what}: idle row must be exactly zero")
        check(err <= tol, f"{what}: kernel vs ref error {err} > {tol}")


# ---------------------------------------------------------------------------
# (b) serving: launch/serve.py engine mode, then the decode program
# ---------------------------------------------------------------------------

SERVE_ARGV = ["--arch", ARCH, "--requests", "16", "--max-new", "32",
              "--slots", "8", "--max-seq", "1024", "--page-size", "32",
              "--prompt-lens", "64,128,256,512"]


def phase_serve():
    from repro.configs import get_config
    from repro.launch import serve as serve_cli
    from repro.models.api import build_model
    from repro.serve import Engine, EngineConfig

    walls = []
    for run in ("cold", "warm"):
        rc, out, wall = run_cli(serve_cli.main, SERVE_ARGV, f"serve_{run}")
        walls.append(wall)
        st = out["stats"]
        check(rc == 0, f"serve CLI exited {rc}")
        check(st["failed_oom"] == 0, f"{st['failed_oom']} requests OOM")
        check(st["completed"] == 16 and out["requests"] == 16,
              f"{st['completed']}/16 requests completed")
        check(out["short_requests"] == 0,
              f"{out['short_requests']} requests short of 32 tokens")
    # the engine's decode step at the CLI's geometry: the kernel must be
    # compiled into it, not interpreted
    model = build_model(get_config(ARCH))
    engine = Engine.local(model, EngineConfig(max_slots=8, max_seq=1024,
                                              page_size=32))
    t0 = time.perf_counter()
    hlo = engine.lower_decode().compile().as_text()
    decode_compile = time.perf_counter() - t0
    del engine
    report("serve", requests=out["requests"], completed=st["completed"],
           failed_oom=st["failed_oom"], short_requests=out["short_requests"],
           tokens_decoded=st["tokens_decoded"],
           prefill_compiles=st["prefill_compiles"],
           decode_compiles=st["decode_compiles"],
           sample_tokens=out["sample_tokens"],
           decode_hlo_tpu_custom_call="tpu_custom_call" in hlo,
           host_wall_cold_s=walls[0], host_wall_warm_s=walls[1],
           host_decode_lower_compile_s=decode_compile,
           **device_bytes())
    check("tpu_custom_call" in hlo,
          "engine decode program holds no tpu_custom_call")


# ---------------------------------------------------------------------------
# (c) training: launch/train.py
# ---------------------------------------------------------------------------

def train_argv(*extra):
    return ["--arch", ARCH, "--ckpt-every", "1000000",
            "--ckpt-dir", str(OUT / "ckpt"), *extra]


def phase_train():
    from repro.launch import train as train_cli

    # batch 4 x 512 tokens: the compiled step's memory_analysis() on a
    # v5e is ~9 GB (5.6 GB of f32 params + AdamW moments, 3.4 GB temp)
    rc, out, wall = run_cli(
        train_cli.main,
        train_argv("--steps", "5", "--batch", "4", "--seq", "512"), "train")
    report("train", steps=out["steps"], batch=4, seq=512,
           loss_first=out["loss_first"], loss_last=out["loss_last"],
           exit_code=rc, host_wall_s=wall,
           host_first_step_s=out["host_first_step_s"],
           host_steady_step_s=out["host_steady_step_s"],
           **device_bytes())
    check(math.isfinite(out["loss_first"]) and math.isfinite(out["loss_last"]),
          "non-finite training loss")
    check(rc == 0, f"train CLI exited {rc} (loss did not fall)")


# ---------------------------------------------------------------------------
# --four-chips: hierarchical vs flat data parallelism across chips
# ---------------------------------------------------------------------------

def phase_four_chip_train():
    from repro.launch import train as train_cli

    outs = {}
    for mode in ("auto", "hierarchical"):
        rc, out, wall = run_cli(
            train_cli.main,
            train_argv("--steps", "3", "--batch", "8", "--seq", "512",
                       "--pool", "scalepool", "--pool-accels", "16",
                       "--dp-mode", mode), f"train4_{mode}")
        outs[mode] = out
        report("train4", dp_mode=out["dp_mode"], mesh=out["mesh"],
               state_devices=out["state_devices"],
               loss_first=out["loss_first"], loss_last=out["loss_last"],
               exit_code=rc, host_wall_s=wall,
               host_first_step_s=out["host_first_step_s"],
               host_steady_step_s=out["host_steady_step_s"],
               **device_bytes())
        check(out["dp_mode"] == mode, f"ran dp_mode={out['dp_mode']}")
        check(out["mesh"] == {"pod": 2, "data": 2, "model": 1},
              f"lease mesh {out['mesh']}")
        check(out["state_devices"] == 4,
              f"train state on {out['state_devices']} devices, not 4")
        check(math.isfinite(out["loss_last"]), "non-finite loss")
        check(rc == 0, f"train CLI exited {rc} (loss did not fall)")
    # same math, different reduction order under bf16 compute
    a, h = outs["auto"], outs["hierarchical"]
    for key in ("loss_first", "loss_last"):
        check(abs(a[key] - h[key]) <= 2e-3 * abs(a[key]),
              f"{key}: auto {a[key]} vs hierarchical {h[key]}")


def phase_four_chip_allreduce():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import hierarchy
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2), ("pod", "data"))
    # integer-valued f32: every summation order gives the same bits
    x = jnp.arange(4 * 1024 * 256, dtype=jnp.float32).reshape(4 * 1024, 256)
    x = jax.device_put(x % 251.0, NamedSharding(mesh, P(("pod", "data"))))
    flat = jax.jit(lambda v: hierarchy.flat_allreduce(v, mesh,
                                                      ("pod", "data")))
    hier = jax.jit(lambda v: hierarchy.hierarchical_allreduce(
        v, mesh, intra_axis="data", inter_axis="pod"))
    res = {}
    for name, fn in (("flat", flat), ("hier", hier)):
        t0 = time.perf_counter()
        y = fn(x).block_until_ready()
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(10):
            z = fn(x)
        z.block_until_ready()
        res[name] = (np.asarray(y), first, (time.perf_counter() - t0) / 10,
                     len(y.sharding.device_set))
    xs = np.asarray(x).reshape(4, 1024, 256)
    want = np.tile(xs.sum(0), (4, 1))
    equal = bool(np.array_equal(res["flat"][0], res["hier"][0]))
    report("allreduce4", shape=list(x.shape), equal=equal,
           matches_numpy=bool(np.array_equal(res["flat"][0], want)),
           devices={k: v[3] for k, v in res.items()},
           host_first_call_s={k: v[1] for k, v in res.items()},
           host_steady_call_s={k: v[2] for k, v in res.items()})
    check(equal, "hierarchical all-reduce differs from flat")
    check(np.array_equal(res["flat"][0], want), "all-reduce != numpy sum")
    check(all(v[3] == 4 for v in res.values()), "result not on 4 devices")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the four-chip hierarchical-dp phases")
    args = p.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(jax.devices()) < need:
        print(f"chip_smoke: needs {need} chips, JAX found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1

    from repro.core.tiering import tier2_memory_kind
    from repro.launch.cache import enable_compile_cache
    report("setup", platform=dev.platform, kind=dev.device_kind,
           count=len(jax.devices()), jax=jax.__version__,
           compile_cache=enable_compile_cache(),
           tier2_memory_kind=tier2_memory_kind())

    phases = ([phase_four_chip_allreduce, phase_four_chip_train]
              if args.four_chips else
              [phase_kernel, phase_serve, phase_train])
    for phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except SmokeFailure as e:
            print(f"chip_smoke: {phase.__name__} failed: {e}",
                  file=sys.stderr)
            return 1
        report("timing", name=phase.__name__,
               host_wall_s=time.perf_counter() - t0)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
