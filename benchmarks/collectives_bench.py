"""Hierarchical vs flat collectives on a REAL JAX mesh (forced host
devices, subprocess): wall-clock per call + lowered collective-traffic
comparison.  This is §4's inter-cluster design measured on the runnable
artifact rather than the analytical model."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from typing import List, Tuple

_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax, jax.numpy as jnp
from repro.core import hierarchy as h
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("pod", "data"))
out = {}
for mb in (1, 8):
    x = jnp.ones((1024 * mb, 128), jnp.float32)   # 0.5/4 MiB per shard
    flat = jax.jit(lambda x: h.flat_allreduce(x, mesh, ("pod", "data")))
    hier = jax.jit(lambda x: h.hierarchical_allreduce(x, mesh,
                                                      intra_axis="data",
                                                      inter_axis="pod"))
    rec = {}
    for name, fn in (("flat", flat), ("hier", hier)):
        c = fn.lower(x).compile()
        ops = H.parse_collectives(c.as_text(), pod_size=4)
        s = H.collective_summary(ops)
        fn(x).block_until_ready()
        t0 = time.time()
        for _ in range(20):
            y = fn(x)
        y.block_until_ready()
        rec[name] = {"us": (time.time() - t0) / 20 * 1e6,
                     "cross_pod_bytes": s["cross_pod_moved_bytes"],
                     "total_bytes": s["total_moved_bytes"]}
    out[f"{mb}x"] = rec
print(json.dumps(out))
"""


def run() -> Tuple[List[str], dict]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(_CHILD)],
                       capture_output=True, text=True, env=env, timeout=570)
    if p.returncode != 0:
        return [f"collectives.error,0,{p.stderr[-200:]}"], {"ok": False}
    data = json.loads(p.stdout.strip().splitlines()[-1])
    lines = []
    summary = {"ok": True}
    for size, rec in data.items():
        ratio = rec["flat"]["cross_pod_bytes"] / max(1.0, rec["hier"]["cross_pod_bytes"])
        lines.append(
            f"collectives.{size},{rec['hier']['us']:.1f},"
            f"flat_us={rec['flat']['us']:.1f};hier_us={rec['hier']['us']:.1f};"
            f"cross_pod_bytes_flat={rec['flat']['cross_pod_bytes']:.3g};"
            f"cross_pod_bytes_hier={rec['hier']['cross_pod_bytes']:.3g};"
            f"cross_pod_reduction={ratio:.2f}x")
        summary[f"cross_pod_reduction_{size}"] = ratio
        # structural claim: hierarchical moves ~1/|data| of flat's bytes
        summary["ok"] &= ratio > 2.0
    return lines, summary
