"""Print the top collectives (by per-device moved bytes) of one cell's
compiled HLO — the §Perf 'profile' on a dry-run-only platform."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import sys
from collections import defaultdict

sys.path.insert(0, "src")
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config
from repro.launch import hlo_analysis as H
from repro.launch.dryrun_cell import (TRAIN_MICROBATCHES, _lower_and_compile,
                                      _attach)
from repro.launch.mesh import make_production_mesh
from repro.models.api import build_model
from repro.models.config import SHAPES, ShapeConfig
from repro.models.unroll import unroll_mode
from repro.optim.adamw import AdamW
from repro.runtime import train as train_rt
from repro.sharding.partition import use_rules
from repro.sharding.profiles import make_rules

enable_compile_cache()
arch = sys.argv[1] if len(sys.argv) > 1 else "olmoe-1b-7b"
fsdp = "--no-fsdp" not in sys.argv

cfg = get_config(arch)
shape0 = SHAPES["train_4k"]
micro = TRAIN_MICROBATCHES.get(arch, 1)
shape = ShapeConfig("train_4k", "train", shape0.seq_len,
                    shape0.global_batch // micro, microbatches=1)
mesh = make_production_mesh(multi_pod=False)
rules = make_rules(cfg, shape, mesh, fsdp=fsdp)
model = build_model(cfg, moe_groups=16)

with use_rules(rules, mesh), unroll_mode(1):
    lowered = _lower_and_compile(cfg, shape, mesh, rules, model, AdamW(),
                                 dp_mode="auto", donate=True)
    compiled = lowered.compile()

txt = compiled.as_text()
ops = H.parse_collectives(txt, pod_size=256)
# aggregate by (kind, result_bytes) signature
agg = defaultdict(lambda: [0, 0.0])
for op in ops:
    key = (op.kind, op.result_bytes, op.group_size)
    agg[key][0] += 1
    agg[key][1] += op.moved_bytes

rows = sorted(agg.items(), key=lambda kv: -kv[1][1])[:15]
total = sum(v[1] for v in agg.values())
print(f"{arch} fsdp={fsdp}: total per-device collective bytes "
      f"(k=1 lowering, x{micro} micro x{cfg.n_layers} layers at runtime): "
      f"{total/1e9:.2f} GB")
for (kind, rb, gs), (count, moved) in rows:
    print(f"  {kind:20s} result={rb/1e6:9.2f}MB group={gs:4d} x{count:3d} "
          f"-> {moved/1e9:8.3f} GB ({moved/total*100:4.1f}%)")
