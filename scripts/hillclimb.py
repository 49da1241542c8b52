"""§Perf hillclimbing driver: run named variants of the three selected
cells, write tagged artifacts, and print before/after roofline deltas.

    PYTHONPATH=src python scripts/hillclimb.py [variant ...]
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import json
import sys
from pathlib import Path

sys.path.insert(0, "src")
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.dryrun_cell import lower_cell  # noqa: E402

OUT = Path("artifacts/perf")
OUT.mkdir(parents=True, exist_ok=True)

# (name, cell-args, lower_cell kwargs)
VARIANTS = {
    # ---- Cell A: olmoe-1b-7b / train_4k / single (worst roofline frac,
    #      most collective-bound: coll 12.4s vs compute 0.28s) ----
    "olmoe-A1-nofsdp": (
        ("olmoe-1b-7b", "train_4k", False),
        dict(fsdp=False),
    ),
    "olmoe-A2-nofsdp-cap1": (
        ("olmoe-1b-7b", "train_4k", False),
        dict(fsdp=False, cfg_patch={"capacity_factor": 1.0}),
    ),
    "olmoe-A3-nofsdp-micro1": (
        ("olmoe-1b-7b", "train_4k", False),
        dict(fsdp=False, micro_override=1),
    ),
    # A4: bf16 combine accumulation (code change in repro.models.moe) —
    # measured against the fp32-combine baseline artifact.
    "olmoe-A4-bf16combine": (
        ("olmoe-1b-7b", "train_4k", False),
        dict(),
    ),
    "olmoe-A5-bf16-cap1": (
        ("olmoe-1b-7b", "train_4k", False),
        dict(cfg_patch={"capacity_factor": 1.0}),
    ),
    # A6: gather-based dispatch (code change in repro.models.moe)
    "olmoe-A6-gather-dispatch": (
        ("olmoe-1b-7b", "train_4k", False),
        dict(),
    ),
    "olmoe-A7-gather-cap1": (
        ("olmoe-1b-7b", "train_4k", False),
        dict(cfg_patch={"capacity_factor": 1.0}),
    ),
    "mixtral-C4-gather-dispatch": (
        ("mixtral-8x7b", "train_4k", False),
        dict(),
    ),
    "mixtral-C5-gather-micro4": (
        ("mixtral-8x7b", "train_4k", False),
        dict(micro_override=4),
    ),
    # ---- Cell B: command-r-plus-104b / train_4k / multi (the paper's
    #      technique cell: cross-pod fabric traffic) ----
    # NOTE: dp_mode=hierarchical with FSDP(data)-sharded grads trips an
    # XLA SPMD-partitioner CHECK at 512 devices (replica-group
    # factorization); the hierarchical phase therefore runs with the
    # non-FSDP parameter layout (documented in EXPERIMENTS.md §Perf).
    "commandr-B1-hier": (
        ("command-r-plus-104b", "train_4k", True),
        dict(dp_mode="hierarchical", fsdp=False),
    ),
    "commandr-B2-hier-int8": (
        ("command-r-plus-104b", "train_4k", True),
        dict(dp_mode="hierarchical", fsdp=False, compress_pod=True),
    ),
    "commandr-B0-nofsdp": (
        ("command-r-plus-104b", "train_4k", True),
        dict(fsdp=False),
    ),
    "commandr-B1f-hier-fsdp": (
        ("command-r-plus-104b", "train_4k", True),
        dict(dp_mode="hierarchical", donate=False),
    ),
    "commandr-B2f-hier-fsdp-int8": (
        ("command-r-plus-104b", "train_4k", True),
        dict(dp_mode="hierarchical", compress_pod=True, donate=False),
    ),
    "commandr-B3-micro4": (
        ("command-r-plus-104b", "train_4k", True),
        dict(micro_override=4),
    ),
    "commandr-B5-micro2": (
        ("command-r-plus-104b", "train_4k", True),
        dict(micro_override=2),
    ),
    # ---- Cell C: mixtral-8x7b / train_4k / single (MoE FFN-sharded
    #      dispatch + FSDP gather traffic) ----
    "mixtral-C1-2dexpert": (
        ("mixtral-8x7b", "train_4k", False),
        dict(rules_patch={"expert_ff": ("data", "model"), "embed": None}),
    ),
    "mixtral-C2-2dexpert-micro4": (
        ("mixtral-8x7b", "train_4k", False),
        dict(rules_patch={"expert_ff": ("data", "model"), "embed": None},
             micro_override=4),
    ),
    "mixtral-C3-cap1": (
        ("mixtral-8x7b", "train_4k", False),
        dict(rules_patch={"expert_ff": ("data", "model"), "embed": None},
             cfg_patch={"capacity_factor": 1.0}),
    ),
}


def baseline_path(arch, shape, multi):
    mesh = "multi" if multi else "single"
    return Path(f"artifacts/dryrun/{arch}__{shape}__{mesh}.json")


def main():
    names = sys.argv[1:] or list(VARIANTS)
    for name in names:
        (arch, shape, multi), kw = VARIANTS[name]
        fp = OUT / f"{name}.json"
        try:
            rec = lower_cell(arch, shape, multi, **kw)
        except Exception as e:  # noqa: BLE001
            import traceback
            rec = {"status": "FAIL", "error": str(e),
                   "traceback": traceback.format_exc()[-1500:]}
        rec["variant"] = name
        rec["variant_kwargs"] = {k: str(v) for k, v in kw.items()}
        fp.write_text(json.dumps(rec, indent=2))
        if rec["status"] != "OK":
            print(f"[FAIL] {name}: {rec.get('error', '')[:160]}", flush=True)
            continue
        base = json.loads(baseline_path(arch, shape, multi).read_text())
        br, vr = base["roofline"], rec["roofline"]
        print(f"[OK] {name}", flush=True)
        for term in ("compute_s", "memory_s", "collective_s"):
            print(f"     {term:13s} {br[term]:10.3f} -> {vr[term]:10.3f}  "
                  f"({vr[term]/max(br[term],1e-12):5.2f}x)", flush=True)
        print(f"     cross_pod_GB  {br['cross_pod_bytes']/1e9:10.2f} -> "
              f"{vr['cross_pod_bytes']/1e9:10.2f}", flush=True)
        print(f"     useful_flops  {br.get('useful_flops_ratio',0):10.3f} -> "
              f"{vr.get('useful_flops_ratio',0):10.3f}", flush=True)
        bdom = max(br['compute_s'], br['memory_s'], br['collective_s'])
        vdom = max(vr['compute_s'], vr['memory_s'], vr['collective_s'])
        print(f"     step_bound_s  {bdom:10.3f} -> {vdom:10.3f}  "
              f"roofline_frac {br['compute_s']/bdom:.3f} -> "
              f"{vr['compute_s']/vdom:.3f}", flush=True)


if __name__ == "__main__":
    enable_compile_cache()
    main()
