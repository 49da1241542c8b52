import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape) cell, build the production mesh
(16x16 single-pod / 2x16x16 multi-pod), lower + compile the appropriate
step (train_step / prefill_step / decode_step) from ShapeDtypeStruct
stand-ins (no allocation), and record memory_analysis / cost_analysis /
collective traffic to ``artifacts/dryrun/*.json`` — §Roofline reads from
these artifacts.

    PYTHONPATH=src python -m repro.launch.dryrun --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
"""

import argparse
import json
import sys
import traceback
from pathlib import Path

from repro.configs import ARCHS
from repro.launch.cache import enable_compile_cache
from repro.launch.dryrun_cell import lower_cell
from repro.obs.console import emit
from repro.models.config import SHAPES


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None, help="single arch (default: all)")
    p.add_argument("--shape", default=None, help="single shape (default: all)")
    p.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    p.add_argument("--smoke", action="store_true", help="use reduced configs")
    p.add_argument("--dp-mode", default="auto", choices=["auto", "hierarchical"])
    p.add_argument("--no-fsdp", action="store_true")
    p.add_argument("--mode", default="extrapolate", choices=["extrapolate", "full"])
    p.add_argument("--out", default="artifacts/dryrun")
    p.add_argument("--tag", default="")
    p.add_argument("--skip-existing", action="store_true")
    args = p.parse_args(argv)

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    archs = [args.arch] if args.arch else sorted(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape_name in shapes:
            for multi_pod in meshes:
                mesh_name = "multi" if multi_pod else "single"
                tag = f"-{args.tag}" if args.tag else ""
                name = f"{arch}__{shape_name}__{mesh_name}{tag}"
                fp = outdir / f"{name}.json"
                if args.skip_existing and fp.exists():
                    rec = json.loads(fp.read_text())
                    if rec.get("status") in ("OK", "SKIP"):
                        n_ok += rec["status"] == "OK"
                        n_skip += rec["status"] == "SKIP"
                        emit(f"[keep] {name}")
                        continue
                try:
                    rec = lower_cell(arch, shape_name, multi_pod,
                                     smoke=args.smoke, dp_mode=args.dp_mode,
                                     fsdp=not args.no_fsdp, mode=args.mode)
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                           "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                fp.write_text(json.dumps(rec, indent=2))
                status = rec["status"]
                n_ok += status == "OK"
                n_skip += status == "SKIP"
                n_fail += status == "FAIL"
                line = f"[{status:4s}] {name}"
                if status == "OK":
                    r = rec["roofline"]
                    line += (f"  compile={rec['compile_s']:.1f}s"
                             f"  flops={r['hlo_flops']:.3g}"
                             f"  coll={r['collective_bytes']:.3g}B"
                             f"  dom={r['dominant']}")
                elif status == "FAIL":
                    line += "  " + rec["error"][:140]
                emit(line)
    emit(f"\ndry-run: {n_ok} OK, {n_skip} SKIP, {n_fail} FAIL")
    return 1 if n_fail else 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
