"""Readings that set a serving cell's limit: for each seed, the widest
logit gap of the tokens the program served, and of the tokens that the
control puts first at the same positions (the control is the reference
that the configuration file names, one precision step below the one
served).  One process, one engine, a short window per seed at the cell's
own load; the benchmark's own runs never run the control.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import common, serve  # noqa: E402


def readings(cell, seeds, seconds: float):
    reference = cell.module("reference")
    engine, mcfg, params = serve.build(cell, seeds[0])
    serve.warm_up(engine, cell)
    check = cell.config["serve"]["check"]
    pad = int(cell.config["serve"]["engine"]["max_seq"])
    for seed in seeds:
        window = serve.run_window(engine, cell, seed, seconds)
        served = serve.sample_finished(window, seed, int(check["min_tokens"]))
        prog, ctrl = reference.served_gaps(params, mcfg, served, pad,
                                           control=True)
        row = {"seed": seed, "requests": len(served),
               "tokens": sum(len(t) for _, t in served),
               "program_gap": max(prog), "control_gap": max(ctrl)}
        print(json.dumps(row), flush=True)
        engine.run_until_idle()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    cell = common.load_cell(args.workload)
    try:
        common.check_devices(cell.chips)
    except common.BenchError as e:
        common.log(f"error: {e}")
        return 2
    common.enable_compile_cache()
    readings(cell, [int(s) for s in args.seeds.split(",")], args.seconds)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
