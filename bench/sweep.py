"""Knee sweep of an open-loop serving cell: several fixed arrival rates,
one after another, through one engine in one process.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1,2,3

Each rate runs a window of ``--seconds`` from the cell's own traffic with
only the rate changed; the engine then drains before the next rate.  One
JSON line per rate: requests due, answered and completed, TTFT median and
90th percentile, the 99th percentile gap between tokens, output tokens/s
and the requests still unanswered at the close.  A rate the system
sustains answers nearly every request it was sent and keeps TTFT flat;
the knee is the highest such rate.  Used once to fix a cell's rate.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
_ROOT = Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import common, readers, serve  # noqa: E402


def sweep(cell, seed: int, seconds: float, rates):
    engine, mcfg, _ = serve.build(cell, seed)
    serve.warm_up(engine, cell)
    common.log(f"set-up {time.perf_counter() - T_START:.1f} s")
    out = []
    for k, rate in enumerate(rates):
        c = dataclasses.replace(cell, traffic={**cell.traffic,
                                               "rate_per_s": rate})
        run = serve.run_window(engine, c, seed + k, seconds)
        rec = serve.summarize(run, mcfg, c)
        due = [r for r in rec["requests"] if r["due"] < seconds]
        row = {"rate_per_s": rate, "due": len(due),
               "answered": sum(bool(r["times"]) for r in due),
               "completed": sum(r["done"] for r in due),
               "ttft_p50_s": readers.ttft_quantile(rec, 0.5),
               "ttft_p90_s": readers.ttft_quantile(rec, 0.9),
               "itl_p99_s": readers.itl_quantile(rec, 0.99),
               "serve_tok_s": readers.serve_tok_s(rec),
               "decode_rows_mean": readers.decode_rows_mean(rec)}
        row["unanswered_at_close"] = row["due"] - row["answered"]
        print(json.dumps(row), flush=True)
        out.append(row)
        engine.run_until_idle()
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)
    cell = common.load_cell(args.workload)
    if cell.traffic["kind"] != "open_loop":
        common.log("a sweep needs an open-loop cell")
        return 2
    try:
        devices = common.check_devices(cell.chips)
    except common.BenchError as e:
        common.log(f"error: {e}")
        return 2
    common.enable_compile_cache()
    rows = sweep(cell, args.seed, args.seconds,
                 [float(r) for r in args.rates.split(",")])
    print(json.dumps({"workload": cell.name, "device": devices[0].device_kind,
                      "sweep": rows}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
