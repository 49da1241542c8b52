"""Run one benchmark cell on the accelerator this process is started on.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``).  Exits non-zero, printing no result,
where JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is timed from process start

import argparse                      # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402
from pathlib import Path             # noqa: E402

_ROOT = Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import common             # noqa: E402

TRACE_S = 8.0                       # profiled span, centred in the window


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def _tracer(root: Path, seconds: float):
    from bench.trace import Tracer
    out = root / ".bench_trace"
    shutil.rmtree(out, ignore_errors=True)
    span = min(TRACE_S, seconds)
    start = max(0.0, seconds / 2 - span / 2)
    return Tracer(str(out), start, start + span), out


def run(args, root: Path = common.ROOT, require_chip: bool = True,
        t_start: float = T_START) -> dict:
    """One run of a cell: the result object and the checks it made."""
    import jax
    cell = common.load_cell(args.workload, root)
    if require_chip:
        devices = common.check_devices(cell.chips)
        peaks = common.peaks(devices[0].device_kind, root)
    else:
        devices, peaks = jax.devices()[: cell.chips], None
    common.enable_compile_cache(root)
    counter = common.CompileCounter()
    rec, checks, attempted, failed = _serve(cell, args, devices, counter,
                                            root, t_start)
    rec["chips"] = cell.chips
    rec["peaks"] = peaks
    metrics = common.read_metrics(
        cell, cell.per_layer if args.trace else cell.end_to_end, rec)
    device = common.device_info(devices, rec["peak_bytes"])
    result = {"correct": all(c["ok"] for c in checks),
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        tr = rec["trace"]
        busy = [c["busy_s"] for c in tr["chips"].values()]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    common.log(f"compiles in the window: {rec['compiles_in_window']} "
               f"(persistent-cache loads {rec['cache_loads_in_window']})")
    return result, checks


def _read_trace(rec, tracer, out_dir):
    from bench import spans, trace
    ext = trace.extract(str(out_dir))
    rec["trace"] = trace.reduce(ext)
    rec["trace"]["host_spans"] = spans.host_spans(ext)
    rec["traced"] = {"steps": tracer.steps,
                     "seconds": tracer.host[1] - tracer.host[0]}
    shutil.rmtree(out_dir, ignore_errors=True)


def _serve(cell, args, devices, counter, root, t_start):
    from bench import serve
    engine, mcfg, params = serve.build(cell, args.seed)
    warm = serve.warm_up(engine, cell)
    common.log(f"warm-up: {warm}")
    setup_s = time.perf_counter() - t_start
    tracer = out_dir = None
    if args.trace:
        tracer, out_dir = _tracer(root, args.seconds)
    counter.armed = True
    run = serve.run_window(engine, cell, args.seed, args.seconds, tracer)
    counter.armed = False
    rec = serve.summarize(run, mcfg, cell)
    rec.update(setup_s=setup_s, compiles_in_window=counter.compiles,
               cache_loads_in_window=counter.loads,
               peak_bytes=common.peak_bytes(devices))
    late = [r["submit"] - r["due"] for r in rec["requests"]]
    common.log(f"generator lateness: max {max(late, default=0.0)!r} s, "
               f"mean {sum(late) / max(1, len(late))!r} s over "
               f"{len(late)} submissions")
    served = serve.sample_finished(run, args.seed,
                                   int(cell.config["serve"]["check"]["min_tokens"]))
    due = [r for r in rec["requests"] if r["due"] < rec["window_s"]]
    failed = sum(r.handle.status.value == "failed_oom" for r in run["requests"])
    del engine, run
    if tracer is not None:
        _read_trace(rec, tracer, out_dir)
    checks = serve.check(params, mcfg, cell, served)
    return rec, checks, len(due), failed


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result, checks = run(args)
    except common.BenchError as e:
        common.log(f"error: {e}")
        return 2
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
