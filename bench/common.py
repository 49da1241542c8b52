"""What every cell shares: finding a cell's files by name, building the
model configuration a configuration file states, the device check, the
peaks table and the result line.

A configuration file holds published keys and the keys the harness
reads (``HARNESS_KEYS``), and nothing else.  Every published key is
applied to a ``ModelConfig`` field: through ``HF_KEYS``, or, for a key
that ``HF_KEYS`` does not name, through the file's own ``map`` (published
key -> field).  ``program`` holds ``ModelConfig`` fields that no
published key states, such as the experts one chip holds, and may not
set a field that a published key or the serving dtypes set.
``reference`` and ``work`` name, relative to the benchmark's root, the
module that checks the served tokens (``served_gaps(params, cfg, served,
pad_to, control=False)``) and the module that counts the work the
readers hold device time against (``prefill_flops(cfg, n)``,
``decode_flops(cfg, n)`` and ``paged_attention_work(cfg, lengths,
page_size, kv_itemsize)``, the last over all layers)."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# published keys of a configuration file -> ModelConfig fields
HF_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
# keys of a configuration file that the harness reads or that document it
HARNESS_KEYS = frozenset({"arch", "smoke", "source", "deployment",
                          "reference", "work", "reduced", "published",
                          "assumed", "serve", "program", "map"})


class BenchError(RuntimeError):
    """A cell that cannot be run as its files state."""


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def module(self, key: str):
        """The module that the configuration file names under ``key``
        (``reference`` or ``work``), from the benchmark's root."""
        return load_module(self.root / self.config[key])


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, its configuration
    file, its traffic file and the metrics it reports."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    _check_keys(config)
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, workload=w, config=config, traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
                root=root)


def _check_keys(config: Dict[str, Any]) -> None:
    """Every key of a configuration file is one the harness applies or
    reads: a key that neither ``HF_KEYS`` nor the file's ``map`` maps and
    that is not a harness key is an error, and so is a ``map`` entry that
    is not such a key of the file, and a file that names no reference or
    no work module."""
    arch = config.get("arch")
    own = config.get("map", {})
    bad = sorted(k for k in own
                 if k not in config or k in HF_KEYS or k in HARNESS_KEYS)
    if bad:
        raise BenchError(f"{arch}: 'map' names {bad}, which are not keys "
                         f"of the file that HF_KEYS leaves unmapped")
    unknown = sorted(set(config) - HARNESS_KEYS - set(HF_KEYS) - set(own))
    if unknown:
        raise BenchError(f"{arch}: configuration keys {unknown} are "
                         f"neither mapped published keys nor harness keys")
    missing = [k for k in ("reference", "work") if k not in config]
    if missing:
        raise BenchError(f"{arch}: the configuration file names no "
                         f"{' and no '.join(missing)} module")


def model_config(config: Dict[str, Any]):
    """The ``repro`` ModelConfig that a configuration file states: the
    registry's architecture with the file's published keys, its serving
    dtypes and then its ``program`` fields applied.  A key the harness
    would not apply, a field set twice and a field that ``ModelConfig``
    lacks are errors."""
    from repro.configs import get_config
    _check_keys(config)
    arch = config["arch"]
    base = get_config(arch, smoke=bool(config.get("smoke", False)))
    keys = {**HF_KEYS, **config.get("map", {})}
    targets = [keys[k] for k in config if k in keys]
    twice = sorted({f for f in targets if targets.count(f) > 1})
    if twice:
        raise BenchError(f"{arch}: published keys set the fields {twice} "
                         f"more than once")
    program = config.get("program", {})
    fields = {f.name for f in dataclasses.fields(base)}
    unknown = sorted((set(targets) | set(program)) - fields)
    if unknown:
        raise BenchError(f"{arch}: {unknown} are not ModelConfig fields")
    changes = {keys[k]: config[k] for k in config if k in keys}
    changes.update(config["serve"]["dtypes"])
    overridden = sorted(set(program) & set(changes))
    if overridden:
        raise BenchError(f"{arch}: program sets {overridden}, which the "
                         f"published keys or the serving dtypes set")
    changes.update(program)
    return dataclasses.replace(base, **changes)


def load_module(path: Path):
    """Import a file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(cell: Cell, metrics: List[Dict[str, Any]],
                 record: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Each metric's reader over the run record.  A reader that finds
    nothing to read returns None: a metric without a ``workloads`` list
    is then left out, and one whose list names this cell is an error (a
    trace name it looks for is missing)."""
    out = {}
    for m in metrics:
        mod = load_module(cell.root / "bench" / "metrics" / f"{m['name']}.py")
        value = mod.read(record)
        if value is None:
            if "workloads" in m:
                raise BenchError(f"metric {m['name']} found nothing to read "
                                 f"in {cell.name}")
            continue
        if not math.isfinite(value):
            raise BenchError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def peaks(device_kind: str, root: Path = ROOT) -> Dict[str, float]:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table:
        raise BenchError(f"no published peaks for device {device_kind!r} "
                         f"in bench/peaks.json")
    return table[device_kind]


def check_devices(chips: int):
    """The chips this cell asks for, or an error: the benchmark never
    falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no accelerator: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache at the fixed ``<checkout>/
    .jax_cache``, every program in it, so that only a cell's first run in
    a checkout compiles."""
    import jax
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads while armed."""

    def __init__(self):
        import jax._src.monitoring as monitoring
        self.armed = False
        self.compiles = 0
        self.loads = 0

        def on_duration(event, duration, **kw):
            if self.armed and event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **kw):
            if self.armed and event == "/jax/compilation_cache/cache_hits":
                self.loads += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


def device_info(devices, peak_bytes: int) -> Dict[str, Any]:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak_bytes)}


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip of the run."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def quantile(values, q: float) -> float:
    """The ``q`` quantile by linear interpolation between order
    statistics (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise BenchError("quantile of no values")
    pos = q * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(result: Dict[str, Any], checks: List[Dict[str, Any]]) -> None:
    """Each compared number beside its limit, as the last lines on
    standard error, then the result line (checks last) on stdout."""
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
            f"({'ok' if c['ok'] else 'FAILED'})")
    result = dict(result)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)

