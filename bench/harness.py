"""The harness: one cell of ``BENCHMARK.json``, one seed, one run.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by its name:

* ``BENCHMARK.json``: the cells (``workloads``), each naming a
  configuration and a traffic mix, and the metrics with the cells that
  report them;
* ``bench/configs/<config>.json``: the model as run (``file`` in
  ``BENCHMARK.json``), naming under ``reference`` the plain reference
  module that states its parameter tree, loss, operation count and
  matrix grain (``REFERENCE_NAMES``);
* ``bench/traffic/<traffic>.json``: optimizer, batch, sequence, steps;
* ``bench/limits/<cell>.json``: the limit of each number the check
  compares, with the readings it was set from;
* ``bench/metrics/<metric>.py``: ``read(run)`` for each metric;
* ``bench/peaks.json``: the chip's peaks, keyed by ``device_kind``.

A run prints, as the last lines of standard error, each number compared
beside its limit, and as the last line of standard output one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and ``check`` last).
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Optional

ROOT = Path(__file__).resolve().parents[1]


class BenchError(Exception):
    """A run that cannot be made: it prints no result and exits non-zero."""


# ------------------------------------------------------------------- specs
def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    reference: Any  # the module the configuration names (reference_module)


def _reports(metric: dict, cell: str) -> bool:
    """A metric with no ``workloads`` list is reported by every cell; one
    with a list, by the cells it names (a metric a later change adds for
    its own cells)."""
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = _load(root / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no cell {name!r} in BENCHMARK.json")
    conf = next((c for c in spec["configs"] if c["name"] == entry["config"]), None)
    if conf is None:
        raise BenchError(f"cell {name!r}: no configuration {entry['config']!r}")
    config = _load(root / conf["file"])
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=config,
        traffic=_load(root / "bench" / "traffic" / f"{entry['traffic']}.json"),
        limits=_load(root / "bench" / "limits" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
        reference=reference_module(config, root),
    )


def arch_fields(config: dict) -> dict:
    """The registry ``ArchConfig`` fields this configuration sets: each
    named key of ``config`` (``arch_keys``), plus ``arch_extra``."""
    fields = {f: config["config"][k] for f, k in config["arch_keys"].items()}
    fields.update(config.get("arch_extra", {}))
    return fields


# What a reference module exports, for the harness to read the model by:
# ``layout(arch) -> {path: shape}``, the parameter tree the program trains;
# ``loss(params, tokens, labels, arch, rnd)``, the mean next-token loss;
# ``flops_per_token(arch, seq)``, what a training step requires a token;
# ``matrix_axes(path, shape) -> int``, how many leading axes of a leaf
# index separate matrices; ``DOUBLED``, the path of the matrix that the
# planted fault of ``bench/control.py`` moves twice.
REFERENCE_NAMES = ("layout", "loss", "flops_per_token", "matrix_axes", "DOUBLED")


def reference_module(config: dict, root: Path = ROOT):
    """The module a configuration names under ``reference``, a path from
    the checkout's root, loaded from that file."""
    rel = config.get("reference")
    if not rel:
        raise BenchError(f"configuration {config.get('name')!r} names no reference")
    path = root / rel
    if not path.exists():
        raise BenchError(f"reference {rel} of configuration {config.get('name')!r} "
                         f"is not at {path}")
    mod = _module("bench_reference_" + path.stem, path)
    missing = [n for n in REFERENCE_NAMES if not hasattr(mod, n)]
    if missing:
        raise BenchError(f"reference {rel} does not export {missing}")
    return mod


def metric_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.exists():
        raise BenchError(f"metric {name!r} has no reader at {path}")
    return _module("bench_metric_" + name, path).read


def _module(name: str, path: Path):
    """The Python file at ``path``, loaded as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    table = _load(root / "bench" / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# --------------------------------------------------------------------- run
@dataclasses.dataclass
class Run:
    """What one run collected; the metric readers take it."""

    cell: Cell
    peaks: dict
    setup_s: float = 0.0
    window_s: float = 0.0
    step_s: list = dataclasses.field(default_factory=list)
    tokens_per_step: int = 0
    flops_per_step: float = 0.0
    memory_peak_bytes: int = 0
    counters: dict = dataclasses.field(default_factory=dict)
    shapes: dict = dataclasses.field(default_factory=dict)  # param path -> shape
    trace: Optional[Any] = None  # bench.trace_reduce.Reduced, --trace 1 only
    traced_steps: int = 0  # the window's last steps, under the profiler


def check_device(chips: int) -> dict:
    """The accelerator JAX finds, or BenchError: never a CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    from repro.kernels import ops

    if ops._mode() != "pallas":
        raise BenchError(f"kernels dispatch to {ops._mode()!r}, not compiled Pallas")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def enable_cache() -> dict:
    """The persistent compile cache, at the fixed path the program keeps
    (``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set);
    every program is written, however fast it compiled."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return {"dir": path}


def peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             device: Optional[dict] = None, peaks: Optional[dict] = None,
             log=print) -> dict:
    """Set up, measure for ``seconds``, check; returns the result object.

    ``device`` and ``peaks`` are given only by tests that drive a run on
    the CPU; otherwise the device is checked and looked up."""
    from bench import train_cell
    from bench.cache_log import CacheLog

    cache = CacheLog().install()
    if device is None:
        device = check_device(cell.chips)
        peaks = peaks_for(device["kind"])
        log(f"[cache] {json.dumps(enable_cache())}")
    if cell.traffic["kind"] != "train":
        raise BenchError(f"traffic kind {cell.traffic['kind']!r} has no runner")
    run = Run(cell=cell, peaks=peaks)
    runner = train_cell.TrainCell(cell, arch_fields(cell.config), seed, log=log)
    runner.setup()
    seen = cache.take()
    log(f"[cache] set-up: {len(seen['hits'])} hits {sorted(set(seen['hits']))[:12]}, "
        f"{len(seen['misses'])} misses {sorted(set(seen['misses']))[:12]}, "
        f"unwritten {seen['unwritten']}")
    reduced = runner.window(seconds, run, trace=trace, t0=t0)
    seen = cache.take()
    if seen["misses"]:
        log(f"[cache] window compiled {seen['misses']}")
    run.memory_peak_bytes = peak_bytes()
    run.counters = runner.counters()
    run.trace = reduced
    names = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in names:
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted, failed = runner.attempted_failed()
    runner.free()
    numbers = runner.check(log=log)
    cache.uninstall()
    # A number whose limit is null has no upper reading to set one from
    # (PERF.md): it is read and logged, and not compared.
    log("[check] not compared: " + json.dumps(
        {k: v for k, v in numbers.items() if cell.limits[k]["limit"] is None}))
    check = {k: {"value": v, "limit": cell.limits[k]["limit"]}
             for k, v in numbers.items() if cell.limits[k]["limit"] is not None}
    correct = all(c["value"] <= c["limit"] for c in check.values())
    result = {
        "correct": bool(correct and failed == 0),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": dict(device, memory_peak_bytes=run.memory_peak_bytes),
    }
    if trace and reduced is not None:
        result["device"].update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result["breakdown"] = reduced.breakdown()
    result["check"] = check
    return result


def main(argv, t0: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        print("--seed must be non-negative", file=sys.stderr)
        return 2

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        cell = load_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t0,
                          log=log)
    except BenchError as e:
        log(f"error: {e}")
        return 3
    for name, c in result["check"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
