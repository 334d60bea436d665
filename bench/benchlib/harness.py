"""The harness: finds a cell's files by name, checks the device, runs the
cell's driver, reads the per-layer metrics and prints the result.

Files, each found by the name ``BENCHMARK.json`` gives it:
- ``bench/configs/<config>.json``: the model's sizes as run, in the
  Hugging Face ``config.json``'s key names;
- ``bench/arch/<architectures[0]>.py``: what depends on the configuration's
  architecture, named by its own ``architectures`` key
  (``Qwen3ForCausalLM``): ``program_config(c)``, the program's model
  configuration; ``dims(c)``, the sizes ``benchlib.counts`` reads (the
  object counts every kind of layer itself); ``top_specs(dims, tied)``,
  the leaves outside the layers as ``(path, shape, kind, scale)``; and
  its layers, either as one kind, ``layer_specs(dims)`` (one layer's
  leaves, stacked over every layer under the root ``blocks``) and
  ``block(w, x, dims, i, theta, eps, cast) -> (x, extra_loss)`` (layer
  ``i``, its index over all the layers, traced, of the plain reference
  and its float32 extra loss: a router's, 0.0 where there is none, summed
  over the layers into the reference's loss), or as several,
  ``layer_kinds(dims)``: a list in layer order of
  ``benchlib.weights.LayerKind(root, layers, specs, block)``, each kind's
  leaves stacked under a root of its own;
- ``bench/traffic/<traffic>.json``: the mix's parameters, including the
  driver kind that serves it;
- ``bench/drivers/<driver>.py``: ``run(ctx) -> Outcome``;
- ``bench/metrics/<metric>.py``: ``read(rec) -> float | None``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import sys
import time
from typing import Any, Dict, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Context:
    """What a driver is given."""
    name: str
    bench: dict
    cell: dict
    config: dict
    arch: Any                         # the configuration's bench/arch module
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float                    # process start, monotonic
    devices: list
    peaks: Optional[dict]
    control: Optional[str] = None     # lower-precision control (calibration)
    scratch: Optional[pathlib.Path] = None


@dataclasses.dataclass
class Outcome:
    """What a driver returns."""
    e2e: Dict[str, float]
    record: Dict[str, Any]
    checks: Dict[str, dict]           # name -> {"value", "limit"}
    attempted: int
    failed: int
    memory_peak_bytes: int
    trace: Optional[dict] = None      # benchlib.trace.reduce's output

    @property
    def correct(self) -> bool:
        return all(c["value"] <= c["limit"] for c in self.checks.values())


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_arch(config: dict, root: pathlib.Path = ROOT):
    """The architecture module that ``config["architectures"][0]`` names."""
    name = config["architectures"][0]
    path = root / "bench" / "arch" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"no architecture module bench/arch/{name}.py for configuration "
            f"{config.get('name')!r} (looked for {path})")
    return load_module(path, f"bench_arch_{name}")


def cell_context(name: str, *, seed: int, seconds: float, trace: bool,
                 t_start: float, devices: list, peaks: Optional[dict],
                 root: pathlib.Path = ROOT, control: Optional[str] = None,
                 scratch: Optional[pathlib.Path] = None) -> Context:
    bench = load_json(root / "BENCHMARK.json")
    cell = find_cell(bench, name)
    bdir = root / "bench"
    config = load_json(bdir / "configs" / f"{cell['config']}.json")
    return Context(name=name, bench=bench, cell=cell, config=config,
                   arch=load_arch(config, root),
                   mix=load_json(bdir / "traffic" / f"{cell['traffic']}.json"),
                   seed=seed, seconds=seconds, trace=trace, t_start=t_start,
                   devices=devices, peaks=peaks, control=control,
                   scratch=scratch)


def run_driver(ctx: Context, root: pathlib.Path = ROOT) -> Outcome:
    kind = ctx.mix["driver"]
    mod = load_module(root / "bench" / "drivers" / f"{kind}.py",
                      f"bench_driver_{kind}")
    return mod.run(ctx)


def e2e_names(bench: dict, cell: str) -> list:
    return [m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def layer_names(bench: dict, cell: str) -> list:
    e2e = set(e2e_names(bench, cell))
    return [m["name"] for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def read_layer_metrics(ctx: Context, outcome: Outcome,
                       root: pathlib.Path = ROOT) -> dict:
    units = {m["name"]: m["unit"] for m in ctx.bench["per_layer"]}
    rec = dict(outcome.record, trace=outcome.trace, peaks=ctx.peaks,
               chips=len(ctx.devices))
    out = {}
    for name in layer_names(ctx.bench, ctx.name):
        mod = load_module(root / "bench" / "metrics" / f"{name}.py",
                          "bench_metric_" + name.replace(".", "_"))
        value = mod.read(rec)
        if value is not None and math.isfinite(value):
            out[name] = {"value": float(value), "unit": units[name]}
    return out


def result_line(ctx: Context, outcome: Outcome) -> dict:
    units = {m["name"]: m["unit"] for m in ctx.bench["end_to_end"]}
    if ctx.trace:
        metrics = read_layer_metrics(ctx, outcome)
    else:
        metrics = {n: {"value": float(outcome.e2e[n]), "unit": units[n]}
                   for n in e2e_names(ctx.bench, ctx.name) if n in outcome.e2e}
    d = ctx.devices
    device = {"platform": d[0].platform, "kind": d[0].device_kind,
              "count": len(d), "memory_peak_bytes": outcome.memory_peak_bytes}
    line = {"correct": outcome.correct, "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics, "device": device}
    if ctx.trace and outcome.trace is not None:
        device["busy_s"] = outcome.trace["busy_s"]
        device["window_s"] = outcome.trace["window_s"]
        line["breakdown"] = {"device_ops": outcome.trace["device_ops"],
                             "idle_gaps": outcome.trace["idle_gaps"]}
    line["checks"] = outcome.checks
    return line


def print_result(line: dict) -> None:
    for name, c in line["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def live_bytes() -> int:
    """Device bytes still held by live arrays (printed before the
    reference runs, which needs the program's state gone)."""
    import jax
    n = sum(a.nbytes for a in jax.live_arrays())
    print(f"bench: live_bytes_before_reference={n}", file=sys.stderr, flush=True)
    return n


def now() -> float:
    return time.monotonic()


def enable_cache() -> str:
    """The program's compile-cache directory (``.jax_cache/`` in the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), with every program cached
    however short its compile."""
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices, or ``NoDevice``."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoDevice(f"JAX's backend is {backend!r}, not a TPU")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def start_trace(path: pathlib.Path) -> None:
    """Start the profiler with Python's tracer off (on by default, it
    records every Python call and doubled the host-bound engine step in
    the first traced runs) and only the host's level-1 events: the
    benchmark's ``TraceAnnotation`` spans and the runtime's main ones."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(path), profiler_options=opts)


def scratch_dir() -> pathlib.Path:
    """Where a run writes its trace: under TMPDIR, removed by the run."""
    import tempfile
    return pathlib.Path(tempfile.mkdtemp(prefix="bench-"))
