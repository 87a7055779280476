"""The harness's parts that runners, readers and tests share: finding the
chip, loading a cell by name, a model family's file by name, the runner's
context and outcome, and the result line."""

from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Mapping, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
FAMILIES = HERE / "reference"  # <family>.py: what it knows of a family

PLATFORM = "tpu"  # what the run must find; the CPU rehearsal test steers it
CACHE_DIR = ROOT / ".jax_cache"  # fixed: part of the compile cache's key


class NoChip(RuntimeError):
    """The host cannot run this cell: no result is printed."""


def tpu_chips_on_bus() -> int:
    """TPU chips on the PCI bus, found without starting any JAX backend."""
    from jax._src.hardware_utils import num_available_tpu_chips_and_device_id
    return num_available_tpu_chips_and_device_id()[0]


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise NoChip(f"no peaks for device kind {device_kind!r} in "
                     f"peaks.json; it holds {sorted(table)}")
    return table[device_kind]


@dataclass
class Cell:
    name: str
    entry: dict  # the workload's entry in BENCHMARK.json
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<cell>.json
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def model(self) -> dict:
        return self.config["model"]


def _reports(metric: dict, cell: str, moved: Optional[set] = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return moved is None or metric["moves"] in moved


def load_cell(name: str) -> Cell:
    bench = benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[entry["config"]]["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{entry['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, name, moved)]
    return Cell(name, entry, config, traffic, e2e, layer)


@dataclass
class Context:
    """What a runner gets: the cell, the run's arguments, the devices."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    peak: dict
    t_start: float

    @property
    def model(self) -> dict:
        return self.cell.model

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


@dataclass
class Outcome:
    """What a runner returns."""
    attempted: int
    failed: int
    setup_s: float
    checks: Dict[str, tuple]  # name -> (value, limit)
    values: Dict[str, float] = field(default_factory=dict)  # end-to-end
    reduced: object = None  # trace.Reduced of the traced window
    counters: Dict[str, float] = field(default_factory=dict)
    memory_peak_bytes: int = 0
    reference_s: float = 0.0  # the comparison's time, after the window


def read_metric(name: str, reading) -> Optional[float]:
    """Run ``metrics/<name>.py``'s ``read``; None where it finds nothing."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(reading)


def family(m: Mapping) -> ModuleType:
    """The family file of a configuration's ``model`` block,
    ``reference/<family>.py`` by the program's ``ModelConfig.family``: its
    parameter leaves (``leaves``, ``weights.Leaf``), its counts over the
    whole stack (``stack_params``, ``matmul_params``, ``mixer_flops``,
    ``decode_mixer_flops``, ``decode_state_bytes``), its plain reference
    (``logits`` for serving, ``loss`` for training) and its CPU sizes
    (``TINY``)."""
    return _load_family(FAMILIES / f"{m['family']}.py")


@functools.lru_cache(maxsize=None)
def _load_family(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(
            f"model family {path.stem!r} has no family file: {path} is "
            f"missing")
    spec = importlib.util.spec_from_file_location(
        "chip_family_" + path.stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Reading:
    """What a per-layer metric's reader reads."""
    reduced: object
    counters: Dict[str, float]
    model: dict
    traffic: dict
    peak: dict
    chips: int


def result_line(ctx: Context, out: Outcome) -> dict:
    cell = ctx.cell
    correct = out.failed == 0 and all(v <= lim
                                      for v, lim in out.checks.values())
    metrics = {}
    if ctx.trace:
        reading = Reading(out.reduced, out.counters, ctx.model, ctx.traffic,
                          ctx.peak, len(ctx.devices))
        for m in cell.per_layer:
            value = read_metric(m["name"], reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(out.values, setup_s=out.setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    d0 = ctx.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if ctx.trace:
        from benchmarks.chip import trace
        device["busy_s"] = out.reduced.busy_mean_s
        device["window_s"] = out.reduced.window_s
        line["breakdown"] = trace.breakdown(out.reduced)
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    return line


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def start_jax(chips: int):
    """The devices this cell runs on; raises NoChip where there are none."""
    if tpu_chips_on_bus() == 0:
        raise NoChip("no TPU chip on this host")
    import jax
    jax.config.update("jax_platforms", PLATFORM)
    devices = jax.devices()
    if devices[0].platform != PLATFORM:
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


