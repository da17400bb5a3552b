"""What every cell shares: the manifest, the run's context, the device
check, the compile watch, and the readers that turn what a driver observed
into named metrics.

Nothing here knows a cell, a configuration or a metric by name. A cell is
``benchmarks/workloads/<name>.json``; it names its driver
(``benchmarks/drivers/<driver>.py``) and its configuration
(``benchmarks/configs/<config>.json``). A metric is a function in any module
under ``benchmarks/end_to_end/`` or ``benchmarks/layer_metrics/`` listed in
that module's ``READERS`` dict under the metric's name; it takes the driver's
observations (a dict) and returns a number, or None where it finds nothing
to read. Adding a cell, a configuration, a driver or a metric is adding files
and one entry to ``BENCHMARK.json``.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import pkgutil
import time
from pathlib import Path
from typing import Any, Callable

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmarks"
#: everything a run leaves behind, inside the checkout and git-ignored
RUNS_DIR = REPO / ".bench_runs"
CACHE_DIR = REPO / ".jax_cache"
TUNE_DIR = REPO / ".tune_cache"


def log(**fields: Any) -> None:
    """One JSON line on standard output, before the result line."""
    print(json.dumps(fields, default=str), flush=True)


def load_manifest(root: Path = REPO) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: list[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"BENCHMARK.json has no {what} named {name!r}")


@dataclasses.dataclass
class Run:
    """One run of one cell."""

    workload: dict          # the manifest's entry
    cell: dict              # benchmarks/workloads/<name>.json
    config: dict            # benchmarks/configs/<config>.json
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_process_start: float
    device: dict = dataclasses.field(default_factory=dict)
    watch: Any = None

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def run_dir(self) -> Path:
        """Scratch for this run's files (metrics rows, the trace)."""
        d = RUNS_DIR / f"{self.name}-s{self.seed}-t{int(self.trace)}"
        d.mkdir(parents=True, exist_ok=True)
        return d


def load_run(root: Path, workload: str, *, seed: int, seconds: float,
             trace: bool, rehearse: bool, t_process_start: float) -> Run:
    manifest = load_manifest(root)
    entry = find(manifest["workloads"], workload, "workload")
    config_entry = find(manifest["configs"], entry["config"], "config")
    cell = json.loads((root / "benchmarks" / "workloads"
                       / f"{workload}.json").read_text())
    config = json.loads((root / config_entry["file"]).read_text())
    return Run(workload=entry, cell=cell, config=config, seed=seed,
               seconds=seconds, trace=trace, rehearse=rehearse,
               t_process_start=t_process_start)


class NoAccelerator(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def claim_devices(run: Run) -> list:
    """The devices this run uses, after checking that they are what the
    cell asks for. A rehearsal takes whatever backend is there (and asks the
    CPU for as many virtual devices as the cell has chips)."""
    import jax
    if run.rehearse and run.chips > 1:
        try:
            jax.config.update("jax_num_cpu_devices", max(run.chips, 4))
        except RuntimeError:
            pass  # backend already up (the test suite's eight devices)
    devices = jax.devices()
    run.device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": run.chips}
    if not run.rehearse and (devices[0].platform != "tpu"
                             or len(devices) < run.chips):
        raise NoAccelerator(
            f"{run.name} needs {run.chips} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform} "
            f"({devices[0].device_kind}). Nothing ran. (--rehearse runs the "
            f"cell at a tiny size on any backend, under that backend's name.)")
    if len(devices) < run.chips:
        raise NoAccelerator(f"{run.name} needs {run.chips} devices even to "
                            f"rehearse; found {len(devices)}")
    return devices[:run.chips]


def enable_caches() -> str:
    """The compile cache where ``JAX_COMPILATION_CACHE_DIR`` says, else at a
    fixed path in the checkout; the kernel-tune lookups in the checkout."""
    from jimm_tpu import tune
    from jimm_tpu.aot.export import enable_persistent_cache
    tune.configure(TUNE_DIR)
    return enable_persistent_cache(CACHE_DIR)


class CompileWatch:
    """Counts backend compile requests and persistent-cache hits and misses
    through ``jax.monitoring`` (copy of ``chip_smoke.py::CompileWatch``)."""

    def __init__(self) -> None:
        import jax
        self.requests: list[dict] = []
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests.append({"fun": str(kw.get("fun_name")),
                                  "seconds": duration,
                                  "started": time.time() - duration})

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def between(self, t0: float, t1: float) -> list[str]:
        """Compile requests that started inside [t0, t1] (wall clock)."""
        return [c["fun"] for c in self.requests if t0 < c["started"] < t1]

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def start_device_trace(trace_dir) -> None:
    """Start the profiler for the device planes alone. The host tracer, even
    at its lowest level, records every chunk of the runtime's host-side
    layout transposes (1.8 million events in three steps) and slowed the
    placement of a batch from 35 ms to half a second; the Python tracer
    slowed the whole loop eightfold (my chip runs, PR 22). Either would turn
    the idle share into a measurement of the profiler. (A CPU runs its
    operations on host threads, so a rehearsal keeps the host tracer.)"""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0 if jax.default_backend() == "tpu" else 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)


def memory_stats_peak(devices) -> int:
    """``peak_bytes_in_use`` on the fullest device, 0 where the backend
    reports none. On this runtime it counts live buffers, not a program's
    scratch, so a driver adds what ``compiled.memory_analysis()`` says."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def program_bytes(compiled) -> dict:
    """What one compiled program asks of each device."""
    ma = compiled.memory_analysis()
    parts = {"arguments": int(ma.argument_size_in_bytes),
             "temporaries": int(ma.temp_size_in_bytes),
             "outputs": int(ma.output_size_in_bytes),
             "aliased": int(ma.alias_size_in_bytes)}
    parts["resident"] = (parts["arguments"] + parts["temporaries"]
                         + parts["outputs"] - parts["aliased"])
    return parts


# ---------------------------------------------------------------------------
# Metrics: found by name
# ---------------------------------------------------------------------------

Reader = Callable[[dict], "float | None"]


def load_readers(package: str) -> dict[str, Reader]:
    """Every ``READERS`` entry of every module in ``benchmarks/<package>/``."""
    readers: dict[str, Reader] = {}
    pkg = importlib.import_module(f"benchmarks.{package}")
    for info in pkgutil.iter_modules(pkg.__path__):
        module = importlib.import_module(f"benchmarks.{package}.{info.name}")
        for name, fn in getattr(module, "READERS", {}).items():
            if name in readers:
                raise SystemExit(f"metric {name!r} has two readers under "
                                 f"benchmarks/{package}/")
            readers[name] = fn
    return readers


def collect_metrics(manifest: dict, run: Run, observed: dict) -> dict:
    """The cell's metrics of this run's kind: ``end_to_end`` without a
    trace, ``per_layer`` with one. A metric that lists ``workloads`` exists
    only there; a reader that finds nothing returns None and the metric is
    left out of the line."""
    kind = "per_layer" if run.trace else "end_to_end"
    readers = load_readers("layer_metrics" if run.trace else "end_to_end")
    reported = {m["name"] for m in manifest["end_to_end"]
                if run.name in m.get("workloads", [run.name])}
    out = {}
    for metric in manifest[kind]:
        if run.name not in metric.get("workloads", [run.name]):
            continue
        if run.trace and metric["moves"] not in reported:
            continue
        reader = readers.get(metric["name"])
        if reader is None:
            raise SystemExit(f"no reader for {kind} metric "
                             f"{metric['name']!r}")
        value = reader(observed)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def result_line(run: Run, outcome: dict, metrics: dict) -> str:
    """The contract's last line."""
    device = dict(run.device)
    device["memory_peak_bytes"] = int(outcome["memory_peak_bytes"])
    if run.trace:
        device["busy_s"] = outcome["trace"]["busy_s"]
        device["window_s"] = outcome["trace"]["window_s"]
    line = {"correct": bool(outcome["correct"]),
            "attempted": int(outcome["attempted"]),
            "failed": int(outcome["failed"]),
            "metrics": metrics, "device": device}
    if run.trace and outcome["trace"].get("breakdown"):
        line["breakdown"] = outcome["trace"]["breakdown"]
    return json.dumps(line)


def import_driver(name: str):
    return importlib.import_module(f"benchmarks.drivers.{name}")
