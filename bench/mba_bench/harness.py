"""The harness: finds a cell's configuration, traffic mix and metric
readers by their names in BENCHMARK.json, checks for the chip, and runs
the cell once (set-up, window, comparison with the reference, metrics)."""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
# the system under test: the program's package, from the checkout
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

CONFIG_DIR = os.path.join(BENCH_DIR, "configs")
TRAFFIC_DIR = os.path.join(BENCH_DIR, "traffic")
METRIC_DIR = os.path.join(BENCH_DIR, "metrics")
# fixed, inside the checkout: the path is part of the cache's key
COMPILE_CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_devices(chips: int) -> Dict:
    """The ``device`` record of the result line; refuses anything but at
    least ``chips`` TPU chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"platform is {devices[0].platform!r}, not 'tpu'")
    if len(devices) < chips:
        raise NoChip(f"{len(devices)} chip(s) found, the cell asks for "
                     f"{chips}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set (JAX reads it itself), else a fixed directory at the
    root of the checkout.  Every program is cached, however fast it
    compiled, so that a run after the first compiles nothing."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def _load_json(directory: str, name: str, what: str) -> Dict:
    path = os.path.join(directory, f"{name}.json")
    if not os.path.isfile(path):
        raise KeyError(f"no {what} named {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> Dict:
    return _load_json(CONFIG_DIR, name, "configuration")


def load_traffic(name: str) -> Dict:
    return _load_json(TRAFFIC_DIR, name, "traffic mix")


def model_for(cfg: Dict):
    """The configuration's Quest patterns."""
    from mba_bench.quest import QuestModel, QuestParams
    return QuestModel.build(QuestParams.from_config(cfg["quest"]),
                            cfg["generator_seed"])


# ---------------------------------------------------------------------------
# BENCHMARK.json: cells and the metrics each reports
# ---------------------------------------------------------------------------

def load_benchmark(path: str = os.path.join(ROOT, "BENCHMARK.json")) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    cfg: Dict
    spec: Dict


def find_cell(bench: Dict, name: str) -> Cell:
    from mba_bench import traffic
    for w in bench["workloads"]:
        if w["name"] == name:
            return Cell(name=name, chips=int(w["chips"]),
                        cfg=load_config(w["config"]),
                        spec=traffic.validate(load_traffic(w["traffic"])))
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def _listed(entry: Dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def end_to_end_metrics(bench: Dict, cell: str) -> List[Dict]:
    return [m for m in bench["end_to_end"] if _listed(m, cell)]


def per_layer_metrics(bench: Dict, cell: str) -> List[Dict]:
    """Per-layer metrics the cell reports: those that list it, and those
    that list no cells and move an end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_metrics(bench, cell)}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in e2e)]


def load_reader(name: str) -> Callable:
    """``read(run)`` of the metric's own file ``metrics/<name>.py``."""
    path = os.path.join(METRIC_DIR, f"{name}.py")
    if not os.path.isfile(path):
        raise KeyError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        "mba_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ---------------------------------------------------------------------------
# one run of one cell
# ---------------------------------------------------------------------------

class Tracer:
    """The JAX profiler over part of a window, reduced after the run."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.active = False
        self.window_s = 0.0
        self._dir = None

    def start(self) -> None:
        if not self.enabled:
            return
        import jax
        self._dir = tempfile.mkdtemp(prefix="mba-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 2
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self.active = True
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        self.active = False

    def load(self):
        from mba_bench import trace as trace_mod
        if self._dir is None:
            return None
        try:
            return trace_mod.load(trace_mod.find_xplane(self._dir))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


class GcWatch:
    """Pauses of Python's garbage collector inside the window, for the
    log: a long one stalls every thread of the process at once."""

    def __init__(self):
        self.pauses: List = []        # (offset from the window start, s, gen)
        self._t0 = self._start = 0.0

    def _callback(self, phase: str, info: Dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._start = now
        else:
            self.pauses.append((self._start - self._t0, now - self._start,
                                info["generation"]))

    def __enter__(self) -> "GcWatch":
        self._t0 = time.perf_counter()
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._callback)

    def summary(self) -> str:
        if not self.pauses:
            return "gc: no collection in the window"
        at, longest, gen = max(self.pauses, key=lambda p: p[1])
        full = sum(1 for p in self.pauses if p[2] == 2)
        return (f"gc: {len(self.pauses)} collections ({full} full), "
                f"{sum(p[1] for p in self.pauses):.3f} s in all, longest "
                f"{longest * 1e3:.1f} ms (generation {gen}) at "
                f"{at:.3f} s into the window")


@dataclass
class RunRecord:
    """What the per-layer readers read."""

    loop: object                 # the MineLoop / OpenLoop after its window
    trace: Optional[object]      # trace.Trace of the traced span
    trace_window_s: float
    peak: Optional[Dict[str, float]]


def memory_peak(chips: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(bench: Dict, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             log=lambda msg: None) -> Dict:
    """Run one cell once; returns the result line as a dict."""
    from mba_bench import loops
    from mba_bench.peaks import peaks
    seed = int(seed) % (1 << 64)          # numpy seeds are non-negative
    cell = find_cell(bench, name)
    if require_chip:
        device = require_devices(cell.chips)
        peak = peaks(device["kind"])          # a chip not in the table: error
        log(f"device {device} | compile cache {enable_compile_cache()}")
    else:
        import jax
        device = {"platform": jax.devices()[0].platform,
                  "kind": jax.devices()[0].device_kind,
                  "count": len(jax.devices())}
        peak = None
    t_device = time.perf_counter()
    model = model_for(cell.cfg)
    T = model.corpus(cell.cfg["generator_seed"])
    # every seed mines the same corpus in another row order
    T = T[np.random.default_rng([int(seed), 2]).permutation(T.shape[0])]
    t_corpus = time.perf_counter()
    loop = loops.LOOPS[cell.spec["kind"]](cell.cfg, cell.spec, T, model,
                                           seed)
    loop.setup(seconds)
    t_setup = time.perf_counter()
    setup_s = t_setup - t_start
    log(f"set-up {setup_s:.3f} s: start to device {t_device - t_start:.3f}, "
        f"corpus {t_corpus - t_device:.3f}, system {t_setup - t_corpus:.3f}")
    tracer = Tracer(trace)
    with GcWatch() as watch:
        loop.window(seconds, tracer)
    log(watch.summary())
    device["memory_peak_bytes"] = memory_peak(cell.chips)
    loop.free()
    outcome = loop.check()
    correct = all(c.ok for c in outcome.checks)
    line: Dict = {"correct": correct, "attempted": outcome.attempted,
                  "failed": outcome.failed, "metrics": {}, "device": device}
    if not trace:
        values = dict(outcome.e2e, setup_s=setup_s)
        for m in end_to_end_metrics(bench, name):
            if m["name"] in values:
                line["metrics"][m["name"]] = {"value": values[m["name"]],
                                              "unit": m["unit"]}
    else:
        from mba_bench import trace as trace_mod
        tr = tracer.load()
        device["busy_s"] = trace_mod.busy_seconds(tr)
        device["window_s"] = tracer.window_s
        run = RunRecord(loop=loop, trace=tr, trace_window_s=tracer.window_s,
                        peak=peak)
        for m in per_layer_metrics(bench, name):
            value = load_reader(m["name"])(run)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
        line["breakdown"] = {"device_ops": trace_mod.top_device_ops(tr),
                             "idle_gaps": trace_mod.idle_gaps(tr)}
        counts = trace_mod.op_counts(tr)
        log(f"trace: busy {device['busy_s']:.4f} s of {tracer.window_s:.4f}"
            f" s; " + ", ".join(f"{op} x{counts[op]} {s:.4f} s" for op, s
                                in line["breakdown"]["device_ops"]))
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in outcome.checks}
    return line
