"""The benchmark's generic harness: finds a cell's pieces by name, runs its
set-up, its measured window and its correctness check, and reduces the run
to metrics.

Everything that belongs to one configuration, traffic mix or metric lives in
a file of its own under this directory, found by the name that
``BENCHMARK.json`` gives:

    configs/<config>.json     the deployment (named by the spec's ``file``)
    traffic/<traffic>.json    the traffic mix's parameters
    drivers/<driver>.py       one per kind of configuration (``Cell`` class)
    metrics/<metric>.py       one reader per metric (``read(run)``)
    peaks.json                published peaks, keyed by ``device_kind``

A driver's ``Cell`` takes ``(config, traffic, devices, control)`` and has

    setup()            -> {"plan_s": .., ...}  host-clock set-up layers
    begin(seed)        fresh inputs and a fresh sample for one seed
    request(i)         one timed request; returns (t_start, t_end, nbytes)
    check()            -> (checks, attempted, failed) after the window
    info               numbers the metric readers need (bytes, counts)

A reader returns a number, or ``None`` where the run has nothing for it to
read; the harness then leaves that metric out of the result.

JAX is imported inside the functions that use it: ``run.py`` imports this
module first and then sets the environment JAX reads when it starts
(``env_for_run``).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]                 # benchmarks/chip -> benchmarks -> root
BENCH_DIR = HERE.relative_to(ROOT)     # where configs/, traffic/, ... live


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


# -- finding a cell's pieces --------------------------------------------------

@dataclasses.dataclass
class CellSpec:
    root: pathlib.Path
    workload: dict
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def bench_path(self, *parts) -> pathlib.Path:
        return self.root.joinpath(BENCH_DIR, *parts)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> CellSpec:
    """The workload ``name`` of ``root/BENCHMARK.json`` with its
    configuration, traffic mix and the metrics it reports."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    try:
        wl = next(w for w in spec["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / BENCH_DIR / "traffic" / f"{wl['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    layer = [m for m in spec["per_layer"] if _applies(m, name)]
    return CellSpec(root, wl, config, traffic, e2e, layer)


def load_module(path: pathlib.Path):
    """Import a file by path (metric files carry dots in their names)."""
    mod_name = "_bench_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(root: pathlib.Path, kind: str) -> dict:
    table = json.loads((root / BENCH_DIR / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in the peaks table "
                       f"{BENCH_DIR / 'peaks.json'}")
    return table[kind]


# -- one run ----------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a metric reader reads."""

    cell: CellSpec
    peaks: Optional[dict]
    setup: Dict[str, float]
    requests: List[Tuple[float, float, int]]   # (t_start, t_end, nbytes)
    window: Tuple[float, float]
    info: dict
    trace: Optional[object] = None            # trace.Trace, --trace 1 only

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def rate_GBps(self) -> Optional[float]:
        """Payload bytes of every request completed in the window over the
        window's seconds."""
        if not self.requests:
            return None
        return sum(r[2] for r in self.requests) / self.window_s / 1e9

    def latency_ms(self, q: float) -> Optional[float]:
        """The ``q``-quantile (0..1, linear) of every request's latency."""
        if not self.requests:
            return None
        lat = np.array([r[1] - r[0] for r in self.requests])
        return float(np.quantile(lat, q)) * 1e3


def device_report(chips: int, require_tpu: bool = True) -> dict:
    """The platform, kind and count as JAX reports them. Raises ``NoChip``
    where there is no TPU (unless ``require_tpu`` is off, for tests) or
    fewer devices than ``chips``."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_tpu and dev["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX's platform is {dev['platform']}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    return dev


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _window(driver, seconds: float):
    """Requests back to back until ``seconds`` have passed; the last one
    started runs to its end."""
    import jax
    reqs = []
    i = 0
    t_start = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            reqs.append(driver.request(i))
            i += 1
            if time.perf_counter() - t_start >= seconds:
                break
    return reqs, (t_start, time.perf_counter())


def _count_compiles():
    """A counter of XLA compilations, fed by JAX's monitoring events."""
    import jax
    box = [0]

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            box[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return box


def checks_pass(checks: Dict[str, list]) -> bool:
    ops = {"<=": lambda v, l: v <= l, ">=": lambda v, l: v >= l}
    return all(ops[op](v, lim) for v, op, lim in checks.values())


class Session:
    """One cell's driver, set up once; ``run`` measures one seed.

    ``t_process`` is the host clock at process start (set-up is measured
    from it). ``traffic_overrides`` and ``require_tpu=False`` serve the
    tests only, which run the cells at a tiny size on CPU devices;
    ``control`` puts the driver's lower-precision reference in the program's
    place (it must come out not correct)."""

    def __init__(self, name: str, *, root: pathlib.Path = ROOT,
                 require_tpu: bool = True,
                 traffic_overrides: Optional[dict] = None,
                 control: bool = False, t_process: Optional[float] = None):
        import jax
        t_process = time.perf_counter() if t_process is None else t_process
        self.cell = cell = load_cell(name, root)
        if traffic_overrides:
            cell.traffic = {**cell.traffic, **traffic_overrides}
        self.device = device_report(cell.chips, require_tpu)
        self.peaks = (load_peaks(root, self.device["kind"]) if require_tpu
                      else None)
        self.devices = jax.devices()[:cell.chips]
        driver = load_module(cell.bench_path(
            "drivers", f"{cell.config['driver']}.py"))
        self.driver = driver.Cell(cell.config, cell.traffic, self.devices,
                                  control=control)
        self.setup = self.driver.setup()
        self.setup["setup_s"] = time.perf_counter() - t_process
        self.compiles = _count_compiles()

    def run(self, seed: int, seconds: float, trace: bool,
            dump_trace: Optional[str] = None) -> dict:
        """Measure one window for ``seed`` and return the result line."""
        import jax
        cell, driver, dev = self.cell, self.driver, dict(self.device)
        driver.begin(seed)
        tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        if trace:
            # host spans (TraceAnnotation) and device ops; no Python tracer
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            # the first dispatch under the profiler stalls for about 0.1 s;
            # one request before the window keeps that out of it
            driver.request(0)
            driver.begin(seed)
        n0 = self.compiles[0]
        try:
            reqs, window = _window(driver, seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        in_window = self.compiles[0] - n0
        dev["memory_peak_bytes"] = memory_peak_bytes(self.devices)

        tr = None
        if trace:
            from trace_reduce import Trace
            try:
                tr = Trace.from_dir(tdir, cell.chips)
            finally:
                shutil.rmtree(tdir, ignore_errors=True)
            dev["busy_s"] = tr.busy_s()
            dev["window_s"] = tr.window_s
            if dump_trace:
                tr.trim(dump_trace)
                pathlib.Path(dump_trace.replace(".json.gz", ".meta.json")) \
                    .write_text(json.dumps({"workload": cell.name,
                                            "info": driver.info,
                                            "device": dev,
                                            "lines": tr.lines}))

        checks, attempted, failed = driver.check()
        run = Run(cell=cell, peaks=self.peaks, setup=self.setup,
                  requests=reqs, window=window, info=driver.info, trace=tr)
        metrics = {}
        for m in (cell.per_layer if trace else cell.end_to_end):
            reader = load_module(cell.bench_path("metrics",
                                                 f"{m['name']}.py"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result = {"correct": checks_pass(checks), "attempted": attempted,
                  "failed": failed, "metrics": metrics, "device": dev}
        if tr is not None:
            result["breakdown"] = tr.breakdown()
        result["requests_in_window"] = len(reqs)
        result["compiles_in_window"] = in_window
        result["checks"] = checks
        return result


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             dump_trace: Optional[str] = None, **kw) -> dict:
    """Set up one cell and run it once (``Session``'s arguments)."""
    return Session(name, **kw).run(seed, seconds, trace, dump_trace)


def format_checks(checks: Dict[str, list]) -> List[str]:
    return [f"check {k}: {v} {op} {lim} "
            f"{'ok' if checks_pass({k: [v, op, lim]}) else 'FAILED'}"
            for k, (v, op, lim) in checks.items()]


def env_for_run() -> None:
    """Process settings made before JAX initialises: the compile cache in a
    fixed directory inside the checkout, and no TPU logs outside it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
