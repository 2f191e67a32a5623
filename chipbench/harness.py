"""One run of one cell: find its files, run its driver, print the result.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* the configuration's ``file`` (``chipbench/configs/<config>.json``), whose
  ``kind`` names the driver ``chipbench/drivers/<kind>.py``;
* the traffic mix ``chipbench/traffic/<traffic>.json``;
* one reader per metric, ``chipbench/metrics/<metric>.py``, whose
  ``read(rec)`` takes the metric from the run's record and returns a
  number, or ``None`` when the record holds nothing to read.

Which metrics a cell reports, so that a new cell needs no edit to an
entry that is there: an end-to-end metric that lists ``workloads`` is
reported in those cells; one that lists none, in every cell whose record
its reader can read (``tree_s`` in every cell whose driver records
trees).  A per-layer metric always lists its cells; an entry without the
list is refused, so that none reaches a newly added cell unasked.

A driver's ``run(run)`` sets the cell up, calls ``run.open_window()``,
measures, calls ``run.close_window()``, and returns the record: a dict of
what the readers read, plus ``answers`` (``(points, merges | None)`` pairs
for the reference) and ``attempted``/``failed``.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

from chipbench.peaks import PEAKS

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    workload: dict
    config: dict
    traffic: dict
    driver: object
    metrics_e2e: list
    metrics_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(root / conf["file"])
    traffic = load_json(root / "chipbench" / "traffic" /
                        (w["traffic"] + ".json"))
    driver = _load_module(root / "chipbench" / "drivers" /
                          (config["kind"] + ".py"),
                          f"chipbench_driver_{config['kind']}")
    for m in bench["per_layer"]:
        if "workloads" not in m:
            raise ValueError(f"per-layer metric {m['name']!r} in "
                             "BENCHMARK.json lists no workloads: a "
                             "per-layer metric names the cells it is "
                             "reported in")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    layer = [m for m in bench["per_layer"] if name in m["workloads"]]
    return Cell(name, w, config, traffic, driver, e2e, layer)


def reader(name: str, root: Path = ROOT):
    mod = _load_module(root / "chipbench" / "metrics" / (name + ".py"),
                       "chipbench_metric_" + name.replace(".", "_"))
    return mod.read


def read_metrics(metrics: list, rec: dict, root: Path = ROOT) -> dict:
    """The result line's metrics: each of ``metrics`` whose reader finds
    something in the record, by name."""
    out = {}
    for m in metrics:
        value = reader(m["name"], root)(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"JAX found platform {dev.platform!r}, not a TPU; the "
                     "benchmark does not run on the CPU")
    if require_tpu and len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class CompileCounter:
    """Counts programs JAX compiles or loads from its persistent cache."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self) -> None:
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.count += 1


@dataclass
class Run:
    """What a driver gets: the cell, the arguments, and the window hooks."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float
    compiles: CompileCounter | None = None
    setup_s: float = 0.0
    t_window: float = 0.0
    t_window_end: float = 0.0
    compiles_in_window: int = 0
    memory_peak_bytes: int = 0
    trace_dir: str | None = None
    _profiling: bool = False
    _window_ann: object = None

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def annotate(self, what: str):
        """A span on the profiler's clock in a traced run; else nothing."""
        if not self._profiling:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench/" + what)

    def open_window(self) -> float:
        """End of set-up, start of the measured window; returns its start."""
        if self.trace:
            import jax

            self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._profiling = True
            self._window_ann = self.annotate("window")
            self._window_ann.__enter__()
        self.t_window = time.perf_counter()
        self.setup_s = self.t_window - self.t_process
        if self.compiles is not None:
            self._compiles0 = self.compiles.count
        return self.t_window

    def close_window(self) -> float:
        """End of the window; reads the memory peak; returns the end."""
        self.t_window_end = time.perf_counter()
        if self.compiles is not None:
            self.compiles_in_window = self.compiles.count - self._compiles0
        if self._profiling:
            import jax

            self._window_ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._profiling = False
        self.memory_peak_bytes = memory_peak(self.cell.workload["chips"])
        return self.t_window_end


def compare(run: Run, rec: dict) -> dict:
    """Run the reference over the record's answers; the compared numbers."""
    from chipbench import reference as ref

    tally = ref.Tally()
    method = run.config["method"]
    for points, merges in rec["answers"]:
        if merges is None:
            tally.add_missing()
        else:
            tally.add(merges, ref.reference_tree(points, method))
    out = tally.numbers()
    out["answers"] = tally.answers
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_tpu: bool = True,
             root: Path = ROOT) -> dict:
    """One run of cell ``name``; returns the result line's object."""
    cell = load_cell(name, root)
    device = device_info(cell.workload["chips"], require_tpu)
    sys.path.insert(0, str(root / "src"))
    import jax
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program goes into the persistent cache, however quick its
    # compile, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    run = Run(cell, seed, seconds, trace, t_process, CompileCounter())
    try:
        rec = cell.driver.run(run)
        rec.setdefault("setup_s", run.setup_s)
        rec.setdefault("window_s", run.t_window_end - run.t_window)
        t_red = time.perf_counter()
        if trace:
            from chipbench import xtrace

            rec["trace"] = xtrace.reduce_dir(run.trace_dir)
        t_ref = time.perf_counter()
        numbers = compare(run, rec)
        t_end = time.perf_counter()
    finally:
        if run.trace_dir:
            shutil.rmtree(run.trace_dir, ignore_errors=True)
    limits = cell.traffic["limits"]
    from chipbench.reference import judge

    correct, checks = judge(numbers, limits)
    metrics = read_metrics(cell.metrics_layer if trace else cell.metrics_e2e,
                           rec, root)
    device["memory_peak_bytes"] = run.memory_peak_bytes
    if device["kind"] in PEAKS:
        rec.setdefault("notes", {})["memory_peak_share"] = (
            run.memory_peak_bytes / PEAKS[device["kind"]]["hbm_bytes"])
    out = {"correct": correct, "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if trace:
        t = rec["trace"]
        device["busy_s"] = t.busy_s
        device["window_s"] = t.window_s
        out["breakdown"] = {"device_ops": t.device_ops,
                            "idle_gaps": t.idle_gaps}
    out["notes"] = {"compiles_in_window": run.compiles_in_window,
                    "answers_compared": numbers["answers"],
                    **{k: v for k, v in numbers.items()
                       if k not in limits and k != "answers"},
                    "trace_reduce_s": round(t_ref - t_red, 3),
                    "reference_s": round(t_end - t_ref, 3),
                    **rec.get("notes", {})}
    out["checks"] = checks
    return out


def print_result(out: dict) -> None:
    notes = " ".join(f"{k}={v}" for k, v in out["notes"].items())
    print(f"notes: {notes}", flush=True)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)

