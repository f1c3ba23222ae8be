"""One run of one cell: find the chip, set up, measure, check, report.

The harness knows no configuration, mix or metric by name.  It reads the
cell from ``BENCHMARK.json``, the configuration from its ``file``, the mix
from ``traffic/<traffic>.json``, drives the configuration's ``system``
(``systems/<system>.py``), and asks each metric's reader
(``metrics/<metric>.py``) for its number.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict   # the configuration file, as loaded
    traffic: dict  # the mix file, as loaded


def load_bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or load_bench()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config, traffic=traffic)


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (``trace`` off) or per-layer metrics
    (``trace`` on): those that list the cell, or list no cells."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_reader(name: str):
    """``metrics/<name>.py`` as a module with a ``read(run)`` function."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""

    cell: Cell
    setup_s: float
    window_s: float = 0.0           # host clock, first op due to last done
    ops: int = 0                    # operations completed in the window
    batches: list = dataclasses.field(default_factory=list)  # one dict per batch
    geometry: tuple | None = None   # (nodes_per_bucket, node_size) of the table
    max_results: int | None = None  # the batches' RANGE output budget
    peak_bytes: int | None = None   # the runtime's peak_bytes_in_use, fullest chip
    temp_bytes: int | None = None   # the window's executor's temporaries, per chip
    peaks: dict | None = None
    trace: object | None = None     # devtrace.Trace of the window
    compiles: int = 0               # backend compilations in the window
    restructures: int = 0           # restructure-and-replay in the window


class Spans:
    """Host spans around the calls into each layer, written into the
    profiler's trace as ``cb:<name>`` when tracing is on (``devtrace``
    names the device's idle gaps by them)."""

    def __init__(self, trace: bool):
        self.trace = trace

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.trace:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation(f"cb:{name}"):
            yield


class CompileCounter:
    """Counts JAX backend compilations (persistent-cache loads included)."""

    def __init__(self):
        import jax

        self.n = 0
        self._cb = lambda event, _dur, **_kw: self._seen(event)
        jax.monitoring.register_event_duration_secs_listener(self._cb)

    def _seen(self, event):
        if event == COMPILE_EVENT:
            self.n += 1

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._cb)


def find_devices(chips: int, *, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if require_chip and len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def peak_bytes(devs) -> int | None:
    peaks = []
    for d in devs:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    bench: dict | None = None,
    require_chip: bool = True,
    exec_config=None,
    fault: str | None = None,
    t_start: float | None = None,
    log=None,
) -> dict:
    """One run; returns the result object the CLI prints last."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    bench = bench or load_bench()
    devs = find_devices(cell.chips, require_chip=require_chip)
    peaks = peaks_for(devs[0].device_kind) if require_chip else None
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")

    system = importlib.import_module(f"chipbench.systems.{cell.config['system']}")
    spans = Spans(trace)
    counter = CompileCounter()
    try:
        sut = system.System(cell, seed, spans, exec_config=exec_config, fault=fault, log=log)
        sut.setup()
        run = Run(cell=cell, setup_s=time.perf_counter() - t_start, peaks=peaks)
        log(f"setup: {run.setup_s:.3f} s")
        compiles0 = counter.n
        with contextlib.ExitStack() as stack:
            if trace:
                import jax

                tdir = stack.enter_context(tempfile.TemporaryDirectory(prefix="cb-trace-"))
                jax.profiler.start_trace(tdir)
            try:
                with spans.span("window"):
                    sut.window(seconds, run)
            finally:
                if trace:
                    jax.profiler.stop_trace()
            run.compiles = counter.n - compiles0
            if trace:
                from chipbench import devtrace

                run.trace = devtrace.load(next(Path(tdir).rglob("*.xplane.pb")))
    finally:
        counter.close()
    run.peak_bytes = peak_bytes(devs)
    run.temp_bytes = sut.temp_bytes
    log(
        f"window: {run.window_s:.3f} s, {run.ops} ops in {len(run.batches)} batches, "
        f"compiles={run.compiles} restructures={run.restructures}"
    )
    t_check = time.perf_counter()
    compared = sut.check(run)
    log(f"check: {time.perf_counter() - t_check:.3f} s")
    correct = all(value <= limit for _, value, limit in compared)

    metrics = {}
    for spec in metrics_for(bench, cell.name, trace):
        value = load_reader(spec["name"]).read(run)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": run.peak_bytes,
    }
    result = {
        "correct": correct,
        "attempted": run.ops,
        "failed": sut.failed,
        "metrics": metrics,
        "device": device,
    }
    if run.trace is not None:
        from chipbench import devtrace

        device["busy_s"], device["window_s"] = devtrace.busy_share(run.trace)
        result["breakdown"] = devtrace.breakdown(run.trace)
    result["compared"] = {name: {"value": v, "limit": lim} for name, v, lim in compared}
    for name, v, lim in compared:
        log(f"compared {name}: {v} (limit {lim})")
    return result
