"""Runs one cell once and prints the result line.

Everything that belongs to one cell is found by name: the cell's entry
in ``BENCHMARK.json`` names its configuration (a file of sizes under
``bench/configs``) and its traffic mix (``bench/traffic/<mix>.json``),
whose ``kind`` names the driver (``bench/drivers/<kind>.py``).  Each
per-layer metric is read by ``bench/metrics/<name>.py``.  A new cell,
mix of an existing kind or per-layer metric is a new file plus an entry
in ``BENCHMARK.json``; nothing here changes.

A driver's ``run(cell, seed=, seconds=, window=, **backends)`` sets up,
measures inside ``window`` and checks its answers, returning an
:class:`Outcome`.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    root: Path = ROOT


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    try:
        w = next(w for w in spec["workloads"] if w["name"] == name)
    except StopIteration:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json") from None
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    traffic = root / HERE.name / "traffic" / f"{w['traffic']}.json"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(traffic.read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        root=root,
    )


def driver(cell: Cell):
    return importlib.import_module(f"bench.drivers.{cell.traffic['kind']}")


def reader(metric: str, root: Path = ROOT):
    """The ``read(readings)`` function of ``bench/metrics/<metric>.py``,
    or, where there is none, of the reader its stem names: one reader
    serves ``device_idle_share.load`` and ``device_idle_share.scan``."""
    path = root / HERE.name / "metrics" / f"{metric}.py"
    if not path.exists():
        path = path.with_name(f"{metric.rsplit('.', 1)[0]}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Compiles:
    """Counts backend compilations (persistent-cache hits are not)."""

    def __init__(self) -> None:
        import jax
        self.n = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs


class Window:
    """The measured window: its clock, compiles and garbage collections
    inside it and, when ``trace_dir`` is given, a profiler trace of it.

    Opening it first collects set-up's garbage, so that a full
    collection of the objects set-up left behind does not stall the
    window."""

    def __init__(self, compiles: Compiles, trace_dir: str | None) -> None:
        self.compiles = compiles
        self.trace_dir = trace_dir
        self.t0 = self.t1 = 0.0
        self.n_compiles = 0
        self.gc_pauses: list[tuple[int, float]] = []   # (generation, s)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self.gc_pauses.append(
                (info["generation"], time.perf_counter() - self._gc_t))

    def open(self) -> float:
        import jax
        gc.collect()
        gc.callbacks.append(self._on_gc)
        self._c0 = self.compiles.n
        if self.trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # no event per Python call
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.t0 = time.perf_counter()
        return self.t0

    def close(self) -> None:
        import jax
        self.t1 = time.perf_counter()
        gc.callbacks.remove(self._on_gc)
        self._span.__exit__(None, None, None)
        if self.trace_dir:
            jax.profiler.stop_trace()
        self.n_compiles = self.compiles.n - self._c0


@dataclass
class Outcome:
    """What a driver hands back.  ``checks`` maps each number compared
    with the reference to ``(value, limit)``: the run is correct when
    every value is at most its limit."""

    attempted: int
    failed: int
    end_to_end: dict[str, float]
    counters: dict[str, float]
    checks: dict[str, tuple[float, float]]
    memory_peak_bytes: int
    notes: list[str] = field(default_factory=list)
    # the control's readings of the same numbers (``bench/control.py``)
    control: dict[str, float] | None = None


@dataclass
class Readings:
    """What a per-layer reader may read."""

    trace: "object | None"        # bench.trace.Summary
    counters: dict[str, float]
    peaks: dict[str, float]


def memory_peak_bytes(n_devices: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()[:n_devices]]
    return int(max(peaks, default=0))


def compile_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``repro.compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``), with every program cached, however fast
    it compiled."""
    import jax
    from repro.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def measure(cell: Cell, *, seed: int, seconds: float, trace: bool,
            t_start: float, device_kind: str, **backends) -> dict:
    """Run ``cell`` once; return the result line's object."""
    from bench import trace as tracing
    from bench.peaks import peaks

    compiles = Compiles()
    print(f"set-up before the driver: {time.perf_counter() - t_start:.3f} s",
          flush=True)
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
        window = Window(compiles, tmp if trace else None)
        out: Outcome = driver(cell).run(
            cell, seed=seed, seconds=seconds, window=window, **backends)
        summary = tracing.reduce(tracing.load(tmp)) if trace else None
    for line in out.notes:
        print(line, flush=True)
    print(f"compiles in the window: {window.n_compiles}; set-up "
          f"compiles: {compiles.n - window.n_compiles} "
          f"({compiles.secs:.3f} s in all)", flush=True)
    full = [s for g, s in window.gc_pauses if g == 2]
    print(f"garbage collections in the window: {len(window.gc_pauses)}, "
          f"{len(full)} full, longest {max(full, default=0.0):.4f} s",
          flush=True)

    metrics: dict[str, dict] = {}
    if trace:
        readings = Readings(summary, out.counters, peaks(device_kind))
        for m in cell.per_layer:
            v = reader(m["name"], cell.root)(readings)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(out.end_to_end, setup_s=window.t0 - t_start)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result = {
        "correct": all(v <= lim for v, lim in out.checks.values()),
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": metrics,
        "device": {"memory_peak_bytes": out.memory_peak_bytes},
    }
    if summary is not None:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = {
            "device_ops": [list(kv) for kv in summary.top_ops],
            "idle_gaps": [list(kv) for kv in summary.idle_by_span],
        }
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in out.checks.items()}
    return result


def main(argv: list[str] | None = None, *, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    print(f"devices: {len(devices)} x {devices[0].device_kind}; compile "
          f"cache {compile_cache()}", flush=True)
    result = measure(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=t_start,
                     device_kind=devices[0].device_kind)
    result["device"] = {"platform": devices[0].platform,
                        "kind": devices[0].device_kind,
                        "count": len(devices), **result["device"]}
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
