"""Stages of the served path, from the program's own spans in a profile.

    python bench/stages.py --workload <cell> --seed <n> --seconds <s>

The benchmark's trace reduction (``bench/trace.py``) reads the device's
operations and the benchmark's own ``bench.*`` host spans.  The program
opens spans of its own, named ``ciao.*`` (``repro.core.telemetry.span``),
in the same profile and on the same clock.  This module reads both kinds,
host thread by host thread (one line of the profile's host plane each):

* :func:`load`: each host thread's ``bench.*`` and ``ciao.*`` spans, the
  window's own span left out, names stripped of ``#...#`` metadata;
* :func:`reduce`: for each span name, the spans that start in the window,
  their time clipped to it, their self time (that time less what their
  child spans on the same thread cover) and the threads they ran on; and
  each device-idle gap charged to the innermost span open on each host
  thread, shared and scaled as ``bench.trace.reduce`` shares a gap among
  the ``bench.*`` spans, the rest to ``no span``;
* :func:`metrics`: the per-layer numbers those stages give a cell.

Run as a script it runs one cell once with its window traced, as
``bench/run.py --trace 1`` does, and prints the stage table, the idle
time by innermost span, the stage metrics, the cell's own per-layer
metrics and whether its answers were correct.  Needs a TPU, like the
benchmark.  ``queued_s`` is read from the serving engine at the window's
open and close, as ``bench/drivers/load.py`` reads ``blocked_s``.
"""
from __future__ import annotations

import bisect
import glob
import os
import sys
from dataclasses import dataclass

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench.trace import NO_SPAN, WINDOW, Event, Timeline, union  # noqa: E402

PREFIXES = ("bench.", "ciao.")


@dataclass(frozen=True)
class Stage:
    n: int            # spans that start in the window
    time_s: float     # their time, clipped to the window
    self_s: float     # that time less what their child spans cover
    threads: int      # host threads they ran on
    max_s: float      # the longest of them, clipped: where a stall sits


@dataclass
class Stages:
    window_s: float
    stages: dict[str, Stage]
    idle_by_stage: list[tuple[str, float]]   # innermost span -> idle s

    def time(self, name: str) -> float | None:
        st = self.stages.get(name)
        return None if st is None else st.time_s


def load(trace_dir: str) -> dict[str, list[Event]]:
    """``<plane>#<line>`` -> that host thread's ``bench.*`` and ``ciao.*``
    spans, for the one profile written under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one profile under {trace_dir}, "
                           f"found {len(paths)}")
    threads: dict[str, list[Event]] = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            spans = [Event(e.name.split("#", 1)[0], e.start_ns, e.duration_ns)
                     for e in line.events if e.name.startswith(PREFIXES)]
            spans = [e for e in spans if e.name != WINDOW]
            if spans:
                threads[f"{plane.name}#{i}"] = spans
    return threads


def _nest(events: list[Event]):
    """One thread's spans, nested as they were opened (each inside the
    last one still open when it starts).  Returns the spans in that
    order, and the thread's time cut into disjoint pieces ``(start, end,
    span index)``, each owned by the innermost span open in it."""
    spans = sorted(events, key=lambda e: (e.start, -e.dur))
    pieces: list[tuple[float, float, int]] = []
    stack: list[list] = []          # [span index, end of what it owns]

    def pop() -> None:
        i, cur = stack.pop()
        if spans[i].end > cur:
            pieces.append((cur, spans[i].end, i))

    for i, e in enumerate(spans):
        while stack and spans[stack[-1][0]].end <= e.start:
            pop()
        if stack:
            top = stack[-1]
            if e.start > top[1]:
                pieces.append((top[1], e.start, top[0]))
            top[1] = max(top[1], min(e.end, spans[top[0]].end))
        stack.append([i, e.start])
    while stack:
        pop()
    pieces.sort()
    return spans, pieces


def _gaps(tl: Timeline, lo: float, hi: float) -> list[tuple[float, float]]:
    """The device-idle gaps of the window, per device, as
    ``bench.trace.reduce`` finds them."""
    if not tl.ops:
        return [(lo, hi)]
    gaps: list[tuple[float, float]] = []
    for events in tl.ops.values():
        busy = [(max(s, lo), min(e, hi))
                for s, e in union([(e.start, e.end) for e in events])
                if e > lo and s < hi]
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    return gaps


def reduce(tl: Timeline, threads: dict[str, list[Event]]) -> Stages:
    """Stage times and innermost-span idle time of the window of ``tl``
    (whose ``bench.window`` span bounds it), from each thread's spans."""
    windows = [e for e in tl.spans if e.name == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found "
                           f"{len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    clip = lambda s, e: max(0.0, min(e, hi) - max(s, lo))  # noqa: E731

    acc: dict[str, list] = {}   # name -> [n, time, self, thread keys, max]
    owned: list[tuple[list[float], list[tuple[float, float, str]]]] = []
    for key, events in threads.items():
        spans, pieces = _nest(events)
        own = [0.0] * len(spans)
        for s, e, i in pieces:
            own[i] += clip(s, e)
        for i, e in enumerate(spans):
            if lo <= e.start < hi:
                a = acc.setdefault(e.name, [0, 0.0, 0.0, set(), 0.0])
                a[0] += 1
                a[1] += clip(e.start, e.end)
                a[2] += own[i]
                a[3].add(key)
                a[4] = max(a[4], clip(e.start, e.end))
        named = [(s, e, spans[i].name) for s, e, i in pieces]
        owned.append(([s for s, _, _ in named], named))

    idle: dict[str, float] = {}
    for gs, ge in _gaps(tl, lo, hi):
        share: dict[str, float] = {}
        for starts, named in owned:
            k = max(bisect.bisect_right(starts, gs) - 1, 0)
            while k < len(named) and named[k][0] < ge:
                ov = min(named[k][1], ge) - max(named[k][0], gs)
                if ov > 0:
                    share[named[k][2]] = share.get(named[k][2], 0.0) + ov
                k += 1
        covered = sum(share.values())
        # several threads may cover one gap: scale their shares to it
        scale = min(1.0, (ge - gs) / covered) if covered else 0.0
        for name, ov in share.items():
            idle[name] = idle.get(name, 0.0) + ov * scale * 1e-9
        if covered < ge - gs:
            idle[NO_SPAN] = idle.get(NO_SPAN, 0.0) + (ge - gs - covered) * 1e-9

    return Stages(
        window_s=(hi - lo) * 1e-9,
        stages={name: Stage(n, t * 1e-9, s * 1e-9, len(keys), m * 1e-9)
                for name, (n, t, s, keys, m) in sorted(acc.items())},
        idle_by_stage=sorted(idle.items(), key=lambda kv: -kv[1]),
    )


def _per(value: float | None, base: float) -> float | None:
    return None if value is None or not base else value / base


def metrics(st: Stages, counters: dict[str, float]) -> dict[str, float | None]:
    """The stage metrics of a cell: those of the scan cells where
    ``counters`` has ``queries``, those of the load cells where it has
    ``chunk_records``; ``None`` where a span or counter is absent."""
    out: dict[str, float | None] = {}
    q = counters.get("queries")
    if q is not None:
        us_per_query = lambda name: _per(st.time(name), q / 1e6)  # noqa: E731
        for stage in ("resolve", "prepare", "launch", "fetch", "assemble"):
            out[f"scan_{stage}_us_per_query"] = us_per_query(
                f"ciao.scan.{stage}")
        sq = st.stages.get("ciao.serve.query")
        out["serve_query_self_us_per_query"] = _per(
            None if sq is None else sq.self_s, q / 1e6)
    C = counters.get("chunk_records")
    if C is not None:
        def us_per_krow(name):
            s = st.stages.get(name)
            return None if s is None else _per(s.time_s, s.n * C / 1e9)

        def share(name, threads=False):
            s = st.stages.get(name)
            if s is None:
                return None
            return 100.0 * s.time_s / (st.window_s * (s.threads if threads
                                                      else 1))

        slices = counters.get("rows", 0) / C
        queued = counters.get("queued_s")
        out.update({
            "client_encode_us_per_krow": us_per_krow("ciao.client.encode"),
            "client_pushdown_host_us_per_krow":
                us_per_krow("ciao.client.pushdown"),
            "writer_busy_share": share("ciao.serve.apply", threads=True),
            "refresh_busy_share": share("ciao.serve.refresh"),
            "ingest_lock_wait_share": share("ciao.store.lock_wait"),
            "ingest_queue_wait_ms": _per(
                None if queued is None else queued * 1e3, slices),
        })
    return out


class _Watch:
    """The serving engines a cell builds while it runs, and the growth
    of their ``queued_s`` over the window (``None`` for a program that
    keeps no such counter)."""

    def __init__(self, window):
        self.window = window
        self.engines: list = []
        self.queued_s: float | None = None

    def _total(self) -> float | None:
        vals = [getattr(e, "queued_s", None) for e in self.engines]
        return None if None in vals or not vals else sum(vals)

    def open(self) -> float:
        self._q0 = self._total()
        return self.window.open()

    def close(self) -> None:
        q1 = self._total()
        self.window.close()
        if q1 is not None and self._q0 is not None:
            self.queued_s = q1 - self._q0

    def __getattr__(self, name):
        return getattr(self.window, name)


def run_cell(cell, *, seed: int, seconds: float, **backends):
    """Run ``cell`` once with its window traced.  Returns the cell's
    :class:`~bench.harness.Outcome` (``queued_s`` added to its counters
    where the program keeps it), the trace's
    :class:`~bench.trace.Summary` and the :class:`Stages`."""
    import tempfile

    from bench import harness, trace
    from repro.serve import store_engine

    base = store_engine.CiaoServeEngine
    with tempfile.TemporaryDirectory(prefix="bench-stages-") as tmp:
        watch = _Watch(harness.Window(harness.Compiles(), tmp))

        class Watched(base):
            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                watch.engines.append(self)

        store_engine.CiaoServeEngine = Watched
        try:
            out = harness.driver(cell).run(cell, seed=seed, seconds=seconds,
                                           window=watch, **backends)
        finally:
            store_engine.CiaoServeEngine = base
        tl = trace.load(tmp)
        summary, stages = trace.reduce(tl), reduce(tl, load(tmp))
    if watch.queued_s is not None:
        out.counters["queued_s"] = watch.queued_s
    return out, summary, stages


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from bench.peaks import peaks
    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"stages: cell {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 1
    harness.compile_cache()
    out, summary, st = run_cell(cell, seed=args.seed, seconds=args.seconds)
    for line in out.notes:
        print(line, flush=True)
    for name, s in st.stages.items():
        print(json.dumps({"stage": name, "n": s.n, "time_s": s.time_s,
                          "self_s": s.self_s, "threads": s.threads,
                          "mean_us": s.time_s / s.n * 1e6 if s.n else None,
                          "max_us": s.max_s * 1e6}))
    print("idle by innermost span: " + ", ".join(
        f"{name} {secs:.3f} s" for name, secs in st.idle_by_stage))
    readings = harness.Readings(summary, out.counters,
                                peaks(devices[0].device_kind))
    print(json.dumps({
        "workload": cell.name, "seed": args.seed,
        "correct": all(v <= lim for v, lim in out.checks.values()),
        "window_s": st.window_s, "busy_s": summary.busy_s,
        "counters": out.counters,
        "stage_metrics": metrics(st, out.counters),
        "per_layer": {m["name"]: harness.reader(m["name"])(readings)
                      for m in cell.per_layer},
        "idle_gaps": summary.idle_by_span,
        "idle_by_stage": st.idle_by_stage,
    }), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
