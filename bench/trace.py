"""From a profiler trace of the measured window to device numbers.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain :class:`Event` lists: the device's operations (the ``XLA Ops``
line of each TPU plane), its program executions (``XLA Modules``) and
the benchmark's own host spans (``TraceAnnotation`` names starting with
``bench.``, on the host plane).  :func:`reduce` turns those lists into a
:class:`Summary`:

* the window: the ``bench.window`` span;
* busy time: the union of the intervals in which an operation ran on a
  device, clipped to the window and averaged over the devices;
* program time: the summed device durations of each program's
  executions, by program name, and how many there were;
* the operations that took most device time, by HLO name;
* idle time by what the host was doing: each gap between busy
  intervals inside the window is shared among the ``bench.*`` spans
  open in it, by how much of it each covers (scaled down where spans of
  several host threads overlap), and what none covers is ``no span``.

Kept apart from JAX so that tests can feed it synthetic events.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

WINDOW = "bench.window"
NO_SPAN = "no span"
_TPU_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclass(frozen=True)
class Event:
    name: str
    start: float    # ns, on the profile's clock
    dur: float      # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Timeline:
    ops: dict[str, list[Event]] = field(default_factory=dict)      # device
    modules: dict[str, list[Event]] = field(default_factory=dict)  # device
    spans: list[Event] = field(default_factory=list)               # host


@dataclass
class Summary:
    window_s: float
    busy_s: float                              # averaged over devices
    programs: dict[str, tuple[int, float]]     # name -> (runs, device s)
    top_ops: list[tuple[str, float]]
    idle_by_span: list[tuple[str, float]]

    def program(self, pattern: str) -> tuple[int, float]:
        """Runs and device seconds of every program whose name holds
        ``pattern``."""
        runs = secs = 0
        for name, (n, s) in self.programs.items():
            if pattern in name:
                runs += n
                secs += s
        return runs, secs


def load(trace_dir: str) -> Timeline:
    """The events of the one profile written under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one profile under {trace_dir}, "
                           f"found {len(paths)}")
    tl = Timeline()
    for plane in ProfileData.from_file(paths[0]).planes:
        if _TPU_PLANE.match(plane.name):
            for line in plane.lines:
                dest = {"XLA Ops": tl.ops,
                        "XLA Modules": tl.modules}.get(line.name)
                if dest is not None:
                    dest.setdefault(plane.name, []).extend(
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tl.spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                                for e in line.events
                                if e.name.startswith("bench."))
    return tl


def op_name(hlo: str) -> str:
    """An operation's name without its HLO text: ``%fusion.3 = u8[..]
    fusion(..)`` is ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(ivs, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def reduce(tl: Timeline, top: int = 10) -> Summary:
    windows = [e for e in tl.spans if e.name == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found "
                           f"{len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    busy_ns = 0.0
    gaps: list[tuple[float, float]] = []
    for events in tl.ops.values():
        busy = _clip(union([(e.start, e.end) for e in events]), lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    n_dev = max(len(tl.ops), 1)
    if not tl.ops:
        gaps = [(lo, hi)]

    programs: dict[str, tuple[int, float]] = {}
    for events in tl.modules.values():
        for e in events:
            if lo <= e.start < hi:
                n, s = programs.get(e.name, (0, 0.0))
                programs[e.name] = (n + 1, s + e.dur * 1e-9)
    op_time: dict[str, float] = {}
    for events in tl.ops.values():
        for e in events:
            if lo <= e.start < hi:
                name = op_name(e.name)
                op_time[name] = op_time.get(name, 0.0) + e.dur * 1e-9

    spans = sorted((e for e in tl.spans if e.name != WINDOW),
                   key=lambda e: e.start)
    starts = [e.start for e in spans]
    longest = max((e.dur for e in spans), default=0.0)
    idle: dict[str, float] = {}
    for gs, ge in gaps:
        share: dict[str, float] = {}
        first = bisect.bisect_left(starts, gs - longest)
        for e in spans[first:bisect.bisect_left(starts, ge)]:
            ov = min(e.end, ge) - max(e.start, gs)
            if ov > 0:
                share[e.name] = share.get(e.name, 0.0) + ov
        covered = sum(share.values())
        # spans of several host threads may overlap one gap: scale to it
        scale = min(1.0, (ge - gs) / covered) if covered else 0.0
        for name, ov in share.items():
            idle[name] = idle.get(name, 0.0) + ov * scale * 1e-9
        if covered < ge - gs:
            idle[NO_SPAN] = idle.get(NO_SPAN, 0.0) + (ge - gs - covered) * 1e-9

    by_time = lambda kv: -kv[1]
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns * 1e-9 / n_dev,
        programs=programs,
        top_ops=sorted(op_time.items(), key=by_time)[:top],
        idle_by_span=sorted(idle.items(), key=by_time)[:top],
    )
