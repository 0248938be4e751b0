"""The stage reduction over the program's own spans (``bench/stages.py``)."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness, stages, trace
from bench.tests import tiny
from bench.tests.test_trace_work import _timeline
from bench.trace import Event, Timeline

MS = 1e6  # ns
DEV = "/device:TPU:0"


def _served():
    """A 10 ms window on one device and four host threads: the query
    thread (``bench.query`` around the scan stages), the writer (two
    applies, the second running past the window's close, and a refresh
    that started before it opened) and two clients encoding."""
    tl = Timeline(
        ops={DEV: [Event("k", 0, 1 * MS), Event("k", 2.8 * MS, 0.4 * MS),
                   Event("k", 8.5 * MS, 0.5 * MS)]},
        spans=[Event("bench.window", 0, 10 * MS),
               Event("bench.query", 1 * MS, 4 * MS)])
    threads = {
        "main": [Event("bench.query", 1 * MS, 4 * MS),
                 Event("ciao.serve.query", 1.5 * MS, 3 * MS),
                 Event("ciao.scan.prepare", 1.5 * MS, 1 * MS),
                 Event("ciao.plane.sync", 2 * MS, 0.5 * MS),
                 Event("ciao.scan.launch", 2.5 * MS, 0.5 * MS),
                 Event("ciao.scan.fetch", 3 * MS, 1 * MS)],
        "writer": [Event("ciao.serve.refresh", -1 * MS, 1.5 * MS),
                   Event("ciao.serve.apply", 6 * MS, 3.5 * MS),
                   Event("ciao.store.ingest", 6 * MS, 1 * MS),
                   Event("ciao.store.lock_wait", 7 * MS, 1 * MS),
                   Event("ciao.serve.apply", 9.8 * MS, 1.2 * MS)],
        "client-1": [Event("ciao.client.encode", 3.5 * MS, 3 * MS)],
        "client-2": [Event("ciao.client.encode", 0.5 * MS, 0.4 * MS)],
    }
    return tl, threads


def test_stage_time_self_time_and_threads():
    st = stages.reduce(*_served())
    got = {k: (s.n, s.time_s * 1e3, s.self_s * 1e3, s.threads, s.max_s * 1e3)
           for k, s in st.stages.items()}
    approx = lambda *v: pytest.approx(v)  # noqa: E731
    assert got == {
        "bench.query": approx(1, 4.0, 1.0, 1, 4.0),
        "ciao.serve.query": approx(1, 3.0, 0.5, 1, 3.0),
        "ciao.scan.prepare": approx(1, 1.0, 0.5, 1, 1.0),
        "ciao.plane.sync": approx(1, 0.5, 0.5, 1, 0.5),
        "ciao.scan.launch": approx(1, 0.5, 0.5, 1, 0.5),
        "ciao.scan.fetch": approx(1, 1.0, 1.0, 1, 1.0),
        # the second apply is clipped to the window's last 0.2 ms
        "ciao.serve.apply": approx(2, 3.7, 1.7, 1, 3.5),
        "ciao.store.ingest": approx(1, 1.0, 1.0, 1, 1.0),
        "ciao.store.lock_wait": approx(1, 1.0, 1.0, 1, 1.0),
        "ciao.client.encode": approx(2, 3.4, 3.4, 2, 3.0),
    }                           # the refresh started before the window
    assert st.window_s == pytest.approx(0.010)


def test_idle_goes_to_the_innermost_span_of_each_thread():
    st = stages.reduce(*_served())
    k = 5.3 / 7.3     # gap [3.2, 8.5] ms: 7.3 ms of spans over 5.3 of gap
    want = {
        "bench.query": 0.5 + 0.5 * k, "ciao.scan.prepare": 0.5,
        "ciao.plane.sync": 0.5, "ciao.scan.launch": 0.3,
        "ciao.scan.fetch": 0.8 * k, "ciao.serve.query": 0.5 * k,
        "ciao.client.encode": 3.0 * k, "ciao.store.ingest": 1.0 * k,
        "ciao.store.lock_wait": 1.0 * k, "ciao.serve.apply": 0.5 * k + 0.7,
        trace.NO_SPAN: 0.3,
    }
    got = dict(st.idle_by_stage)
    assert got == {n: pytest.approx(v * 1e-3) for n, v in want.items()}
    assert sum(got.values()) == pytest.approx(0.0081)    # all idle time
    assert [n for n, _ in st.idle_by_stage][0] == "ciao.client.encode"


@pytest.mark.parametrize("make", [
    _timeline,
    lambda: Timeline(
        ops={DEV: [Event("k", 0, 1 * MS), Event("k", 5 * MS, 1 * MS)]},
        spans=[Event("bench.window", 0, 8 * MS),
               Event("bench.encode", 1 * MS, 4 * MS),
               Event("bench.ingest_chunk", 1 * MS, 4 * MS),
               Event("bench.encode", 6 * MS, 1 * MS)]),
], ids=["one-thread", "overlapping-threads"])
def test_without_program_spans_idle_is_shared_as_idle_by_span(make):
    """Each benchmark span on a thread of its own: the innermost-span
    shares are the benchmark's own ``idle_by_span``, which the stage
    reduction leaves as it was."""
    tl = make()
    before = trace.reduce(tl)
    threads = {str(i): [e] for i, e in enumerate(tl.spans)
               if e.name != trace.WINDOW}
    st = stages.reduce(tl, threads)
    assert trace.reduce(tl) == before
    assert dict(st.idle_by_stage) == {
        n: pytest.approx(v) for n, v in before.idle_by_span}


def test_a_profile_keeps_program_spans_per_thread(tmp_path):
    """A real CPU profile: the benchmark's reduction sees only its own
    spans, the stage loader every ``bench.*`` and ``ciao.*`` span by
    thread, with metadata stripped from the names."""
    import threading

    import jax
    import jax.numpy as jnp

    from repro.core.telemetry import span

    def writer():
        with span("ciao.serve.apply"), span("ciao.store.ingest#rows=8#"):
            jnp.ones(8).block_until_ready()

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("bench.window"):
            t = threading.Thread(target=writer)
            t.start()
            t.join()
            with span("bench.query"), span("ciao.serve.query"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    tl = trace.load(str(tmp_path))
    assert sorted(e.name for e in tl.spans) == ["bench.query", "bench.window"]
    by_thread = sorted(sorted(e.name for e in evs)
                       for evs in stages.load(str(tmp_path)).values())
    assert by_thread == [["bench.query", "ciao.serve.query"],
                         ["ciao.serve.apply", "ciao.store.ingest"]]
    st = stages.reduce(tl, stages.load(str(tmp_path)))
    assert st.stages["ciao.store.ingest"].n == 1
    assert st.stages["bench.query"].self_s < st.stages["bench.query"].time_s


def test_stage_metrics_from_their_spans_and_counters():
    st = stages.Stages(window_s=2.0, idle_by_stage=[], stages={
        "ciao.scan.resolve": stages.Stage(100, 0.01, 0.01, 1, 0.001),
        "ciao.scan.prepare": stages.Stage(100, 0.2, 0.05, 1, 0.01),
        "ciao.scan.launch": stages.Stage(100, 0.03, 0.03, 1, 0.01),
        "ciao.scan.fetch": stages.Stage(100, 0.04, 0.04, 1, 0.01),
        "ciao.scan.assemble": stages.Stage(100, 0.05, 0.05, 1, 0.01),
        "ciao.serve.query": stages.Stage(100, 0.4, 0.06, 1, 0.01),
        "ciao.client.encode": stages.Stage(10, 0.05, 0.05, 4, 0.01),
        "ciao.client.pushdown": stages.Stage(10, 0.1, 0.1, 4, 0.01),
        "ciao.serve.apply": stages.Stage(5, 0.5, 0.1, 1, 0.01),
        "ciao.serve.refresh": stages.Stage(40, 0.2, 0.02, 1, 0.01),
        "ciao.store.lock_wait": stages.Stage(50, 0.1, 0.1, 2, 0.01),
    })
    scan = stages.metrics(st, {"queries": 100})
    assert scan == pytest.approx({
        "scan_resolve_us_per_query": 100.0,
        "scan_prepare_us_per_query": 2000.0,
        "scan_launch_us_per_query": 300.0,
        "scan_fetch_us_per_query": 400.0,
        "scan_assemble_us_per_query": 500.0,
        "serve_query_self_us_per_query": 600.0})
    load = stages.metrics(st, {"chunk_records": 1000, "rows": 5000,
                               "queued_s": 0.25})
    assert load == pytest.approx({
        "client_encode_us_per_krow": 5000.0,
        "client_pushdown_host_us_per_krow": 10000.0,
        "writer_busy_share": 25.0, "refresh_busy_share": 10.0,
        "ingest_lock_wait_share": 5.0, "ingest_queue_wait_ms": 50.0})
    # a program without the spans or the counter: no number, no error
    empty = stages.Stages(window_s=2.0, stages={}, idle_by_stage=[])
    both = stages.metrics(empty, {"queries": 100, "chunk_records": 1000})
    assert set(both) == set(scan) | set(load)
    assert all(v is None for v in both.values())


CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_a_tiny_traced_run_gives_every_stage_metric(name):
    # 256-record chunks: a 1 s window then holds many applies and
    # refreshes even on a busy test host, at less than half the CPU time
    c = tiny.cell(name)
    c.config = dict(c.config, chunk_records=256)
    out, summary, st = stages.run_cell(
        c, seed=2**31 + 11, seconds=1.0, **tiny.BACKENDS)
    assert all(v <= lim for v, lim in out.checks.values())
    got = stages.metrics(st, out.counters)
    assert len(got) == 6 and all(v is not None and v >= 0
                                 for v in got.values()), got
    assert dict(st.idle_by_stage).get(trace.NO_SPAN, 0.0) < summary.window_s


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/stages.py", "--workload", "ycsb.scan-a",
         "--seed", "1", "--seconds", "1"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 1 and "{" not in p.stdout
    assert "needs 1 TPU" in p.stderr
