"""Open-loop COUNT(*) queries on the device plane of a quiesced store.

Set-up draws ``store_records`` records from the seed, builds the
pushdown plan for the traffic's query workload, runs every chunk through
the client pushdown kernel into ``CiaoServeEngine`` and quiesces it.
It then answers every distinct query of the workload once with
``mode="device"``, which compiles every launch shape the window uses and
pays whatever promotion of raw rows the mix needs.

In the window one generator sends ``ceil(rate_qps * seconds)`` queries,
due at evenly spaced times whatever came before (open loop), in an
order drawn from the seed: back-to-back permutations of the workload,
so every seed sends the same mix.  A query's latency runs from when it
was due to when its answer is back, so a stall delays every query
behind it.  Queries still unanswered ``late_limit_s`` after the window
closes count as failed.

Afterwards every answer to a sample of the distinct queries, drawn from
the seed, is compared with the reference's exact count over all
ingested records, and the snapshot must hold every acknowledged row.
"""
from __future__ import annotations

import gc
import math
import time
from dataclasses import dataclass

import numpy as np

from bench import reference, sut, work, workload
from bench.harness import Outcome, memory_peak_bytes


@dataclass
class State:
    """A quiesced, warmed store behind its serving engine."""

    serve: object
    table: object
    pool: list
    queries: list          # pool-index tuples, the workload in order
    program: dict          # pool-index tuple -> program Query
    notes: list


def setup(cell, seed: int, *, client_backend: str = "pallas",
          scan_backend: str = "pallas") -> State:
    from repro.core.client import encode_chunk
    from repro.core.server import CiaoStore
    from repro.kernels.engine import KernelEngine
    from repro.serve.store_engine import CiaoServeEngine

    cfg, tr = cell.config, cell.traffic
    ds = sut.dataset(cfg["dataset"])
    pool = ds.pool()
    queries = workload.generate(len(pool), **tr["workload"])
    t = time.perf_counter()
    table = ds.generate(cfg["store_records"], seed)
    records = table.json()
    t_gen = time.perf_counter() - t
    plan = sut.build_plan(pool, queries, records[:cfg["plan_sample_records"]],
                          cfg["budget_us_per_record"])
    C = cfg["chunk_records"]
    eng = KernelEngine(client_backend, r_blk=cfg["client_r_blk"])
    clauses = tuple(plan.clauses)
    serve = CiaoServeEngine(CiaoStore(plan), device_backend=scan_backend)
    t = time.perf_counter()
    for i in range(0, len(records), C):
        ch = encode_chunk(records[i:i + C])
        serve.ingest_chunk(ch, eng.eval_fused(ch, clauses))
    serve.quiesce()
    t_ingest = time.perf_counter() - t
    del records
    distinct = sorted(set(queries))
    program = dict(zip(distinct, sut.program_queries(pool, distinct)))
    t = time.perf_counter()
    for q in distinct:
        serve.query(program[q], mode="device")
    t_warm = time.perf_counter() - t
    stats = serve.snapshot().stats
    pushed = set(sut.pushed_pool_indices(plan, pool))
    holding = sum(bool(pushed & set(q)) for q in queries)
    return State(serve, table, pool, queries, program, notes=[
        f"plan: {len(pushed)} pushed clauses "
        f"{[pool[i] for i in sorted(pushed)]}; {holding} of {len(queries)} "
        f"queries hold one",
        f"loading ratio {stats.loading_ratio:.4f} ({stats.n_loaded} of "
        f"{stats.n_records} rows loaded, {stats.n_jit_loaded} promoted)",
        f"set-up: generation {t_gen:.3f} s; ingest + quiesce {t_ingest:.3f} "
        f"s; {len(distinct)} "
        f"distinct queries warmed in {t_warm:.3f} s",
    ])


def serve_window(state: State, *, seed: int, rate: float, seconds: float,
                 window, late_limit_s: float) -> dict:
    """Send the window's queries; returns their schedule and answers."""
    import jax
    span = jax.profiler.TraceAnnotation
    n = math.ceil(rate * seconds)
    rng = np.random.default_rng([seed, 2])
    blocks = -(-n // len(state.queries))
    order = np.concatenate([rng.permutation(len(state.queries))
                            for _ in range(blocks)])[:n]
    sent = [state.queries[k] for k in order]
    latency = np.zeros(n)
    started = np.zeros(n)
    results: list = [None] * n
    serve = state.serve
    t0 = window.open()
    give_up = t0 + seconds + late_limit_s
    for k, q in enumerate(sent):
        due = t0 + k / rate
        now = time.perf_counter()
        if now < due:
            with span("bench.sleep"):
                time.sleep(due - now)
        elif now > give_up:
            latency[k:] = now - (t0 + np.arange(k, n) / rate)
            break
        started[k] = time.perf_counter() - due
        with span("bench.query"):
            results[k] = serve.query(state.program[q], mode="device")
        latency[k] = time.perf_counter() - due
    window.close()
    return {"sent": sent, "latency": latency, "late": started,
            "results": results}


def run(cell, *, seed: int, seconds: float, window,
        client_backend: str = "pallas", scan_backend: str = "pallas",
        control: bool = False) -> Outcome:
    cfg, tr = cell.config, cell.traffic
    st = setup(cell, seed, client_backend=client_backend,
               scan_backend=scan_backend)
    w = serve_window(st, seed=seed, rate=tr["rate_qps"], seconds=seconds,
                     window=window, late_limit_s=tr["late_limit_s"])
    peak = memory_peak_bytes(cell.chips)
    snap = st.serve.snapshot()
    stats = snap.stats
    st.serve.close()
    st.serve = snap = None
    gc.collect()

    done = [(q, r) for q, r in zip(w["sent"], w["results"]) if r is not None]
    resident = stats.n_loaded + stats.n_jit_loaded
    counters = {
        "queries": len(done),
        "rows_scanned": sum(r.rows_scanned for _, r in done),
        "rows_skipped": sum(r.rows_skipped for _, r in done),
        "rows_on_device": sum(r.rows_on_device for _, r in done),
        "rows_on_host": sum(r.rows_on_host for _, r in done),
        "resident_rows": resident,
        # least bytes of the launches: queries the device plane answered
        "scan_least_bytes": sum(
            work.scan_bytes(resident, len({st.pool[i][1] for i in q}))
            for q, r in done if r.rows_on_device),
    }

    # -- the reference: every answer to a seeded sample of the queries --
    ref = reference.Reference(st.table.cols, st.table.nested)
    rng = np.random.default_rng([seed, 1])
    asked = sorted({q for q, _ in done})
    pick = rng.choice(len(asked), min(tr["check_queries"], len(asked)),
                      replace=False)
    want = {asked[k]: ref.count([st.pool[i] for i in asked[k]])
            for k in pick}
    compared = [(want[q], r.count) for q, r in done if q in want]
    C = cfg["chunk_records"]
    last = slice(ref.n - C, ref.n)        # the last acknowledged chunk
    checks = {
        "rows_unaccounted": (abs(ref.n - stats.n_records), 0),
        "answers_wrong": (sum(a != b for a, b in compared), 0),
    }
    lat_ms = w["latency"] * 1e3
    late = w["late"][:len(done)]
    worst = int(np.argmax(lat_ms))
    out = Outcome(
        attempted=len(w["sent"]), failed=len(w["sent"]) - len(done),
        end_to_end={"query_p50_ms": float(np.percentile(lat_ms, 50))},
        counters=counters, checks=checks, memory_peak_bytes=peak,
        notes=st.notes + [
            f"{len(w['sent'])} queries sent at {tr['rate_qps']} q/s, "
            f"{len(done)} answered; generator lateness p50 "
            f"{np.percentile(late, 50) * 1e3:.3f} ms, max "
            f"{late.max() * 1e3:.3f} ms; slowest answer {lat_ms[worst]:.3f} "
            f"ms, due {worst / tr['rate_qps']:.3f} s into the window; "
            f"latency p95 {np.percentile(lat_ms, 95):.3f} ms",
            f"{len(compared)} answers to {len(want)} distinct queries "
            f"compared with the reference",
        ])
    if control:
        # the reference in the program's place with the last acknowledged
        # chunk lost: the configuration's guarantee broken once
        lost = {q: want[q] - int(ref.query_mask(
            [st.pool[i] for i in q])[last].sum()) for q in want}
        out.control = {
            "rows_unaccounted": C,
            "answers_wrong": sum(want[q] != lost[q]
                                 for q, _ in done if q in want),
        }
    return out
