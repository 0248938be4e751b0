"""Closed-loop client ingest through the serving engine, no queries.

Set-up draws a pool of ``pool_chunks`` chunks of records from the seed,
builds the pushdown plan for the traffic's query workload, and warms the
client kernel on every chunk stride the pool yields.  In the window
``client_threads`` clients each loop: take the next pool chunk (cycled,
so the stream never runs dry), encode it, run the pushdown kernel on it
and submit it with its bitvectors to ``CiaoServeEngine.ingest_chunk``
(backpressure ``block``) into a store that starts empty.

``ingest_rows_per_s`` counts the rows a snapshot can read at the
window's close (chunks the engine's writers have applied) over the
window's seconds.  After the window every submitted chunk is drained
(``quiesce``) and checked against the reference: rows readable, rows
loaded at ingest (the client bitvectors' load mask), per-clause match
counts (the bitvectors themselves), and the exact answers of a sample of
the workload's queries, drawn from the seed.
"""
from __future__ import annotations

import itertools
import threading
import time

import numpy as np

from bench import reference, sut, workload
from bench.harness import Outcome, memory_peak_bytes


def _stride(records, align: int = 128) -> int:
    return -(-max(len(r) for r in records) // align) * align


def run(cell, *, seed: int, seconds: float, window,
        client_backend: str = "pallas", scan_backend: str = "pallas",
        control: bool = False) -> Outcome:
    import jax
    from repro.core.client import encode_chunk
    from repro.core.server import CiaoStore
    from repro.kernels.engine import KernelEngine
    from repro.serve.store_engine import CiaoServeEngine

    cfg, tr = cell.config, cell.traffic
    ds = sut.dataset(cfg["dataset"])
    pool = ds.pool()
    queries = workload.generate(len(pool), **tr["workload"])
    C = cfg["chunk_records"]
    t = time.perf_counter()
    table = ds.generate(tr["pool_chunks"] * C, seed)
    records = table.json()
    chunks = [records[i:i + C] for i in range(0, len(records), C)]
    t_gen = time.perf_counter() - t
    plan = sut.build_plan(pool, queries, records[:cfg["plan_sample_records"]],
                          cfg["budget_us_per_record"])
    clauses = tuple(plan.clauses)
    t = time.perf_counter()

    # warm every chunk stride the stream yields, in every client's engine
    n_clients = cfg["client_threads"]
    engines = [KernelEngine(client_backend, r_blk=cfg["client_r_blk"])
               for _ in range(n_clients)]
    firsts = {}
    for recs in chunks:
        firsts.setdefault(_stride(recs), recs)
    for recs in firsts.values():
        ch = encode_chunk(recs)
        for eng in engines:
            bv = eng.eval_fused(ch, clauses)
    CiaoStore(plan).ingest_chunk(ch, bv)     # warm the host ingest path
    t_warm = time.perf_counter() - t

    store = CiaoStore(plan)
    serve = CiaoServeEngine(store, device_backend=scan_backend,
                            backpressure=tr["backpressure"])
    span = jax.profiler.TraceAnnotation
    next_chunk = itertools.count()
    acked = np.zeros(len(chunks), np.int64)
    tally = {"submitted": 0, "failed": 0, "last": -1}
    lock = threading.Lock()
    stop = threading.Event()
    errors: list[BaseException] = []

    def client(eng) -> None:
        while not stop.is_set():
            with lock:
                i = next(next_chunk) % len(chunks)
                tally["submitted"] += 1
            try:
                with span("bench.encode"):
                    ch = encode_chunk(chunks[i])
                with span("bench.client_kernel"):
                    bv = eng.eval_fused(ch, clauses)
                with span("bench.ingest_chunk"):
                    serve.ingest_chunk(ch, bv)
            except BaseException as e:       # reported as a failed submit
                with lock:
                    tally["failed"] += 1
                errors.append(e)
                return
            with lock:
                acked[i] += 1
                tally["last"] = i

    threads = [threading.Thread(target=client, args=(e,), daemon=True)
               for e in engines]
    t0 = window.open()
    for t in threads:
        t.start()
    time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    applied = serve.drained
    load_s, blocked_s = store.stats.load_time_s, serve.blocked_s
    window.close()
    stop.set()
    for t in threads:
        t.join()
    serve.quiesce()
    peak = memory_peak_bytes(cell.chips)
    snap = serve.snapshot()

    # -- the reference, over the pool, times how often each chunk went in --
    pushed = sut.pushed_pool_indices(plan, pool)
    bits = reference.raw_bits(records, [pool[i] for i in pushed])
    per_chunk = lambda m: m.reshape(len(chunks), C).sum(axis=1)
    loaded = per_chunk(bits.any(axis=0))
    clause_hits = np.stack([per_chunk(b) for b in bits]) if len(bits) \
        else np.zeros((0, len(chunks)), np.int64)
    ref = reference.Reference(table.cols, table.nested)
    rng = np.random.default_rng([seed, 1])
    distinct = sorted(set(queries))
    pick = rng.choice(len(distinct), min(tr["check_queries"], len(distinct)),
                      replace=False)
    sample = [distinct[k] for k in sorted(pick)]
    answers = np.stack(
        [per_chunk(ref.query_mask([pool[i] for i in q])) for q in sample])
    got = [serve.query(q, mode="host").count
           for q in sut.program_queries(pool, sample)]
    serve.close()

    def compare(rows, n_loaded, clause_counts, counts) -> dict[str, int]:
        """Each number compared: how far these readings are from the
        reference's for the acknowledged chunks."""
        return {
            "rows_unaccounted": abs(int(acked.sum()) * C - rows),
            "loaded_rows_off": abs(int(loaded @ acked) - n_loaded),
            "clause_counts_off": int(np.abs(
                clause_hits @ acked - clause_counts).sum()),
            "answers_wrong": int(np.sum(answers @ acked != counts)),
        }

    checks = {k: (v, 0) for k, v in compare(
        snap.stats.n_records, snap.stats.n_loaded,
        store.clause_counts[:len(pushed)], got).items()}
    out = Outcome(
        attempted=tally["submitted"], failed=tally["failed"],
        end_to_end={"ingest_rows_per_s": applied * C / seconds},
        counters={
            "window_s": seconds, "rows": applied * C,
            "load_time_s": load_s, "blocked_s": blocked_s,
            "client_threads": n_clients, "chunk_records": C,
            "chunk_raw_bytes": len(b"".join(records)) / len(chunks),
            "pushed_clauses": len(pushed),
        },
        checks=checks, memory_peak_bytes=peak,
        notes=[
            f"plan: {len(pushed)} pushed clauses {[pool[i] for i in pushed]}",
            f"loading ratio {snap.stats.loading_ratio:.4f} "
            f"({snap.stats.n_loaded} of {snap.stats.n_records} rows)",
            f"chunk strides {sorted(firsts)}; {tally['submitted']} submits, "
            f"{int(acked.sum())} acknowledged, {applied} applied in the "
            f"window; {len(sample)} queries checked",
            f"set-up: generation {t_gen:.3f} s; warm-up {t_warm:.3f} s",
        ] + [f"client error: {e!r}" for e in errors[:3]],
    )
    if control:
        # the reference in the program's place, with the last acknowledged
        # chunk lost: the configuration's guarantee broken once
        lost = acked.copy()
        lost[tally["last"]] -= 1
        out.control = compare(int(lost.sum()) * C, int(loaded @ lost),
                              clause_hits @ lost, answers @ lost)
    return out
