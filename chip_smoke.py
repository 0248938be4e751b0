"""Drive CIAO's served main path once on the chip and check its answers.

    python chip_smoke.py              # one TPU: the whole main path
    python chip_smoke.py --chips 4    # four TPUs: the sharded device plane only

One process, no child processes.  Phases:

  1. generate ``--records`` YCSB-fakeit records (about 300 B each) from
     ``--seed``;
  2. plan: budgeted predicate selection over Table III workload A
     (Zipf 1.5) at 1.0 us/record, as ``examples/quickstart.py`` does;
  3. clients: every 4096-record chunk through the Pallas pushdown kernel
     (``KernelEngine("pallas")``); the bitvectors must equal
     ``NumpyEngine``'s on every 16th chunk, and on the first chunk for
     the panel's clauses too (every predicate kind);
  4. ingest through ``CiaoServeEngine``, ``quiesce()``, no writer error;
  5. an 8-query panel (one query per predicate kind of the YCSB pool,
     then the first distinct workload-A queries) answered with
     ``mode="device"`` by the Pallas scan kernel over the
     device-resident plane.  Counts must be bit-identical to the ``xla``
     device backend, to ``mode="host"`` and to ``matches_exact`` over
     every record, and the device plane must have answered rows.

``--chips 4`` runs only the sharded phase: the same data in a 4-shard
``ShardedCiaoStore`` behind the engine, each shard's plane on its own
chip and one SPMD launch per query, compared with sequential per-shard
launches, ``mode="host"`` and the oracle.

Without a TPU it exits nonzero and prints no result.  Earlier lines
report phase wall times, compile times, plane bytes, uploads and counts;
the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

N_RECORDS = 1 << 20
CHUNK = 4096
PANEL = 8
CHECK_EVERY = 16


class SmokeFailure(RuntimeError):
    """A phase produced a wrong answer."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _log(msg: str) -> None:
    print(msg, flush=True)


def _timed(name: str, fn):
    t0 = time.perf_counter()
    out = fn()
    _log(f"[phase] {name}: {time.perf_counter() - t0:.3f} s")
    return out


def plan_and_panel(records: list[bytes]):
    """Workload A (Zipf 1.5) plan at 1.0 us/record + the query panel.

    The panel opens with one single-clause query per predicate kind of
    the YCSB pool (Table II), so every term kind the scan kernel lowers
    runs on the chip, then fills up with the first distinct workload-A
    queries."""
    import numpy as np

    from repro.core.planner import build_plan
    from repro.core.predicates import Query
    from repro.core.workload import generate_workload
    from repro.data.datasets import predicate_pool

    pool = predicate_pool("ycsb")
    workload = generate_workload(
        pool, n_queries=200, distribution="zipf",
        zipf_a=1.5, rng=np.random.default_rng(0), name="A")
    report = build_plan(workload, records[:500], budget_us=1.0)
    panel: list = []
    for c in pool:
        if all(c.terms[0].kind != q.clauses[0].terms[0].kind for q in panel):
            panel.append(Query((c,)))
    for q in workload.queries:
        if q not in panel:
            panel.append(q)
        if len(panel) == PANEL:
            break
    return report.plan, panel


def run(records: list[bytes], *, n_shards: int = 1,
        client_backend: str = "pallas", scan_backend: str = "pallas",
        chunk: int = CHUNK, check_every: int = CHECK_EVERY) -> dict:
    """Phases 2-5 over ``records``; raises :class:`SmokeFailure` on any
    wrong answer and returns the printed summary.

    One store (``n_shards == 1``): the device panel is compared with the
    ``xla`` backend.  A sharded store: with the same backend launched
    sequentially per shard (``spmd=False``).
    """
    import numpy as np

    from repro.core.client import NumpyEngine, encode_chunk
    from repro.core.device_scan import DeviceScanner, ShardedDeviceScanner
    from repro.core.server import CiaoStore
    from repro.core.shard import (
        ShardedCiaoStore, ShardRouter, choose_routing_key,
    )
    from repro.kernels.engine import KernelEngine
    from repro.serve.store_engine import CiaoServeEngine

    plan, panel = _timed("plan", lambda: plan_and_panel(records))
    kinds = sorted({t.kind.name for q in panel for c in q.clauses
                    for t in c.terms})
    _log(f"plan: {len(plan.clauses)} pushed clauses; panel of {len(panel)} "
         f"queries, term kinds {kinds}")

    chunks = _timed("encode chunks", lambda: [
        encode_chunk(records[i:i + chunk])
        for i in range(0, len(records), chunk)])

    # -- clients: the pushdown kernel on every chunk ------------------------
    engine = KernelEngine(client_backend)
    t0 = time.perf_counter()
    bitvecs = [engine.eval_fused(chunks[0], plan.clauses)]
    _log(f"client kernel first chunk (compile + run): "
         f"{time.perf_counter() - t0:.3f} s")
    bitvecs += _timed(f"client kernel ({client_backend}), "
                      f"{len(chunks) - 1} more chunks", lambda: [
                          engine.eval_fused(c, plan.clauses)
                          for c in chunks[1:]])
    ref = NumpyEngine()

    def same(got, want) -> bool:
        return (np.array_equal(got.words, want.words)
                and np.array_equal(got.or_words, want.or_words)
                and np.array_equal(got.counts, want.counts))

    checked = list(range(0, len(chunks), check_every))
    for i in checked:
        _check(same(bitvecs[i], ref.eval_fused(chunks[i], plan.clauses)),
               f"chunk {i}: {client_backend} client bitvectors differ "
               f"from NumpyEngine")
    _log(f"client bitvectors equal NumpyEngine on {len(checked)} chunks")
    # the plan may push one predicate kind only: the panel's clauses
    # (every kind of the pool) go through the kernel on one chunk too
    probe = tuple(c for q in panel for c in q.clauses)
    _check(same(engine.eval_fused(chunks[0], probe),
                ref.eval_fused(chunks[0], probe)),
           f"{client_backend} client bitvectors differ from NumpyEngine "
           f"on the panel's {len(probe)} clauses")
    _log(f"client bitvectors equal NumpyEngine on the panel's "
         f"{len(probe)} clauses")

    # -- ingest through the serving engine ------------------------------------
    if n_shards == 1:
        store = CiaoStore(plan)
    else:
        store = ShardedCiaoStore(plan, router=ShardRouter(
            n_shards=n_shards, key=choose_routing_key(plan)))
    with CiaoServeEngine(store, device_backend=scan_backend) as serve:
        def ingest():
            for c, bv in zip(chunks, bitvecs):
                serve.ingest_chunk(c, bv)
            serve.quiesce()           # re-raises a deferred writer error
        _timed(f"ingest {len(records)} records + quiesce", ingest)
        report = serve.stats_report()
        _check(report["engine"]["errors"] == 0, "writer errors recorded")
        _log(f"loaded {store.stats.n_loaded} of {store.stats.n_records} "
             f"records (loading ratio {store.stats.loading_ratio:.4f})")

        # -- the panel on the device plane ------------------------------------
        t0 = time.perf_counter()
        device = [serve.query(panel[0], mode="device")]
        _log(f"device scan first query (sync + compile + run): "
             f"{time.perf_counter() - t0:.3f} s")
        device += _timed(f"device scan ({scan_backend}), "
                         f"{len(panel) - 1} more queries", lambda: [
                             serve.query(q, mode="device")
                             for q in panel[1:]])
        again = _timed(f"device scan ({scan_backend}), panel again", lambda: [
            serve.query(q, mode="device").count for q in panel])
        plane = serve.stats_report()["device_plane"]
        host = _timed("host scan panel", lambda: [
            serve.query(q, mode="host").count for q in panel])
        snap = serve.snapshot()
        if n_shards == 1:
            other, other_name = DeviceScanner(
                snap, backend="xla", log_queries=False), "xla backend"
        else:
            other, other_name = ShardedDeviceScanner(
                snap, backend=scan_backend, log_queries=False,
                spmd=False), "sequential per-shard launches"
        second = _timed(f"device scan panel, {other_name}", lambda: [
            r.count for r in other.scan_batch(panel)])

    objs = _timed("parse records for the oracle",
                  lambda: [json.loads(r) for r in records])
    oracle = _timed("matches_exact oracle", lambda: [
        sum(1 for o in objs if q.matches_exact(o)) for q in panel])

    counts = [r.count for r in device]
    on_device = sum(r.rows_on_device for r in device)
    on_host = sum(r.rows_on_host for r in device)
    _log(f"panel counts: device {counts}")
    _log(f"rows answered: device plane {on_device}, host fallback {on_host}")
    for name, other_counts in (("device again", again),
                               (other_name, second),
                               ("mode=host", host),
                               ("matches_exact", oracle)):
        _check(counts == other_counts,
               f"panel counts differ: device {counts} vs {name} "
               f"{other_counts}")
        _log(f"panel counts equal {name}")
    _check(on_device > 0, "the device plane answered no rows")
    shards = plane["shards"] if "shards" in plane else [plane]
    for i, s in enumerate(shards):
        _log(f"plane[{i}]: device {s['device']}, {s['slots']} slots, "
             f"{s['plane_bytes']} bytes, {s['uploads']} uploads "
             f"({s['upload_bytes']} bytes), {s['evictions']} evictions; "
             f"{s['launches']} launches, {s['param_transfers']} parameter "
             f"transfers ({s['param_bytes']} bytes), {s['fetches']} fetches")
    if n_shards == 1:
        _check(plane["launches"] > 0
               and plane["param_transfers"] == plane["launches"]
               and plane["fetches"] == plane["launches"],
               "a launch made more than one parameter transfer or fetch")
    if n_shards > 1:
        homes = {s["device"] for s in shards}
        _check(len(homes) == n_shards,
               f"shard planes share devices: {sorted(homes)}")
        _check(plane["spmd"], "the SPMD launch was not used")
    return {"counts": counts, "rows_on_device": on_device,
            "rows_on_host": on_host, "plane": plane}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--records", type=int, default=N_RECORDS)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded 4-chip phase")
    args = ap.parse_args(argv)

    import jax

    compiles: list[float] = []

    def on_duration(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {len(devices)} "
              f"{devices[0].platform} device(s))", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.data.datasets import generate_records

    _log(f"devices: {len(devices)} x {devices[0].device_kind}; "
         f"compile cache {enable_compile_cache()}")
    if args.records != N_RECORDS:
        _log(f"size cut: {args.records} records instead of {N_RECORDS}")
    records = _timed(f"generate {args.records} ycsb records", lambda:
                     generate_records("ycsb", args.records, seed=args.seed))
    _log(f"raw JSON: {sum(len(r) for r in records)} bytes")
    run(records, n_shards=4 if args.chips == 4 else 1)
    _log(f"compile time: {sum(compiles):.3f} s over {len(compiles)} "
         f"backend compiles (persistent-cache hits not included)")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
