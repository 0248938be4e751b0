"""Device-resident scan plane vs the host columnar scanner (DESIGN.md §15).

Measures the tentpole replacement: the host ``DataSkippingScanner``
walks segments one at a time (zone-prune, bitvector AND, vectorized
residual per segment, per query), while :class:`DeviceScanner` keeps
every hot segment resident as device arrays and evaluates the WHOLE
query batch against the WHOLE plane in one fused launch.

Setup reuses ``bench_scan``'s mixed-epoch / mixed-tier ycsb store and
its selective workload (pushed clauses from both epochs, pushed+residual
conjunctions, residual-only clauses, point lookups, no-match probes), so
the two artifacts describe the same population.

The gated ``numpy`` baseline is ``scan_core_numpy`` — the SAME
multi-query plane scan, numpy-vectorized with one temporary per stage,
driven through the same scanner pipeline (``DeviceScanner`` with
``backend="numpy"``, plane pre-mirrored to host) — so the speedup
isolates what the fused single launch buys on identical work, exactly
like ``bench_kernels``' numpy-vectorized vs xla-jit rows.  The host
``DataSkippingScanner`` is the CORRECTNESS oracle and is reported
untimed-gated as ``host_skipping`` context: on this selective workload
its zone-map + pushed-bitvector skipping does far less work per query
than any dense plane pass, and the artifact says so rather than hiding
it.

Claim gates (enforced by ``bench_schema.validate_device``):

  * counts bit-identical to sequential host scans (plus full
    rows_scanned / rows_skipped accounting equality — checked here),
    for BOTH the device backend and the numpy reference;
  * ZERO steady-state host->device uploads: after the warm pass the
    plane is resident and scans move only (Q, S) parameter tables;
  * fused batched device scan >= 2x the numpy-vectorized reference;
  * a batch of 8 queries >= 3x over the same 8 queries launched
    sequentially (the multi-query fusion claim);
  * roofline fraction from the analytic flops model
    (``analysis.flops.scan_estimate`` over the EXACT launch shape) vs
    the measured launch: ``v5e_bound_s / measured_launch_s``.

    PYTHONPATH=src python -m benchmarks.bench_device
"""
from __future__ import annotations

import json
import time

import numpy as np

from benchmarks.bench_scan import _best_of, _build_store, _workload
from repro.analysis.flops import scan_estimate
from repro.analysis.roofline import V5E, Roofline
from repro.core.device_scan import DeviceScanner
from repro.core.server import DataSkippingScanner
from repro.kernels.scan_fused import scan_counts


def _accounting(r) -> tuple:
    return (r.count, r.rows_scanned, r.rows_skipped, r.raw_parsed,
            r.segments_pruned,
            tuple(sorted((k, (g.count, g.rows_scanned, g.rows_skipped))
                         for k, g in r.groups.items())))


def run(n_records: int = 24576, chunk_records: int = 512,
        segment_capacity: int = 8192, repeats: int = 3,
        backend: str = "xla", quick: bool | None = None) -> dict:
    import jax

    quick = (n_records <= 8192) if quick is None else quick
    store, fam0, fam1, ranked, recs = _build_store(
        n_records, chunk_records, segment_capacity)
    rng = np.random.default_rng(5)
    queries = _workload(fam0, fam1, ranked, recs, rng)

    host = DataSkippingScanner(store, log_queries=False)
    dev = DeviceScanner(store, backend=backend, log_queries=False)
    npy = DeviceScanner(store, backend="numpy", log_queries=False)

    # warm pass: uploads the plane, compiles the launch.  The store was
    # fully promoted by _build_store, so repeated scans are idempotent
    # and the bit-identical gate can compare steady passes directly.
    dev_results = dev.scan_batch(queries)
    uploads_warm = dev.cache.uploads
    dev_results = dev.scan_batch(queries)
    uploads_steady = dev.cache.uploads - uploads_warm
    npy_results = npy.scan_batch(queries)

    host_results = [host.scan(q) for q in queries]
    counts_match = all(
        _accounting(d) == _accounting(h) == _accounting(n)
        for d, h, n in zip(dev_results, host_results, npy_results))

    host_s = _best_of(lambda: [host.scan(q) for q in queries], repeats)
    numpy_s = _best_of(lambda: npy.scan_batch(queries), repeats)
    device_s = _best_of(lambda: dev.scan_batch(queries), repeats)

    # multi-query fusion: 8 queries in one launch vs 8 single launches.
    # best-of with extra repeats — the two sides are compared against
    # each other, so this ratio is the most noise-sensitive gate
    qs8 = queries[:8]
    dev.scan_batch(qs8)                       # warm the Q=8 shape
    for q in qs8:
        dev.scan_batch([q])                   # warm the Q=1 shape
    reps8 = max(repeats, 5)
    batch8_s = _best_of(lambda: dev.scan_batch(qs8), reps8)
    seq8_s = _best_of(lambda: [dev.scan_batch([q]) for q in qs8], reps8)

    # roofline: analytic flops/bytes of the EXACT steady launch shape,
    # v5e bound vs the measured launch (parameter prep excluded — this
    # is the kernel's fraction, not the host pipeline's)
    prep = dev._prepare(queries)
    p = prep.params
    plane = dev.cache.plane
    assert p is not None and plane is not None
    shape = dict(n_rows=int(plane.sid.shape[0]),
                 n_terms=int(p.kinds.shape[0]),
                 n_clauses=int(p.membership.shape[0]),
                 n_queries=int(p.query_clause.shape[0]),
                 n_slots=int(p.pushed_tbl.shape[1]) - 1)
    est = scan_estimate(**shape)
    scan_counts(plane, p, backend=backend)    # warm this exact shape
    launch_s = _best_of(lambda: scan_counts(plane, p, backend=backend),
                        repeats)
    roof = Roofline(
        arch="tpu-v5e",
        shape="x".join(f"{k[2:]}{v}" for k, v in shape.items()),
        mesh="1x1", device_flops=est.flops_global,
        device_bytes=est.hbm_bytes_global, collective_bytes=0.0,
        model_flops_global=est.flops_global, n_devices=1,
        device_kind=V5E,
    ).finalize()
    roofline_frac = roof.step_time_s / launch_s

    n_queries = len(queries)
    n_segments = len(store.blocks) + len(store.jit_blocks)

    def side(scan_s: float) -> dict:
        return {
            "scan_s": round(scan_s, 6),
            "us_per_query": round(scan_s / n_queries * 1e6, 1),
            "records_per_s": int(n_records * n_queries / scan_s),
        }

    out = {
        "quick": bool(quick),
        "backend": backend,
        "device": jax.devices()[0].platform,
        "interpret": backend == "pallas_interpret",
        "n_records": int(n_records),
        "n_segments": int(n_segments),
        "n_queries": n_queries,
        "n_slots": len(dev.cache.slots),
        "numpy": side(numpy_s),
        "host_skipping": side(host_s),
        "device_batched": side(device_s),
        "device_sequential": side(seq8_s / 8 * n_queries),
        "speedup": round(numpy_s / device_s, 2),
        "batch8_speedup": round(seq8_s / batch8_s, 2),
        "counts_match": bool(counts_match),
        "uploads_steady": int(uploads_steady),
        "upload_bytes_warm": int(dev.cache.upload_bytes),
        "roofline": {
            "device_flops": est.flops_global,
            "device_bytes": est.hbm_bytes_global,
            "compute_s": roof.compute_s,
            "memory_s": roof.memory_s,
            "step_time_s": roof.step_time_s,
            "measured_s": round(launch_s, 6),
            "dominant": roof.dominant,
            "shape": shape,
        },
        "roofline_frac": round(roofline_frac, 6),
    }
    print(f"[device] {n_records} records, {n_segments} segments "
          f"({len(dev.cache.slots)} device-resident), {n_queries} queries, "
          f"backend={backend}")
    print(f"[device] numpy reference{numpy_s * 1e3:9.2f} ms/batch; host "
          f"skipping scanner {host_s * 1e3:.2f} ms/batch (context)")
    print(f"[device] device fused   {device_s * 1e3:9.2f} ms/batch "
          f"(x{out['speedup']}, counts_match={counts_match}, "
          f"steady uploads={uploads_steady})")
    print(f"[device] batch-of-8     {batch8_s * 1e3:9.2f} ms vs sequential "
          f"{seq8_s * 1e3:9.2f} ms (x{out['batch8_speedup']})")
    print(f"[device] launch {launch_s * 1e6:9.1f} us measured; v5e "
          f"{roof.dominant}-bound {roof.step_time_s * 1e6:.1f} us "
          f"-> roofline_frac {roofline_frac:.4f}")
    return out


if __name__ == "__main__":
    import os

    os.makedirs("artifacts", exist_ok=True)
    out = run()
    with open("artifacts/bench_device.json", "w") as f:
        json.dump(out, f, indent=1)
