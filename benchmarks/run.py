"""Benchmark orchestrator — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows summarizing every benchmark,
and writes the detailed JSON artifacts under artifacts/.

    PYTHONPATH=src python -m benchmarks.run            # full suite
    PYTHONPATH=src python -m benchmarks.run --quick    # reduced sizes
"""
from __future__ import annotations

import argparse
import json
import os


def _row(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.3f},{derived}")


def _write_gated_artifacts(
    out: dict, *, validator, detail_name: str, quick: bool,
    artifacts_dir: str, tracked_path: str,
) -> list[str]:
    """Schema-gated artifact writer shared by every tracked benchmark.

    The schema gate runs FIRST (a malformed artifact is a bug, not data).
    Quick runs only ever write under ``artifacts_dir`` — the tracked
    perf-trajectory file records full-size numbers exclusively, so a CI
    smoke run can never clobber PR-over-PR comparability.
    """
    validator(out)
    detail = os.path.join(artifacts_dir, detail_name)
    with open(detail, "w") as f:
        json.dump(out, f, indent=1)
    written = [detail]
    if not quick:
        with open(tracked_path, "w") as f:
            json.dump(out, f, indent=1)
        written.append(tracked_path)
    return written


def write_kernels_artifacts(
    out: dict, *, quick: bool, artifacts_dir: str = "artifacts",
    tracked_path: str = "BENCH_kernels.json",
) -> list[str]:
    """Write the kernels benchmark JSON; returns the paths written."""
    from .bench_schema import validate_kernels

    return _write_gated_artifacts(
        out, validator=validate_kernels, detail_name="bench_kernels.json",
        quick=quick, artifacts_dir=artifacts_dir, tracked_path=tracked_path)


def write_tiers_artifacts(
    out: dict, *, quick: bool, artifacts_dir: str = "artifacts",
    tracked_path: str = "BENCH_tiers.json",
) -> list[str]:
    """Write the tiered-fleet benchmark JSON; returns the paths written."""
    from .bench_schema import validate_tiers

    return _write_gated_artifacts(
        out, validator=validate_tiers, detail_name="bench_tiers.json",
        quick=quick, artifacts_dir=artifacts_dir, tracked_path=tracked_path)


def write_scan_artifacts(
    out: dict, *, quick: bool, artifacts_dir: str = "artifacts",
    tracked_path: str = "BENCH_scan.json",
) -> list[str]:
    """Write the columnar-scan benchmark JSON; returns the paths written."""
    from .bench_schema import validate_scan

    return _write_gated_artifacts(
        out, validator=validate_scan, detail_name="bench_scan.json",
        quick=quick, artifacts_dir=artifacts_dir, tracked_path=tracked_path)


def write_shard_artifacts(
    out: dict, *, quick: bool, artifacts_dir: str = "artifacts",
    tracked_path: str = "BENCH_shard.json",
) -> list[str]:
    """Write the sharded-store benchmark JSON; returns the paths written."""
    from .bench_schema import validate_shard

    return _write_gated_artifacts(
        out, validator=validate_shard, detail_name="bench_shard.json",
        quick=quick, artifacts_dir=artifacts_dir, tracked_path=tracked_path)


def write_device_artifacts(
    out: dict, *, quick: bool, artifacts_dir: str = "artifacts",
    tracked_path: str = "BENCH_device.json",
) -> list[str]:
    """Write the device-scan benchmark JSON; returns the paths written."""
    from .bench_schema import validate_device

    return _write_gated_artifacts(
        out, validator=validate_device, detail_name="bench_device.json",
        quick=quick, artifacts_dir=artifacts_dir, tracked_path=tracked_path)


def write_batch_artifacts(
    out: dict, *, quick: bool, artifacts_dir: str = "artifacts",
    tracked_path: str = "BENCH_batch.json",
) -> list[str]:
    """Write the multi-query batch benchmark JSON; returns the paths written."""
    from .bench_schema import validate_batch

    return _write_gated_artifacts(
        out, validator=validate_batch, detail_name="bench_batch.json",
        quick=quick, artifacts_dir=artifacts_dir, tracked_path=tracked_path)


def write_serve_artifacts(
    out: dict, *, quick: bool, artifacts_dir: str = "artifacts",
    tracked_path: str = "BENCH_serve.json",
) -> list[str]:
    """Write the async serving plane benchmark JSON; returns the paths
    written."""
    from .bench_schema import validate_serve

    return _write_gated_artifacts(
        out, validator=validate_serve, detail_name="bench_serve.json",
        quick=quick, artifacts_dir=artifacts_dir, tracked_path=tracked_path)


def write_tuner_artifacts(
    out: dict, *, quick: bool, artifacts_dir: str = "artifacts",
    tracked_path: str = "BENCH_tuner.json",
) -> list[str]:
    """Write the physical-design tuner benchmark JSON; returns the paths
    written."""
    from .bench_schema import validate_tuner

    return _write_gated_artifacts(
        out, validator=validate_tuner, detail_name="bench_tuner.json",
        quick=quick, artifacts_dir=artifacts_dir, tracked_path=tracked_path)


def write_skip_artifacts(
    out: dict, *, quick: bool, artifacts_dir: str = "artifacts",
    tracked_path: str = "BENCH_skip.json",
) -> list[str]:
    """Write the skipping-index benchmark JSON; returns the paths written."""
    from .bench_schema import validate_skip

    return _write_gated_artifacts(
        out, validator=validate_skip, detail_name="bench_skip.json",
        quick=quick, artifacts_dir=artifacts_dir, tracked_path=tracked_path)


# suite name -> what it measures (single source for --only and --list)
_SUITES = {
    "e2e": "paper Figs 3-5 end-to-end loading/query/overlap speedups",
    "micro": "paper Figs 6-12 micro-benchmarks + pattern-memo check",
    "cost": "paper Table IV cost-model fit",
    "selection": "CELF predicate selection scaling + quality bound",
    "kernels": "client engine throughput + fused-vs-split launches",
    "replan": "workload-drift replanning vs a static plan",
    "tiers": "tiered fleet allocation vs uniform baselines",
    "scan": "columnar segment scan vs row-at-a-time",
    "shard": "sharded store scaling + partition pruning",
    "device": "device-resident fused scan plane",
    "batch": "multi-query batcher + result cache",
    "serve": "async serving under live ingest",
    "tuner": "online physical-design tuner drift recovery",
    "skip": "skipping-index registry: range/IN/n-gram pruning",
    "roofline": "per-kernel analytic roofline cells",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--only", default=None,
        help="comma list of suites (see --list): "
             + ",".join(_SUITES))
    ap.add_argument("--list", action="store_true",
                    help="list the registered bench suites and exit")
    args = ap.parse_args()
    if args.list:
        for name, what in _SUITES.items():
            print(f"{name:10s} {what}")
        return
    only = set(args.only.split(",")) if args.only else None
    if only is not None:
        unknown = only - set(_SUITES)
        if unknown:
            ap.error(f"unknown suite(s): {','.join(sorted(unknown))} "
                     f"(see --list)")
    os.makedirs("artifacts", exist_ok=True)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    csv_rows: list[tuple[str, float, str]] = []

    if only is None or "e2e" in only:
        from . import bench_end_to_end

        n = 6000 if args.quick else 20000
        rows = bench_end_to_end.run(n_records=n,
                                    n_queries_exec=20 if args.quick else 60)
        with open("artifacts/bench_end_to_end.json", "w") as f:
            json.dump(rows, f, indent=1)
        best = {}
        for r in rows:
            for k in ("loading_speedup", "query_speedup", "e2e_speedup",
                      "e2e_overlapped_speedup"):
                best[k] = max(best.get(k, 0), r[k])
        at1 = [r for r in rows if r["budget_us"] == 1.0]
        csv_rows.append((
            "fig3-5_end_to_end",
            1e6 * sum(r["loading_s"] + r["query_s"] for r in at1) / max(
                sum(1 for _ in at1), 1) / 1000,
            f"best_load_x{best['loading_speedup']};best_query_x{best['query_speedup']};"
            f"best_e2e_x{best['e2e_speedup']};best_e2e_overlap_x{best['e2e_overlapped_speedup']}"
            f";paper=21x/23x/19x",
        ))

    if only is None or "micro" in only:
        from . import bench_micro

        out = bench_micro.main()
        fr = [r["fraction_improved"] for r in out["fig6_query_fraction"]]
        csv_rows.append(("fig6_query_fraction", 0.0,
                         f"improved_{min(fr):.0%}-{max(fr):.0%};paper=37-68%"))
        csv_rows.append(("fig7-12_micro", 0.0,
                         f"selectivity+overlap+skewness recorded"))

    if only is None or "cost" in only:
        from . import bench_cost_model

        rows = bench_cost_model.main(n_records=1500 if args.quick else 3000)
        r2s = ";".join(f"{r['platform']}=R2_{r['r_squared']}" for r in rows)
        csv_rows.append(("tableIV_cost_model", 0.0, r2s + ";paper=0.666-0.978"))

    if only is None or "selection" in only:
        from . import bench_selection

        out = bench_selection.main()
        last = out["scaling"][-1]
        csv_rows.append((
            "selection_celf", last["celf_s"] * 1e6 / max(last["n_preds"], 1),
            f"celf_x{last['speedup']}_at_P{last['n_preds']};"
            f"quality_worst_{out['quality']['worst_ratio']}(>=0.316)",
        ))

    if only is None or "kernels" in only:
        from . import bench_kernels

        out = bench_kernels.main(n_records=1500 if args.quick else 4000)
        for r in out["engines"]:
            csv_rows.append((f"kernel_{r['engine']}", r["us_per_record"],
                             f"{r['records_per_s']}rec/s;{r['effective_GBps']}GBps"))
        for r in out["fused_vs_split"]:
            csv_rows.append((
                f"kernel_fused_{r['backend']}", r["fused_us_per_record"],
                f"split_{r['split_us_per_record']}us;x{r['speedup']};"
                f"launches_{r['launches_split']}->{r['launches_fused']}",
            ))
        write_kernels_artifacts(out, quick=args.quick)

    if only is None or "replan" in only:
        from . import bench_replan
        from .bench_schema import validate_replan

        out = bench_replan.run(
            n_records=4096 if args.quick else 16384,
            queries_per_phase=80 if args.quick else 150,
            n_tail_queries=30 if args.quick else 60,
        )
        validate_replan(out)
        with open("artifacts/bench_replan.json", "w") as f:
            json.dump(out, f, indent=1)
        csv_rows.append((
            "replan_drift", 0.0,
            f"scan_x{out['post_drift_scan_speedup']};"
            f"ratio_{out['adaptive']['eff_loading_ratio']:.2f}vs"
            f"{out['static']['eff_loading_ratio']:.2f};"
            f"epochs_{out['adaptive']['epoch']}",
        ))

    if only is None or "tiers" in only:
        from . import bench_tiers

        out = bench_tiers.run(
            n_records=4864 if args.quick else 13312,
            n_queries=200 if args.quick else 300,
            n_exec_queries=80 if args.quick else 120,
        )
        write_tiers_artifacts(out, quick=args.quick)
        csv_rows.append((
            "tiers_fleet", 0.0,
            f"eff_{out['tiered']['eff_loading_ratio']:.2f}vs"
            f"{out['uniform_min']['eff_loading_ratio']:.2f}/"
            f"{out['uniform_max']['eff_loading_ratio']:.2f};"
            f"e2e_{out['tiered']['end_to_end_s']}vs"
            f"{out['uniform_min']['end_to_end_s']}/"
            f"{out['uniform_max']['end_to_end_s']};"
            f"retiers_{out['tiered']['retier_events']}",
        ))

    if only is None or "scan" in only:
        from . import bench_scan

        out = bench_scan.run(
            n_records=6144 if args.quick else 24576,
            repeats=2 if args.quick else 3,
            quick=args.quick,
        )
        write_scan_artifacts(out, quick=args.quick)
        csv_rows.append((
            "scan_columnar", out["columnar"]["us_per_query"],
            f"row_{out['row_at_a_time']['us_per_query']}us;"
            f"x{out['speedup']};cold_x{out['cold_speedup']};"
            f"pruned_{out['columnar']['segments_pruned']};"
            f"counts_match_{out['counts_match']}",
        ))

    if only is None or "shard" in only:
        from . import bench_shard

        out = bench_shard.run(
            n_records=16384 if args.quick else 65536,
            repeats=2 if args.quick else 3,
            quick=args.quick,
        )
        write_shard_artifacts(out, quick=args.quick)
        at8 = next(r for r in out["runs"] if r["n_shards"] == 8)
        csv_rows.append((
            "shard_store", at8["us_per_query"],
            f"x{out['speedup_4']}@4;x{out['speedup_8']}@8;"
            f"pruned_{out['selective_pruned_fraction']:.0%};"
            f"counts_match_{out['counts_match']}",
        ))

    if only is None or "device" in only:
        from . import bench_device

        out = bench_device.run(
            n_records=6144 if args.quick else 24576,
            repeats=2 if args.quick else 3,
            quick=args.quick,
        )
        write_device_artifacts(out, quick=args.quick)
        csv_rows.append((
            "device_scan", out["device_batched"]["us_per_query"],
            f"x{out['speedup']}_vs_numpy;batch8_x{out['batch8_speedup']};"
            f"uploads_steady_{out['uploads_steady']};"
            f"roofline_frac_{out['roofline_frac']};"
            f"counts_match_{out['counts_match']}",
        ))

    if only is None or "batch" in only:
        from . import bench_batch

        out = bench_batch.run(
            n_records=6144 if args.quick else 24576,
            repeats=2 if args.quick else 3,
            quick=args.quick,
        )
        write_batch_artifacts(out, quick=args.quick)
        csv_rows.append((
            "batch_scan", out["batched"]["us_per_query"],
            f"seq_{out['sequential']['us_per_query']}us;x{out['speedup']};"
            f"cache_x{out['cache_speedup']};"
            f"counts_match_{out['counts_match']}",
        ))

    if only is None or "serve" in only:
        from . import bench_serve

        out = bench_serve.run(
            n_records=6144 if args.quick else 24576,
            segment_capacity=512 if args.quick else 1024,
            quick=args.quick,
        )
        write_serve_artifacts(out, quick=args.quick)
        csv_rows.append((
            "serve_live_p99", out["live"]["p99_us"],
            f"x{out['throughput_speedup']}_vs_serialized;"
            f"p99_ratio_{out['p99_ratio']};"
            f"counts_match_{out['counts_match']}",
        ))

    if only is None or "tuner" in only:
        from . import bench_tuner

        out = bench_tuner.run(
            n_records=8192 if args.quick else 49152,
            segment_capacity=512 if args.quick else 1024,
            quick=args.quick,
        )
        write_tuner_artifacts(out, quick=args.quick)
        csv_rows.append((
            "tuner_drift", out["after"]["us_per_query"],
            f"recovery_x{out['recovery_speedup']}_vs_stale;"
            f"p99_ratio_{out['p99_ratio']};"
            f"rows_moved_{out['migration']['rows_moved']};"
            f"counts_match_{out['counts_match']}",
        ))

    if only is None or "skip" in only:
        from . import bench_skip

        out = bench_skip.run(
            n_records=6144 if args.quick else 24576,
            repeats=2 if args.quick else 3,
            quick=args.quick,
        )
        write_skip_artifacts(out, quick=args.quick)
        csv_rows.append((
            "skip_registry", out["skip"]["us_per_query"],
            f"noskip_{out['noskip']['us_per_query']}us;x{out['speedup']};"
            f"pruned_{out['pruned_fraction']:.0%};"
            f"migration_ok_{out['migration_ok']};"
            f"counts_match_{out['counts_match']}",
        ))

    if only is None or "roofline" in only:
        from . import bench_roofline

        recs = bench_roofline.main()
        if recs:
            ok = [r for r in recs.values() if "roofline" in r]
            csv_rows.append((
                "roofline_cells", 0.0,
                f"{len(ok)}_cells_compiled;"
                f"{sum(1 for r in recs.values() if 'skipped' in r)}_documented_skips",
            ))

    print("\n=== name,us_per_call,derived ===")
    for name, us, derived in csv_rows:
        _row(name, us, derived)


if __name__ == "__main__":
    main()
