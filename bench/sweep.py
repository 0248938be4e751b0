"""Find the knee of an open-loop scan cell: the highest rate it sustains.

    python bench/sweep.py --workload <cell> --seed <n> --seconds 10 \
        --fractions 0.6,0.8,1.0,1.2

One set-up, then: the closed-loop service time of the mix (every query
of the workload once, back to back), whose inverse estimates the knee,
and one open-loop window at each given fraction of that estimate.  Each
window prints one JSON line with its latency quartiles, the share of
queries answered, and how late the generator ran at the end of the
window against its start: a backlog that grows through the window means
the rate is above the knee.  The cell's ``rate_qps`` is set once,
from such a sweep, to about four fifths of the knee.
"""
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    import argparse
    import json

    import numpy as np

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fractions", required=True)
    args = ap.parse_args(argv)

    import jax
    from bench import harness
    from bench.drivers import scan
    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 1
    harness.compile_cache()
    compiles = harness.Compiles()
    st = scan.setup(cell, args.seed)
    print("\n".join(st.notes), flush=True)
    t = time.perf_counter()
    for q in st.queries:
        st.serve.query(st.program[q], mode="device")
    service = (time.perf_counter() - t) / len(st.queries)
    print(json.dumps({"closed_loop_ms_per_query": service * 1e3,
                      "knee_estimate_qps": 1.0 / service}), flush=True)
    for f in (float(x) for x in args.fractions.split(",")):
        rate = f / service
        w = harness.Window(compiles, None)
        out = scan.serve_window(st, seed=args.seed, rate=rate,
                                seconds=args.seconds, window=w,
                                late_limit_s=30.0)
        lat = out["latency"] * 1e3
        late = out["late"] * 1e3
        q = max(len(late) // 4, 1)
        print(json.dumps({
            "rate_qps": rate, "sent": len(lat),
            "answered": sum(r is not None for r in out["results"]),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "late_first_quarter_ms": float(late[:q].mean()),
            "late_last_quarter_ms": float(late[-q:].mean()),
            "compiles": w.n_compiles}), flush=True)
    st.serve.close()
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    sys.exit(main())
