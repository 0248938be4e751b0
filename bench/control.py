"""Read every number a cell compares, from the program and from its control.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

The control is the plain reference put in the program's place with one
of the configuration's guarantees broken: the last acknowledged chunk
is lost.  For each seed this runs the cell once (a fresh set-up and a
short window at the cell's own load, on the chip) and prints one JSON
line with the program's readings and the control's.  The program has to
pass every limit and the control has to fail at least one; the limits
in ``PERF.md`` were set from these readings.  The benchmark's own runs
never compute the control.
"""
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    import jax
    from bench import harness
    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: no TPU", file=sys.stderr)
        return 1
    harness.compile_cache()
    compiles = harness.Compiles()
    all_ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = harness.driver(cell).run(
            cell, seed=seed, seconds=args.seconds,
            window=harness.Window(compiles, None), control=True)
        program = {k: v for k, (v, _) in out.checks.items()}
        limits = {k: lim for k, (_, lim) in out.checks.items()}
        sound = all(program[k] <= limits[k] for k in limits)
        caught = any(out.control[k] > limits[k] for k in limits)
        all_ok &= sound and caught
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "program": program, "control": out.control,
                          "limits": limits, "program_passes": sound,
                          "control_fails": caught,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0 if all_ok else 1


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    sys.exit(main())
