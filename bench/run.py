"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip and starts no other that touches JAX.  It
generates the cell's data from ``--seed``, sets up, warms the cell's own
shapes, measures for ``--seconds``, checks every answer it compares
against the plain reference, and prints as its last line of standard
output one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each number compared with its limit, also the last lines of
standard error).  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` profiles the window and reports its per-layer metrics.
Without as many TPU chips as the cell asks for it exits 1 and prints no
result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    # libtpu would otherwise keep its logs in a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    from bench.harness import main
    sys.exit(main(t_start=T_START))
