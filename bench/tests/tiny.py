"""Cells cut to a size the CPU test run can hold, and how to run them.

The cut is made here, on the loaded cell, not through an option of the
benchmark: a few thousand records, 1,024-record chunks, two clients and
a 40-query mix; the Pallas kernels run in interpret mode.
"""
from __future__ import annotations

import time

from bench import harness

BACKENDS = {"client_backend": "pallas_interpret",
            "scan_backend": "pallas_interpret"}


def cell(name: str, root=harness.ROOT) -> harness.Cell:
    c = harness.load_cell(name, root)
    c.config = dict(c.config, store_records=4096, chunk_records=1024,
                    client_threads=2)
    c.traffic = dict(c.traffic, pool_chunks=3, rate_qps=20.0,
                     workload=dict(c.traffic["workload"], n_queries=40))
    return c


def measure(c: harness.Cell, *, seed: int = 2**31 + 11, trace=False,
            seconds: float = 1.0) -> dict:
    return harness.measure(c, seed=seed, seconds=seconds, trace=trace,
                           t_start=time.perf_counter(),
                           device_kind="TPU v5 lite", **BACKENDS)


def run(c: harness.Cell, *, seed: int = 2**31 + 11, control=False,
        seconds: float = 1.0) -> harness.Outcome:
    window = harness.Window(harness.Compiles(), None)
    return harness.driver(c).run(c, seed=seed, seconds=seconds,
                                 window=window, control=control, **BACKENDS)
