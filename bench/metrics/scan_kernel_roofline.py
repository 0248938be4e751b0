"""Scan kernel: least time over device time, in percent.

Least time is the least bytes of the window's launches, which the
driver counts with ``bench.work.scan_bytes`` (for each query that
reached the device,
every resident row read once per distinct key the query references)
over the chip's HBM bandwidth; the kernel's arithmetic is a few integer
compares a row, so bytes bound it.
"""
PROGRAM = "_scan_core_pallas"


def read(r):
    if r.trace is None:
        return None
    runs, secs = r.trace.program(PROGRAM)
    least = r.counters.get("scan_least_bytes", 0)
    if not runs or secs <= 0 or not least:
        return None
    return 100.0 * least / r.peaks["hbm_bytes_per_s"] / secs
