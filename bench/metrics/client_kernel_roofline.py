"""Client pushdown kernel: least time over device time, in percent.

Least time is the least bytes of each chunk (``bench.work.client_bytes``:
its records' raw JSON bytes read once, the packed bitvectors written
once) over the chip's HBM bandwidth; the kernel does no arithmetic worth
counting against a compute peak, so bytes bound it.  The pool is cycled
chunk by chunk, so the mean raw bytes of a pool chunk stand for the
chunks of the window.
"""
from bench.work import client_bytes

PROGRAM = "clause_bitvectors_fused"


def read(r):
    if r.trace is None:
        return None
    runs, secs = r.trace.program(PROGRAM)
    if not runs or secs <= 0:
        return None
    c = r.counters
    least = runs * client_bytes(int(c["chunk_raw_bytes"]),
                                int(c["chunk_records"]),
                                int(c["pushed_clauses"]))
    return 100.0 * least / r.peaks["hbm_bytes_per_s"] / secs
