"""Client pushdown kernel: device microseconds per 1,000 records.

The summed device time of the pushdown program's executions in the
traced window (``clause_bitvectors_fused``: the kernel and the layout
transposes jitted with it), over the records those executions covered.
"""
PROGRAM = "clause_bitvectors_fused"


def read(r):
    if r.trace is None:
        return None
    runs, secs = r.trace.program(PROGRAM)
    if not runs:
        return None
    return secs * 1e6 / (runs * r.counters["chunk_records"] / 1000)
