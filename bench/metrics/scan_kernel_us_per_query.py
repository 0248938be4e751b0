"""Scan kernel: device microseconds per query answered in the window.

The summed device time of the scan program's executions in the traced
window (``_scan_core_pallas``: the substring-probe gathers and the one
``pallas_call``, jitted together), over the queries answered in it.
"""
PROGRAM = "_scan_core_pallas"


def read(r):
    if r.trace is None or not r.counters.get("queries"):
        return None
    runs, secs = r.trace.program(PROGRAM)
    if not runs:
        return None
    return secs * 1e6 / r.counters["queries"]
