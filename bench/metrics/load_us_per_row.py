"""Partial load: host microseconds of ``CiaoStore.ingest_chunk`` per row.

The growth of the store's ``LoadStats.load_time_s`` over the window,
over the rows the writers applied in it.
"""


def read(r):
    rows = r.counters.get("rows", 0)
    if not rows:
        return None
    return r.counters["load_time_s"] * 1e6 / rows
