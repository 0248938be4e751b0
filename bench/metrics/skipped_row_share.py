"""Skipping cascade: share of row visits the window's queries skipped,
in percent.

Sum of ``ScanResult.rows_skipped`` over sum of ``rows_scanned`` plus
``rows_skipped``, over every query answered in the window: rows the
zone maps, the pushed bitvectors and the skip index let a query pass
over, raw remainders skipped whole included.
"""


def read(r):
    c = r.counters
    total = c.get("rows_scanned", 0) + c.get("rows_skipped", 0)
    if not total:
        return None
    return 100.0 * c["rows_skipped"] / total
