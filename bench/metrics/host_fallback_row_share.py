"""Device plane: share of device-mode row visits answered by the host
fallback, in percent.

Sum of ``ScanResult.rows_on_host`` over sum of ``rows_on_device`` plus
``rows_on_host``, over every query answered in the window: segments the
device plane does not hold (open builder tails, evicted or ineligible
segments, queries it cannot lower) are scanned on the host.
"""


def read(r):
    c = r.counters
    total = c.get("rows_on_device", 0) + c.get("rows_on_host", 0)
    if not total:
        return None
    return 100.0 * c["rows_on_host"] / total
