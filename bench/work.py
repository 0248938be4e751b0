"""The least work a kernel's call needs, counted on its inputs.

Counted on what the algorithm must touch, not on what one
implementation of it reads, so that a faster rewrite of a kernel is not
credited with less work.

* Client pushdown, per chunk: every raw JSON byte of its records (not
  the padded chunk) read once, and the packed bitvectors written once:
  one 32-bit word per 32 records for each pushed clause and for the
  load mask, plus one 32-bit count per clause.
* Device scan, per query: for every row resident on the device plane
  (rows loaded at ingest plus rows promoted from the raw remainder),
  one dictionary code of :data:`SCAN_BYTES_PER_ROW_KEY` bytes for each
  distinct key the query references; the least any lowered predicate
  kind reads to decide a row.

Neither has arithmetic worth counting against the chip's compute peak,
so both are bound by memory bandwidth.
"""
from __future__ import annotations

#: bytes of one int32 dictionary code: the least a row's key is read as
SCAN_BYTES_PER_ROW_KEY = 4


def client_bytes(raw_bytes: int, n_records: int, n_clauses: int) -> int:
    words = -(-n_records // 32)
    return raw_bytes + 4 * (words * (n_clauses + 1) + n_clauses)


def scan_bytes(resident_rows: int, distinct_keys: int) -> int:
    return resident_rows * distinct_keys * SCAN_BYTES_PER_ROW_KEY
