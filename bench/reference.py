"""The plain reference the benchmark's answers are compared with.

Independent of the program: it imports nothing of it and reads only the
generated columns and JSON bytes.  Two semantics, both as CIAO (§IV)
defines them for its four predicate kinds:

* exact, on parsed values (what a COUNT(*) query must return):
  ``presence`` — the key is there and not null; ``exact`` — equal
  string; ``substring`` — the value is a string that contains the
  operand; ``key_value`` — equal value, where a boolean matches only a
  boolean and otherwise two scalars are equal when their JSON texts
  are.  A boolean operand never matches a non-boolean value, for any
  kind but presence, and a nested value (an object or an array) matches
  only presence.
* raw, on JSON bytes (what a client's pushdown bitvector must hold):
  the operand's pattern occurs in the record, and for ``key_value`` the
  value pattern occurs after an occurrence of ``"key"`` and before the
  next ``,`` or ``}`` (anywhere after it, when the value pattern itself
  holds one of those).

A clause is one ``(kind, key, value)`` triple; a query is a tuple of
clauses, all of which must hold.
"""
from __future__ import annotations

import json
from typing import Sequence

import numpy as np


def json_text(v) -> str:
    """The JSON text of a scalar, as the records render it."""
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, float)):
        return json.dumps(v)
    if isinstance(v, str):
        return v
    raise TypeError(f"no JSON scalar text for {v!r}")


def _exact_term(col: np.ndarray, kind: str, value) -> np.ndarray:
    col_bool = col.dtype == bool
    col_int = col.dtype.kind in "iu"
    col_str = col.dtype.kind == "S"
    if isinstance(value, bool) != col_bool:
        return np.zeros(len(col), bool)
    if kind == "exact":
        if col_str != isinstance(value, str):
            return np.zeros(len(col), bool)
        return col == (value.encode() if col_str else value)
    if kind == "substring":
        if not col_str:
            return np.zeros(len(col), bool)
        return np.strings.find(col, str(value).encode()) >= 0
    if kind == "key_value":
        if col_bool or (col_int and isinstance(value, int)):
            return col == value
        text = json_text(value).encode()
        if col_int:                      # int column, text operand
            return col.astype("S") == text
        return col == text               # text column
    raise ValueError(f"unknown predicate kind {kind!r}")


class Reference:
    """Exact COUNT(*) answers over one generated table."""

    def __init__(self, cols: dict[str, np.ndarray],
                 nested: frozenset[str] = frozenset()):
        self.cols = cols
        self.nested = nested
        self.n = len(next(iter(cols.values())))
        self._masks: dict[tuple, np.ndarray] = {}

    def clause_mask(self, clause: tuple) -> np.ndarray:
        kind, key, value = clause
        memo = (kind, key, type(value), value)   # 1 == True, but not here
        m = self._masks.get(memo)
        if m is None:
            col = self.cols.get(key)
            if col is None:
                m = np.zeros(self.n, bool)
            elif kind == "presence":
                m = np.ones(self.n, bool)     # generated values are never null
            elif key in self.nested:
                m = np.zeros(self.n, bool)
            else:
                m = _exact_term(col, kind, value)
            self._masks[memo] = m
        return m

    def query_mask(self, query: Sequence[tuple]) -> np.ndarray:
        m = np.ones(self.n, bool)
        for c in query:
            m &= self.clause_mask(c)
        return m

    def count(self, query: Sequence[tuple]) -> int:
        return int(self.query_mask(query).sum())


def raw_patterns(clause: tuple) -> tuple[bytes, ...]:
    kind, key, value = clause
    if kind == "exact":
        return (f'"{value}"'.encode(),)
    if kind == "substring":
        return (str(value).encode(),)
    if kind == "presence":
        return (f'"{key}"'.encode(),)
    if kind == "key_value":
        return (f'"{key}"'.encode(), json_text(value).encode())
    raise ValueError(f"unknown predicate kind {kind!r}")


def raw_match(record: bytes, clause: tuple) -> bool:
    """Does a client's pattern search over ``record`` find ``clause``?"""
    pats = raw_patterns(clause)
    if len(pats) == 1:
        return pats[0] in record
    key, val = pats
    unbounded = b"," in val or b"}" in val
    at = record.find(key)
    while at != -1:
        lo = at + len(key)
        hi = len(record)
        if not unbounded:
            ends = [e for e in (record.find(b",", lo), record.find(b"}", lo))
                    if e != -1]
            hi = min(ends) if ends else hi
        if record.find(val, lo, hi) != -1:
            return True
        at = record.find(key, at + 1)
    return False


def raw_bits(records: Sequence[bytes], clauses: Sequence[tuple]
             ) -> np.ndarray:
    """``bool[C, R]``: clause ``c`` found in record ``r`` by pattern search."""
    return np.array([[raw_match(r, c) for r in records] for c in clauses],
                    dtype=bool).reshape(len(clauses), len(records))
