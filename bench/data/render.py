"""Column tables and their JSON rendering, shared by the generators.

A dataset generator returns a :class:`Table`: one numpy array per key,
in schema order.  Integer columns are ``int64``, boolean columns
``bool`` and string columns byte strings (``S``) drawn from fixed ASCII
vocabularies that hold no ``"`` and no control character (a ``\\`` is
escaped when rendered).  A key in ``Table.nested`` holds objects or
arrays: its column is their compact JSON text, built with :func:`obj`
and :func:`array`, and is rendered as it stands.  :meth:`Table.json`
renders every row as the compact JSON that
``json.dumps(obj, separators=(",", ":"))`` gives, with one ``%``-format
per row over whole columns instead of one ``json.dumps`` per record.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


_SMALL = np.arange(100_000).astype("S")


def digits(a: np.ndarray, width: int = 0) -> np.ndarray:
    """Decimal text of a non-negative integer array, zero-padded to
    ``width``."""
    a = np.asarray(a, np.int64)
    if a.size and a.max() >= len(_SMALL):
        hi, lo = np.divmod(a, len(_SMALL))
        s = np.where(hi > 0, cat(digits(hi), digits(lo, 5)), _SMALL[lo])
    else:
        s = _SMALL[a]
    return np.strings.zfill(s, width) if width else s


def vocab(words, idx: np.ndarray) -> np.ndarray:
    """``words[idx]`` as a byte-string array."""
    return np.asarray([w.encode() for w in words], dtype="S")[idx]


def hexes(rng: np.random.Generator, n: int, width: int) -> np.ndarray:
    """``n`` random lower-case hexadecimal strings of ``width`` digits."""
    lut = np.frombuffer(b"0123456789abcdef", np.uint8)
    return lut[rng.integers(0, 16, (n, width))].view(f"S{width}")[:, 0]


def uuid(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` random UUID-shaped strings (8-4-4-4-12 hex digits)."""
    return cat(*[p for w in (8, 4, 4, 4, 12)
                 for p in (b"-", hexes(rng, n, w))][1:])


def cat(*parts) -> np.ndarray:
    """Concatenate byte-string arrays and literals element-wise."""
    out = parts[0]
    for p in parts[1:]:
        out = np.strings.add(out, p)
    return np.asarray(out)


def _escaped(col: np.ndarray) -> np.ndarray:
    """A string column as it stands inside JSON quotes."""
    if b"\\" not in col.tobytes():
        return col
    return np.strings.replace(col, b"\\", b"\\\\")


def _text(col: np.ndarray) -> np.ndarray:
    """The JSON text of a column's values, element-wise."""
    if col.dtype == bool:
        return np.where(col, b"true", b"false")
    if col.dtype.kind in "iu":
        return digits(col)
    return cat(b'"', _escaped(col), b'"')


def obj(**fields: np.ndarray) -> np.ndarray:
    """The compact JSON text of one object per row, keys in the given
    order; a nested field's column is JSON text already (``bytes`` keys
    are not supported)."""
    parts: list = []
    for i, (key, col) in enumerate(fields.items()):
        text = col if isinstance(col, Nested) else _text(col)
        parts += [f'{"," if i else "{"}"{key}":'.encode(), text]
    return Nested(cat(*parts, b"}"))


def array(items: list[np.ndarray], counts: np.ndarray) -> np.ndarray:
    """The compact JSON text of one array per row: row ``r`` holds the
    first ``counts[r]`` of ``items`` (columns of one element each)."""
    items = [it if isinstance(it, Nested) else _text(it) for it in items]
    out = np.full(len(counts), b"", "S1")
    acc = None
    for c, it in enumerate(items, 1):
        acc = it if acc is None else cat(acc, b",", it)
        out = np.where(counts == c, acc, out)
    return Nested(cat(b"[", out, b"]"))


class Nested(np.ndarray):
    """A column of JSON texts of objects or arrays."""

    def __new__(cls, a: np.ndarray) -> "Nested":
        return np.asarray(a).view(cls)


@dataclass
class Table:
    """Generated rows as columns, in schema order."""

    cols: dict[str, np.ndarray]
    nested: frozenset[str] = field(init=False)

    def __post_init__(self) -> None:
        self.nested = frozenset(k for k, c in self.cols.items()
                                if isinstance(c, Nested))

    def json(self) -> list[bytes]:
        """Every row as compact JSON bytes, keys in schema order."""
        fields, values = [], []
        for key, col in self.cols.items():
            if key in self.nested:
                fields.append(f'"{key}":%s')
                values.append(np.asarray(col).tolist())
            elif col.dtype == bool:
                fields.append(f'"{key}":%s')
                values.append(np.where(col, b"true", b"false").tolist())
            elif col.dtype.kind in "iu":
                fields.append(f'"{key}":%d')
                values.append(col.tolist())
            else:
                fields.append(f'"{key}":"%s"')
                values.append(_escaped(col).tolist())
        fmt = ("{" + ",".join(fields) + "}").encode()
        return [fmt % row for row in zip(*values)]
