"""Jit'd public wrappers over the Pallas kernels with backend dispatch.

Backends:
  * ``"pallas"``           — real TPU lowering (``interpret=False``);
  * ``"pallas_interpret"`` — kernel body interpreted on CPU (CI/correctness);
  * ``"xla"``              — the pure-jnp oracle from :mod:`repro.kernels.ref`.

All wrappers pad R to the record-block multiple and slice back, so callers
never see alignment constraints.  Block shapes are FIXED (never derived
from the incoming chunk size): a chunk only triggers a fresh jit
specialization when it lands in a new (padded-R, L, P, M) bucket, not per
distinct record count (DESIGN.md §3.5).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import ref
from .bitvector_ops import bitvector_reduce
from .fused import clause_bitvectors_fused
from .substring_match import key_value_match, multi_match_any

_PALLAS_BACKENDS = ("pallas", "pallas_interpret")


def _pad_rows(data: np.ndarray, r_blk: int) -> tuple[jnp.ndarray, int]:
    R = data.shape[0]
    padded = max(((R + r_blk - 1) // r_blk) * r_blk, r_blk)
    if padded != R:
        data = np.concatenate(
            [data, np.zeros((padded - R,) + data.shape[1:], data.dtype)], axis=0
        )
    return jnp.asarray(data), R


def match_any(data, patterns, plens, *, backend: str = "pallas",
              r_blk: int = 256) -> np.ndarray:
    """bool[P, R] any-position multi-pattern match."""
    if backend == "xla":
        out = ref.multi_match_any_ref(
            jnp.asarray(data), jnp.asarray(patterns), jnp.asarray(plens)
        )
        return np.asarray(out, dtype=bool)
    if backend not in _PALLAS_BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")
    dataj, R = _pad_rows(np.asarray(data), r_blk)
    out = multi_match_any(
        dataj,
        jnp.asarray(patterns),
        jnp.asarray(plens, dtype=jnp.int32),
        r_blk=r_blk,
        interpret=(backend == "pallas_interpret"),
    )
    return np.asarray(out, dtype=bool)[:, :R]


def match_key_value(data, key: bytes, val: bytes, *,
                    backend: str = "pallas", r_blk: int = 256) -> np.ndarray:
    """bool[R] key-value predicate match (paper Table I row 4)."""
    mk, mv = len(key), len(val)
    unbounded = b"," in val or b"}" in val
    key_arr = jnp.asarray(np.frombuffer(key, np.uint8)[None, :])
    val_arr = jnp.asarray(np.frombuffer(val, np.uint8)[None, :])
    if backend == "xla":
        out = ref.key_value_match_ref(
            jnp.asarray(data), key_arr, val_arr, mk=mk, mv=mv, unbounded=unbounded
        )
        return np.asarray(out[0], dtype=bool)
    if backend not in _PALLAS_BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")
    dataj, R = _pad_rows(np.asarray(data), r_blk)
    out = key_value_match(
        dataj, key_arr, val_arr, mk=mk, mv=mv, unbounded=unbounded,
        r_blk=r_blk,
        interpret=(backend == "pallas_interpret"),
    )
    return np.asarray(out[0], dtype=bool)[:R]


def clause_bitvectors(data, plan, *, backend: str = "pallas",
                      r_blk: int = 256):
    """Fused pushdown pass: dense chunk -> packed per-clause bitvectors.

    ONE device launch regardless of plan composition.  ``plan`` is a
    :class:`repro.kernels.plan.CompiledPlan`.  Returns
    ``(words uint32[C, W], or_words uint32[W], counts int32[C])`` with
    ``W = ceil(R / 32)`` — the clause bitvectors, the ingest load mask
    (OR over clauses) and per-clause popcounts (selectivity feedback).
    """
    data = np.asarray(data, dtype=np.uint8)
    R = data.shape[0]
    C, P = plan.membership.shape
    if C == 0 or P == 0 or R == 0:  # nothing to evaluate: empty outputs
        W = (R + 31) // 32
        return (np.zeros((C, W), np.uint32), np.zeros((W,), np.uint32),
                np.zeros((C,), np.int32))
    if not np.all(np.diff(plan.kinds) >= 0):
        raise ValueError("predicates must be ordered simple-first "
                         "(kernels.plan.compile_plan does this)")
    n_valid = jnp.asarray(np.array([[R]], dtype=np.int32))

    if backend == "xla":
        words, or_words, counts = ref.clause_bitvectors_ref(
            jnp.asarray(data),
            jnp.asarray(plan.ukeys), jnp.asarray(plan.uklens),
            jnp.asarray(plan.uvals), jnp.asarray(plan.uvlens),
            jnp.asarray(plan.uunb),
            jnp.asarray(plan.key_ids), jnp.asarray(plan.val_ids),
            jnp.asarray(plan.membership, dtype=jnp.uint8),
            n_valid, n_simple=plan.n_simple,
        )
        return (np.asarray(words), np.asarray(or_words), np.asarray(counts))
    if backend not in _PALLAS_BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")
    dataj, R = _pad_rows(data, r_blk)
    i32 = lambda a: jnp.asarray(np.asarray(a, dtype=np.int32))  # noqa: E731
    words, or_words, counts = clause_bitvectors_fused(
        dataj, i32(plan.keys), i32(plan.klens), i32(plan.vals),
        i32(plan.vlens), i32(plan.kinds), i32(plan.unbounded),
        i32(plan.membership), i32([R]),
        r_blk=r_blk, interpret=(backend == "pallas_interpret"),
    )
    W = (R + 31) // 32
    return (np.asarray(words)[:, :W], np.asarray(or_words)[:W],
            np.asarray(counts))


def reduce_bitvectors(bitvecs, *, backend: str = "pallas",
                      w_blk: int = 128):
    """(and_words, or_words, surviving_count) over uint32[P, W]."""
    bv = np.asarray(bitvecs, dtype=np.uint32)
    if backend == "xla":
        a, o, c = ref.bitvector_reduce_ref(jnp.asarray(bv))
        return np.asarray(a), np.asarray(o), int(c)
    W = bv.shape[1]
    padded = max(((W + w_blk - 1) // w_blk) * w_blk, w_blk)
    if padded != W:
        bv = np.concatenate(
            [bv, np.zeros((bv.shape[0], padded - W), np.uint32)], axis=1
        )
    a, o, c = bitvector_reduce(
        jnp.asarray(bv), w_blk=w_blk, interpret=(backend == "pallas_interpret")
    )
    return np.asarray(a)[:W], np.asarray(o)[:W], int(c)
