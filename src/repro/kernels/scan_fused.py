"""Fused multi-query scan kernel over the device-resident segment plane.

This is the device half of DESIGN.md §15.  The host half
(:class:`repro.core.device_cache.DeviceSegmentCache`) keeps every hot
segment's columnar state resident as per-shard ``jnp`` buffers:

  * per-key row masks     — present / notnull / is_bool / num_valid,
    stacked ``uint8[K, N]`` over the concatenated rows of all cached
    segments (``K`` = union of keys, row 0 reserved all-absent);
  * dictionary codes      — ``str_codes`` / ``repr_codes`` ``int32[K, N]``
    (-1 = not-a-string / absent, matching ``core.columnar.KeyColumn``);
  * ``seg_ids int32[N]``  — row -> cache slot (-1 = capacity padding);
  * ``clause_word``       — the segment's packed pushed bitvectors,
    TRANSPOSED to one ``uint32`` per row (bit *p* = clause row *p* of
    that segment's coverage; cache admission requires n_covered <= 32).

A batch of queries compiles once (:func:`compile_scan_batch`) into the
same clause/term-dedup shape the ingest plan compiler uses
(``kernels.plan`` / ``core.client.dedup_terms``) — except keyed on
type-strict predicate identity rather than pattern bytes, because two
predicates with identical raw patterns (e.g. EXACT on different keys)
evaluate differently under ``core.columnar.eval_lowered``.  Everything
else arrives as small per-scan parameter tables resolved on the host
from the segment dictionaries (codes, substring LUTs, pushed-bit masks,
zone-prune verdicts): parameters are O(terms x slots), never O(rows) —
segment columns are uploaded at admission only.

One launch then evaluates the whole batch: zone-prune mask -> pushed
bitvector AND -> lowered residual on dictionary codes -> per-(query,
slot) popcount.  Counts are bit-identical to
``core.columnar.query_mask`` because every ``eval_lowered`` branch has
an exact integer form:

  * KEY_PRESENCE          — ``notnull``;
  * EXACT (str value)     — ``str_codes == str_index.get(v, -2)``;
  * SUBSTRING             — per-slot LUT over the string dictionary,
    probed by ``str_codes`` (offset -1 = provably-empty / missing key);
  * KEY_VALUE             — repr-code equality, plus the null branch,
    plus the numeric branch: ``num_valid & (num == float(v))`` equals
    ``num_valid & repr_codes ∈ codes(_num_reprs(float(v)))`` — the repr
    dictionary encodes ``json_scalar`` of every present value, and
    ``_num_reprs`` enumerates every rendering a float64-equal scalar can
    have — so no float column ever needs to leave the host.

Two backends: ``"xla"`` (jitted jnp, the fast path on CPU hosts) and
``"pallas"``/interpret (one real ``pallas_call``: per-row slot one-hot,
parameter gathers and the final per-slot popcount are all expressed as
f32 matmuls — integer tables at fp32 contraction precision, exact for
codes < 2^24 — so the kernel maps onto the MXU; plane masks arrive as
uint8 and widen through int32, since Mosaic has no uint8 -> f32 cast.
The substring LUT probe is a data-dependent gather into a LUT of
unbounded length, which Mosaic cannot lower: it runs as 1-D XLA gathers
in the same jitted program, one row per substring term, ahead of the
kernel; :func:`scan_counts` sends its parameter tables packed in one
int32 buffer, one upload, and fetches counts and cands in one copy).
Both return
``counts[Q, S]`` / ``cands[Q, S]`` (matches, pushed-candidate rows) per
cache slot, which the device scanner folds into the standard per-(epoch,
tier) :class:`~repro.core.server.ScanResult` accounting.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.predicates import (
    Clause, Kind, Query, SimplePredicate, lowerable,
)
from repro.core.telemetry import span

from .plan import compile_query_batch
from .residual import _pow2

KIND_PRESENCE = 0
KIND_EXACT = 1
KIND_SUBSTRING = 2
KIND_KV = 3
_KIND_CODE = {
    Kind.KEY_PRESENCE: KIND_PRESENCE,
    Kind.EXACT: KIND_EXACT,
    Kind.SUBSTRING: KIND_SUBSTRING,
    Kind.KEY_VALUE: KIND_KV,
}

#: cache slots carry pushed coverage as one uint32 word per row
MAX_COVERED = 32


def device_lowerable(t: SimplePredicate) -> bool:
    """True iff ``t`` evaluates on the device dictionary-code plane.

    Stricter than host ``lowerable``: RANGE and IN lower to vectorized
    numpy (repr-LUT / per-element OR) but have no ``_KIND_CODE`` row —
    their repr LUTs would be per-(term, slot) rebuilt parameters of
    unbounded width.  Queries containing them fall back whole to the
    host scanner (the standard non-eligible path), keeping counts
    bit-identical.
    """
    return lowerable(t) and t.kind in _KIND_CODE


# ---------------------------------------------------------------------------
# batch compilation: queries -> deduped clause/term tables
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ScanBatch:
    """Clause/term-deduped encoding of a query batch (host-side)."""

    queries: tuple[Query, ...]
    clauses: tuple[Clause, ...]          # unique clauses across the batch
    terms: tuple[SimplePredicate, ...]   # unique terms across those clauses
    membership: np.ndarray               # uint8[C, T] clause -> term
    query_clause: np.ndarray             # uint8[Q, C] query -> clause
    query_ok: tuple[bool, ...]           # per-query device eligibility

    @property
    def n_queries(self) -> int:
        return len(self.queries)

    @property
    def n_clauses(self) -> int:
        return len(self.clauses)

    @property
    def n_terms(self) -> int:
        return len(self.terms)


def compile_scan_batch(queries: Sequence[Query]) -> ScanBatch:
    """Dedup clauses and terms across a query batch.

    Thin wrapper over :func:`repro.kernels.plan.compile_query_batch` —
    ONE implementation of the query -> clause -> term type-strict dedup
    serves both multi-query planes (the host ``ScanBatcher`` and this
    device compiler); see its docstring for why the dedup keys on
    predicate equality rather than ``dedup_terms``' pattern bytes.
    ``query_ok`` is the per-query device-eligibility flag: every term
    must lower onto the dictionary-code plane (:func:`device_lowerable`
    — host-lowerable RANGE/IN terms still disqualify a query here).
    """
    qb = compile_query_batch(queries)
    ok = tuple(
        all(device_lowerable(t) for c in q.clauses for t in c.terms)
        for q in qb.queries
    )
    return ScanBatch(
        queries=qb.queries, clauses=qb.clauses, terms=qb.terms,
        membership=qb.membership, query_clause=qb.query_clause,
        query_ok=ok,
    )


class ScanParams(NamedTuple):
    """Per-scan parameter tables (host numpy, bucket-padded).

    Shapes: T/C/Q/S1 are power-of-two buckets of (terms, clauses,
    queries, slots + 1); the extra slot S1-1 is the dummy that
    capacity-padding rows (seg_id -1) resolve to, with ``active`` zeroed
    so they can never contribute.
    """

    key_ids: np.ndarray      # int32[T]   term -> plane key row (0 = absent)
    kinds: np.ndarray        # int32[T]   KIND_* (-1 = padding, inert)
    code_a: np.ndarray       # int32[T, S1]  EXACT str code / KV repr code
    num_codes: np.ndarray    # int32[T, 3, S1] KV numeric repr codes
    lut_off: np.ndarray      # int32[T, S1]  substring LUT base (-1 = empty)
    lut_flat: np.ndarray     # uint8[L]      concatenated substring LUTs
    is_null: np.ndarray      # uint8[T]   KV value is None
    is_boolv: np.ndarray     # uint8[T]   KV value is a bool
    membership: np.ndarray   # uint8[C, T]
    query_clause: np.ndarray  # uint8[Q, C]
    pushed_tbl: np.ndarray   # uint32[Q, S1] pushed clause bits (0 = all-pass)
    active: np.ndarray       # uint8[Q, S1]  zone-prune verdict (0 = pruned)


# ---------------------------------------------------------------------------
# XLA backend
# ---------------------------------------------------------------------------

def scan_core_xla(pres, notn, isb, numv, scod, rcod, sid, cw,
                  key_ids, kinds, code_a, num_codes, lut_off, lut_flat,
                  is_null, is_boolv, membership, query_clause,
                  pushed_tbl, active, kind_counts=None):
    """Unjitted fused scan body (also the ``shard_map`` SPMD payload).

    Three CPU-motivated structural choices, all bit-exact:

      * ``optimization_barrier`` after every gather-producing
        intermediate — XLA fusion otherwise inlines the gathers into
        each consumer's scalar loop and recomputes them per use (~2.4x
        on the CPU backend);
      * the clause/query matmuls and the per-slot count reduction run
        as f32 GEMMs (Eigen on CPU, MXU on TPU) instead of int32
        matmuls / ``.at[].add`` scatters.  Exact: every operand is 0/1
        and every sum is bounded by max(T, C, N) < 2^24;
      * when ``kind_counts`` (a static ``(n_presence, n_exact,
        n_substring, n_kv)`` tuple over kind-sorted term rows) is
        given, each term row evaluates ONLY its own kind's branch and
        gathers only the tables that branch reads, instead of
        computing all four branches for every row and selecting.  The
        per-kind expressions are unchanged, so the term matrix is
        identical row-for-row; this is what makes a batched launch
        scale with the batch's real work.  ``None`` keeps the generic
        select body (the ``shard_map`` path, where kinds arrive
        traced).
    """
    S1 = pushed_tbl.shape[1]
    L = lut_flat.shape[0]
    bar = jax.lax.optimization_barrier
    sid = jnp.where(sid < 0, S1 - 1, sid)
    sid = bar(sid)
    if kind_counts is None:
        tp = pres[key_ids] > 0                # (T, N)
        tn = notn[key_ids] > 0
        tb = isb[key_ids] > 0
        tv = numv[key_ids] > 0
        ts = scod[key_ids]
        tr = rcod[key_ids]
        tp, tn, tb, tv, ts, tr = bar((tp, tn, tb, tv, ts, tr))
        ca = code_a[:, sid]                   # (T, N)
        off = lut_off[:, sid]
        ca, off = bar((ca, off))
        m_exact = ts == ca
        idx = jnp.clip(off + 1 + ts, 0, L - 1)
        m_sub = (lut_flat[idx] > 0) & (off >= 0)
        nc = num_codes[:, :, sid]             # (T, 3, N)
        nc = bar(nc)
        m_num = tv & jnp.any(nc == tr[:, None, :], axis=1)
        m_null = (is_null[:, None] > 0) & tp & ~tn
        compat = jnp.where(is_boolv[:, None] > 0, tb, tp & ~tb)
        m_kv = ((tr == ca) | m_num | m_null) & compat
        k = kinds[:, None]
        term = jnp.where(
            k == KIND_PRESENCE, tn,
            jnp.where(k == KIND_EXACT, m_exact,
                      jnp.where(k == KIND_SUBSTRING, m_sub,
                                jnp.where(k == KIND_KV, m_kv, False))))
    else:
        n_pre, n_ex, n_sub, n_kv = kind_counts
        parts = []
        a = 0
        if n_pre:
            parts.append(bar(notn[key_ids[a:a + n_pre]]) > 0)
        a += n_pre
        if n_ex:
            ts = scod[key_ids[a:a + n_ex]]
            ca = code_a[a:a + n_ex][:, sid]
            ts, ca = bar((ts, ca))
            parts.append(ts == ca)
        a += n_ex
        if n_sub:
            ts = scod[key_ids[a:a + n_sub]]
            off = lut_off[a:a + n_sub][:, sid]
            ts, off = bar((ts, off))
            idx = jnp.clip(off + 1 + ts, 0, L - 1)
            hitb = bar(lut_flat[idx])
            parts.append((hitb > 0) & (off >= 0))
        a += n_sub
        if n_kv:
            kk = key_ids[a:a + n_kv]
            tp = pres[kk] > 0
            tn = notn[kk] > 0
            tb = isb[kk] > 0
            tv = numv[kk] > 0
            tr = rcod[kk]
            tp, tn, tb, tv, tr = bar((tp, tn, tb, tv, tr))
            ca = code_a[a:a + n_kv][:, sid]
            nc = num_codes[a:a + n_kv][:, :, sid]
            ca, nc = bar((ca, nc))
            m_num = tv & jnp.any(nc == tr[:, None, :], axis=1)
            m_null = (is_null[a:a + n_kv, None] > 0) & tp & ~tn
            compat = jnp.where(is_boolv[a:a + n_kv, None] > 0,
                               tb, tp & ~tb)
            parts.append(((tr == ca) | m_num | m_null) & compat)
        a += n_kv
        if kinds.shape[0] > a:                # bucket-padding rows: inert
            parts.append(jnp.zeros((kinds.shape[0] - a, sid.shape[0]),
                                   bool))
        term = parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)
    term = bar(term)
    cm = (membership.astype(jnp.float32) @ term.astype(jnp.float32)) > 0.0
    viol = query_clause.astype(jnp.float32) @ (1.0 - cm.astype(jnp.float32))
    qm = viol == 0.0                          # (Q, N)
    ptab = pushed_tbl[:, sid]                 # (Q, N)
    ptab = jax.lax.optimization_barrier(ptab)
    pm = (cw[None, :] & ptab) == ptab
    act = active[:, sid] > 0
    hit = qm & pm & act
    hit, pa = bar((hit, pm & act))
    # per-slot popcount as ONE (2Q, N) @ (N, S1) f32 GEMM: the one-hot
    # is built directly in (N, S1) layout — Eigen runs the non-transposed
    # product ~3x faster than two (Q, N) @ (S1, N)^T calls
    iota = jax.lax.broadcasted_iota(jnp.int32, (sid.shape[0], S1), 1)
    slot_oh = (sid[:, None] == iota).astype(jnp.float32)
    z = jnp.concatenate([hit, pa], axis=0).astype(jnp.float32)
    seg = (z @ slot_oh).astype(jnp.int32)
    Q = pushed_tbl.shape[0]
    return seg[:Q], seg[Q:]


_scan_core_xla = jax.jit(scan_core_xla, static_argnames=("kind_counts",))


# ---------------------------------------------------------------------------
# pallas backend
# ---------------------------------------------------------------------------

def _mm(a, b, *, exact: bool = False):
    """f32 matmul ``a @ b``.  ``exact`` forces fp32 contraction: one-hot
    row selection of an integer table is then exact for |values| < 2^24
    (a single-pass bf16 MXU product would round codes above 256)."""
    return jax.lax.dot(
        a, b, precision=jax.lax.Precision.HIGHEST if exact else None,
        preferred_element_type=jnp.float32)


def _mm_t(a, b):
    """f32 ``a @ b.T`` (0/1 operands, integer sums < 2^24: exact)."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _scan_kernel(keym_ref, pres_ref, notn_ref, isb_ref, numv_ref,
                 scod_ref, rcod_ref, sid_ref, cw_ref, sub_ref,
                 subsel_ref, kinds_ref, code_a_ref, num_codes_ref,
                 is_null_ref, is_boolv_ref, mem_ref, qc_ref,
                 plo_ref, phi_ref, act_ref,
                 counts_ref, cands_ref, *, n_slots: int, r_blk: int):
    nb = pl.program_id(0)

    @pl.when(nb == 0)
    def _init():  # first tile zeroes the accumulators
        counts_ref[...] = jnp.zeros_like(counts_ref)
        cands_ref[...] = jnp.zeros_like(cands_ref)

    f32, i32 = jnp.float32, jnp.int32

    def mask(ref):   # uint8 plane rows -> f32 0/1 (no direct u8->f32 cast)
        return ref[...].astype(i32).astype(f32)

    sid = sid_ref[...]                                    # (1, blk)
    sid = jnp.where(sid < 0, n_slots - 1, sid)
    # per-row slot one-hot: every parameter gather and the final per-slot
    # reduction become (.., S1) x (S1, blk) matmuls on the MXU
    iota = jax.lax.broadcasted_iota(i32, (n_slots, r_blk), 0)
    slot_oh = (sid == iota).astype(f32)                   # (S1, blk)

    def per_row(ref):  # (.., S1) integer table -> (.., blk), exact
        return _mm(ref[...], slot_oh, exact=True).astype(i32)

    keym = keym_ref[...]                                  # (T, K) one-hot
    tp = _mm(keym, mask(pres_ref)) > 0.0                  # (T, blk)
    tn = _mm(keym, mask(notn_ref)) > 0.0
    tb = _mm(keym, mask(isb_ref)) > 0.0
    tv = _mm(keym, mask(numv_ref)) > 0.0
    ts = _mm(keym, scod_ref[...].astype(f32), exact=True).astype(i32)
    tr = _mm(keym, rcod_ref[...].astype(f32), exact=True).astype(i32)
    ca = per_row(code_a_ref)
    m_exact = ts == ca
    # substring LUT probes arrive pre-gathered (one row per substring
    # term); subsel maps them back onto their term rows
    m_sub = _mm(subsel_ref[...], sub_ref[...].astype(f32)) > 0.0
    m_num = tv & ((per_row(num_codes_ref.at[0]) == tr)
                  | (per_row(num_codes_ref.at[1]) == tr)
                  | (per_row(num_codes_ref.at[2]) == tr))
    shape = tp.shape

    def flag(ref):   # (T, 1) int32 term flag -> (T, blk) bool
        return jnp.broadcast_to(ref[...], shape) > 0

    m_null = flag(is_null_ref) & tp & ~tn
    boolv = flag(is_boolv_ref)
    compat = (boolv & tb) | (~boolv & tp & ~tb)      # no i1 select
    m_kv = ((tr == ca) | m_num | m_null) & compat
    k = jnp.broadcast_to(kinds_ref[...], shape)
    term = (((k == KIND_PRESENCE) & tn) | ((k == KIND_EXACT) & m_exact)
            | ((k == KIND_SUBSTRING) & m_sub) | ((k == KIND_KV) & m_kv))
    cm = _mm(mem_ref[...], term.astype(f32)) > 0.0         # (C, blk)
    viol = _mm(qc_ref[...], 1.0 - cm.astype(f32))
    qm = viol == 0.0                                      # (Q, blk)
    # pushed words gathered as two exact 16-bit halves (int32 bit ops)
    ptab = (per_row(phi_ref) << 16) | per_row(plo_ref)
    cw = cw_ref[...]                                      # (1, blk) int32
    pm = (cw & ptab) == ptab
    act = _mm(act_ref[...], slot_oh) > 0.0
    hit = (qm & pm & act).astype(f32)
    counts_ref[...] += _mm_t(hit, slot_oh)                # (Q, S1)
    cands_ref[...] += _mm_t((pm & act).astype(f32), slot_oh)


def _substring_hits(scod, sid, sub_keys, sub_off, lut_flat):
    """``int32[Ts, N]`` substring LUT probes (XLA gathers).

    Row j probes term j's per-slot LUT with the row's string code —
    exactly the ``m_sub`` of :func:`scan_core_xla`.  A data-dependent
    gather into a LUT of unbounded length has no Mosaic lowering, so it
    runs as an XLA stage of the same jitted program, ahead of the kernel.
    """
    S1 = sub_off.shape[1]
    L = lut_flat.shape[0]
    sid = jnp.where(sid < 0, S1 - 1, sid)
    rows = []
    for j in range(sub_keys.shape[0]):    # Ts is a small static bucket
        # one row at a time keeps every gather 1-D: a 2-D (Ts, N)
        # gather picks a lane-padded (N, Ts) layout on TPU (~1 GB temp)
        off = sub_off[j][sid]                              # (N,)
        code = jax.lax.dynamic_index_in_dim(
            scod, sub_keys[j], axis=0, keepdims=False)     # (N,)
        idx = jnp.clip(off + 1 + code, 0, L - 1)
        rows.append((lut_flat[idx] > 0) & (off >= 0))
    return jnp.stack(rows).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("r_blk", "interpret"))
def _scan_core_pallas(pres, notn, isb, numv, scod, rcod, sid, cw,
                      keym, kinds, code_a, num_codes, sub_keys, sub_off,
                      subsel, lut_flat, is_null, is_boolv, membership,
                      query_clause, plo, phi, active, *, r_blk: int,
                      interpret: bool):
    """Fused scan: XLA substring probes + one ``pallas_call``.

    Plane: ``uint8[K, N]`` masks, ``int32[K, N]`` codes, ``int32[N]``
    ``sid`` / ``cw`` (clause words bit-cast to int32).  Parameters:
    f32 one-hot / 0-1 tables (``keym [T, K]``, ``subsel [T, Ts]``,
    ``membership [C, T]``, ``query_clause [Q, C]``, ``active [Q, S1]``),
    f32 integer tables (``code_a [T, S1]``, ``num_codes [3, T, S1]``,
    ``plo`` / ``phi [Q, S1]``), ``int32 [T, 1]`` term flags, and the
    substring LUT inputs (``sub_keys int32[Ts]``, ``sub_off
    int32[Ts, S1]``, ``lut_flat uint8[L]``).
    """
    K, N = pres.shape
    T = kinds.shape[0]
    C = membership.shape[0]
    Q, S1 = plo.shape
    Ts = subsel.shape[1]
    if N % r_blk or r_blk % 128:
        raise ValueError(f"N={N} not a multiple of r_blk={r_blk} "
                         f"(a multiple of 128)")
    sub = _substring_hits(scod, sid, sub_keys, sub_off, lut_flat)

    def col(k):      # (k, N) column tiles
        return pl.BlockSpec((k, r_blk), lambda nb: (0, nb))

    def full(*shape):  # whole-array parameter blocks
        return pl.BlockSpec(shape, lambda nb, _n=len(shape): (0,) * _n)

    counts, cands = pl.pallas_call(
        functools.partial(_scan_kernel, n_slots=S1, r_blk=r_blk),
        grid=(N // r_blk,),
        in_specs=[
            full(T, K),                       # keym
            col(K), col(K), col(K), col(K),   # pres/notn/isb/numv
            col(K), col(K),                   # scod/rcod
            col(1), col(1),                   # sid/cw
            col(Ts),                          # substring probes
            full(T, Ts),                      # subsel
            full(T, 1),                       # kinds
            full(T, S1),                      # code_a
            full(3, T, S1),                   # num_codes
            full(T, 1), full(T, 1),           # is_null / is_boolv
            full(C, T), full(Q, C),           # membership / query_clause
            full(Q, S1), full(Q, S1),         # plo / phi
            full(Q, S1),                      # active
        ],
        out_specs=[full(Q, S1), full(Q, S1)],
        out_shape=[
            jax.ShapeDtypeStruct((Q, S1), jnp.float32),
            jax.ShapeDtypeStruct((Q, S1), jnp.float32),
        ],
        interpret=interpret,
        name="_scan_core_pallas",   # the trace's name for the kernel
    )(keym, pres, notn, isb, numv, scod, rcod,
      sid.reshape(1, N), jax.lax.bitcast_convert_type(cw, jnp.int32)
      .reshape(1, N), sub, subsel, kinds, code_a, num_codes,
      is_null, is_boolv, membership, query_clause, plo, phi, active)
    return counts.astype(jnp.int32), cands.astype(jnp.int32)


#: dtypes of the packed parameter buffer's tables.  Every element is one
#: int32 word: f32 tables travel as their bit pattern, the uint8 substring
#: LUT widened (it is only gathered and compared ``> 0``)
_F32, _I32, _U8 = "float32", "int32", "uint8"


@functools.lru_cache(maxsize=256)
def scan_args_layout(T: int, K: int, C: int, Q: int, S1: int, Ts: int,
                     L: int) -> tuple:
    """Static layout of the packed parameter buffer: one ``(offset,
    shape, dtype)`` per table of :func:`_scan_core_pallas`, in its
    argument order after the eight plane arrays.  It depends on the
    bucketed shapes alone, so the same buckets reuse one trace."""
    tables = (
        ((T, K), _F32),                       # keym
        ((T, 1), _I32),                       # kinds
        ((T, S1), _F32),                      # code_a
        ((3, T, S1), _F32),                   # num_codes
        ((Ts,), _I32), ((Ts, S1), _I32),      # sub_keys / sub_off
        ((T, Ts), _F32),                      # subsel
        ((L,), _U8),                          # lut_flat
        ((T, 1), _I32), ((T, 1), _I32),       # is_null / is_boolv
        ((C, T), _F32), ((Q, C), _F32),       # membership / query_clause
        ((Q, S1), _F32), ((Q, S1), _F32),     # plo / phi
        ((Q, S1), _F32),                      # active
    )
    out, off = [], 0
    for shape, dtype in tables:
        out.append((off, shape, dtype))
        off += math.prod(shape)
    return tuple(out)


def _layout_words(layout: tuple) -> int:
    off, shape, _ = layout[-1]
    return off + math.prod(shape)


def _host_tables(buf: np.ndarray, layout: tuple) -> list[np.ndarray]:
    """Writable views of each table in the packed host buffer (f32
    tables as float32 views of their words, the LUT as its int32 words)."""
    out = []
    for off, shape, dtype in layout:
        words = buf[off:off + math.prod(shape)].reshape(shape)
        out.append(words.view(np.float32) if dtype == _F32 else words)
    return out


def pack_scan_args(params: ScanParams, n_keys: int,
                   n_sub: int | None = None) -> tuple[np.ndarray, tuple]:
    """The parameter tables of :func:`_scan_core_pallas`, built straight
    into one contiguous ``int32`` host buffer; returns it with its
    :func:`scan_args_layout`.  ``n_sub`` pads the substring-probe rows to
    a common bucket (SPMD launches)."""
    T = params.kinds.shape[0]
    C = params.membership.shape[0]
    Q, S1 = params.pushed_tbl.shape
    sub_rows = np.flatnonzero(params.kinds == KIND_SUBSTRING)
    Ts = n_sub or bucket_pow2(len(sub_rows))
    layout = scan_args_layout(T, n_keys, C, Q, S1, Ts,
                              params.lut_flat.shape[0])
    buf = np.zeros(_layout_words(layout), np.int32)   # 0 is also 0.0f
    (keym, kinds, code_a, num_codes, sub_keys, sub_off, subsel, lut,
     is_null, is_boolv, membership, query_clause, plo, phi,
     active) = _host_tables(buf, layout)
    keym[np.arange(T), params.key_ids] = 1.0
    kinds[:, 0] = params.kinds
    code_a[...] = params.code_a
    num_codes[...] = params.num_codes.transpose(1, 0, 2)
    sub_keys[:len(sub_rows)] = params.key_ids[sub_rows]
    sub_off[...] = -1
    sub_off[:len(sub_rows)] = params.lut_off[sub_rows]
    subsel[sub_rows, np.arange(len(sub_rows))] = 1.0
    lut[...] = params.lut_flat
    is_null[:, 0] = params.is_null
    is_boolv[:, 0] = params.is_boolv
    membership[...] = params.membership
    query_clause[...] = params.query_clause
    plo[...] = params.pushed_tbl & np.uint32(0xFFFF)
    phi[...] = params.pushed_tbl >> np.uint32(16)
    active[...] = params.active
    return buf, layout


def pallas_scan_args(params: ScanParams, n_keys: int,
                     n_sub: int | None = None) -> tuple:
    """The tables of :func:`pack_scan_args` one by one, each in the
    dtype :func:`_scan_core_pallas` takes (the SPMD launch uploads them
    table by table)."""
    buf, layout = pack_scan_args(params, n_keys, n_sub)
    return tuple(t.astype(dtype, copy=False) for t, (_, _, dtype)
                 in zip(_host_tables(buf, layout), layout))


def unpack_scan_args(packed: jnp.ndarray, layout: tuple) -> list:
    """Device side of :func:`pack_scan_args`: static slices of the packed
    words, each cast back to its table's dtype."""
    out = []
    for off, shape, dtype in layout:
        a = jax.lax.slice_in_dim(packed, off, off + math.prod(shape))
        a = a.reshape(shape)
        if dtype == _F32:
            a = jax.lax.bitcast_convert_type(a, jnp.float32)
        elif dtype == _U8:
            a = a.astype(jnp.uint8)
        out.append(a)
    return out


@functools.partial(
    jax.jit, static_argnames=("layout", "r_blk", "interpret"))
def _scan_core_pallas_packed(pres, notn, isb, numv, scod, rcod, sid, cw,
                             packed, *, layout: tuple, r_blk: int,
                             interpret: bool):
    """:func:`_scan_core_pallas` over one packed ``int32`` parameter
    buffer (:func:`pack_scan_args`), unpacked in the same program.
    Returns ``int32[2, Q, S1]``: counts and cands stacked, one fetch."""
    counts, cands = _scan_core_pallas(
        pres, notn, isb, numv, scod, rcod, sid, cw,
        *unpack_scan_args(packed, layout), r_blk=r_blk, interpret=interpret)
    return jnp.stack([counts, cands])


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

class DevicePlaneArrays(NamedTuple):
    """The device-resident plane a launch consumes (all ``jnp``)."""

    pres: jnp.ndarray    # uint8[K, N]
    notn: jnp.ndarray    # uint8[K, N]
    isb: jnp.ndarray     # uint8[K, N]
    numv: jnp.ndarray    # uint8[K, N]
    scod: jnp.ndarray    # int32[K, N]
    rcod: jnp.ndarray    # int32[K, N]
    sid: jnp.ndarray     # int32[N] (-1 = padding)
    cw: jnp.ndarray      # uint32[N]


def scan_core_numpy(pres, notn, isb, numv, scod, rcod, sid, cw,
                    params: ScanParams) -> tuple[np.ndarray, np.ndarray]:
    """Numpy-vectorized reference of the fused scan, bit-identical.

    Plane arrays arrive as HOST numpy (the baseline's "resident"
    mirror).  Serves two roles: the differential oracle the kernel
    backends are tested against, and the ``numpy`` side of
    ``benchmarks.bench_device`` — the same multi-query plane scan,
    vectorized the way a numpy engine would write it (one temporary per
    stage), so the gated speedup isolates what the fused single launch
    buys on identical work.
    """
    S1 = params.pushed_tbl.shape[1]
    L = params.lut_flat.shape[0]
    sid = np.where(sid < 0, S1 - 1, sid)
    key_ids = params.key_ids
    tp = pres[key_ids] > 0                    # (T, N)
    tn = notn[key_ids] > 0
    tb = isb[key_ids] > 0
    tv = numv[key_ids] > 0
    ts = scod[key_ids]
    tr = rcod[key_ids]
    ca = params.code_a[:, sid]
    off = params.lut_off[:, sid]
    m_exact = ts == ca
    idx = np.clip(off + 1 + ts, 0, L - 1)
    m_sub = (params.lut_flat[idx] > 0) & (off >= 0)
    nc = params.num_codes[:, :, sid]
    m_num = tv & (nc == tr[:, None, :]).any(axis=1)
    m_null = (params.is_null[:, None] > 0) & tp & ~tn
    compat = np.where(params.is_boolv[:, None] > 0, tb, tp & ~tb)
    m_kv = ((tr == ca) | m_num | m_null) & compat
    k = params.kinds[:, None]
    term = np.select(
        [k == KIND_PRESENCE, k == KIND_EXACT, k == KIND_SUBSTRING,
         k == KIND_KV],
        [tn, m_exact, m_sub, m_kv], False)
    cm = (params.membership.astype(np.int32) @ term.astype(np.int32)) > 0
    viol = params.query_clause.astype(np.int32) @ (1 - cm.astype(np.int32))
    qm = viol == 0                            # (Q, N)
    ptab = params.pushed_tbl[:, sid]
    pm = (cw[None, :] & ptab) == ptab
    act = params.active[:, sid] > 0
    hit = qm & pm & act
    pa = pm & act
    Q = params.pushed_tbl.shape[0]
    counts = np.zeros((Q, S1), np.int32)
    cands = np.zeros((Q, S1), np.int32)
    for q in range(Q):
        counts[q] = np.bincount(sid, weights=hit[q], minlength=S1)[:S1]
        cands[q] = np.bincount(sid, weights=pa[q], minlength=S1)[:S1]
    return counts, cands


@dataclass
class LaunchCounters:
    """Host <-> device traffic of the scan launches they are handed to."""

    launches: int = 0
    param_transfers: int = 0   # host -> device parameter uploads
    param_bytes: int = 0
    fetches: int = 0           # device -> host result copies


def scan_counts(plane: DevicePlaneArrays, params: ScanParams, *,
                backend: str = "pallas", r_blk: int = 512,
                counters: LaunchCounters | None = None,
                ) -> tuple[np.ndarray, np.ndarray]:
    """One fused launch over the plane; ``(counts, cands)`` as int32[Q, S1].

    ``backend``: ``"xla"`` (jitted jnp), ``"pallas_interpret"`` (the
    pallas kernel under the interpreter — the CPU-verifiable TPU
    artifact), ``"pallas"`` (compiled, real hardware), or ``"numpy"``
    (the host reference — converts the plane per call; perf baselines
    should pre-convert and call :func:`scan_core_numpy` directly).
    The pallas backends send every parameter table in one packed upload
    and fetch counts and cands in one copy.  ``counters`` counts the
    launch's transfers.
    """
    if counters is None:
        counters = LaunchCounters()
    counters.launches += 1

    def put(a: np.ndarray):
        counters.param_transfers += 1
        counters.param_bytes += a.nbytes
        return jax.device_put(a, plane.pres.sharding)

    with span("ciao.scan.launch"):
        if backend == "numpy":
            counts, cands = scan_core_numpy(
                *(np.asarray(a) for a in plane), params)
            return counts, cands
        if backend == "xla":
            # sort term rows by kind (stable; bucket padding, kind -1, goes
            # last) so the launch can evaluate each row's own branch only.
            # Membership columns permute with them — results are identical.
            kinds = params.kinds
            order = np.argsort(
                np.where(kinds < 0, np.int32(KIND_KV + 1), kinds),
                kind="stable")
            kc = tuple(int((kinds == k).sum())
                       for k in (KIND_PRESENCE, KIND_EXACT,
                                 KIND_SUBSTRING, KIND_KV))
            out = _scan_core_xla(
                *plane,
                put(params.key_ids[order]),
                put(params.kinds[order]),
                put(params.code_a[order]),
                put(params.num_codes[order]),
                put(params.lut_off[order]),
                put(params.lut_flat),
                put(params.is_null[order]),
                put(params.is_boolv[order]),
                put(params.membership[:, order]),
                put(params.query_clause),
                put(params.pushed_tbl), put(params.active),
                kind_counts=kc,
            )
        elif backend in ("pallas", "pallas_interpret"):
            with span("ciao.scan.pack"):
                buf, layout = pack_scan_args(params, plane.pres.shape[0])
            with span("ciao.scan.upload"):
                packed = put(buf)
            with span("ciao.scan.dispatch"):
                out = _scan_core_pallas_packed(
                    *plane, packed, layout=layout,
                    r_blk=min(r_blk, plane.sid.shape[0]),
                    interpret=(backend == "pallas_interpret"))
        else:
            raise ValueError(f"unknown device scan backend {backend!r}")
    with span("ciao.scan.fetch"):
        if backend == "xla":
            counters.fetches += 2
            return np.asarray(out[0]), np.asarray(out[1])
        counters.fetches += 1
        both = np.asarray(out)
        return both[0], both[1]


def bucket_pow2(n: int, floor: int = 1) -> int:
    """Power-of-two shape bucket (shared with ``kernels.residual``)."""
    return _pow2(n, floor)
