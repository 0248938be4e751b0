"""Fused single-pass pushdown kernel: chunk -> packed clause bitvectors.

ONE ``pallas_call`` evaluates an entire pushdown plan on a dense chunk
(DESIGN.md §3.4).  The seed path needed 1 launch for the simple-pattern set
plus one launch *per key-value pair* (each a fresh jit specialization),
then round-tripped bool hits to the host to OR disjuncts, pack bitvectors
in numpy, and launched ``bitvector_reduce`` again for the load mask.  Here
the whole chunk -> packed-bitvector path stays on device:

  grid = (R/R_blk, P)   (predicate index innermost, so the record tile
                         stays resident in VMEM across all P predicates)

Layout (TPU Mosaic): the chunk is transposed to ``uint8[L, R]`` before
the call, so a record tile is ``(L, R_blk)`` — byte positions on
sublanes, records on lanes — and widened to int32 inside the kernel.
Every per-record result (hits, the clause accumulator, the valid mask)
is then a lane-dense ``(.., R_blk)`` row, window shifts are sublane
rotations (``pltpu.roll``) with the wrapped rows masked to the original
zero fill, and the per-predicate tables live in SMEM and are read as
scalars.

Per grid step (rb, p) the kernel evaluates predicate ``p`` on the record
tile with *masked dynamic lengths* — the simple any-position match and
the key-value match share one window-equality routine, so one compilation
serves every pattern in the plan (no per-(mk, mv) specializations) — and
ORs the per-record hits into a (C, R_blk) clause accumulator through the
clause-membership matrix.  At ``p == P-1`` it bit-packs the accumulator
into uint32 words (little-endian, ``core.bitvector`` layout) with two
exact 16-bit f32 matmuls, ORs the clause words into the ingest load mask,
and accumulates per-clause popcounts, emitting all three outputs from the
same pass.

Predicate encoding (built once per plan by ``kernels.engine``):
  * ``keys  int32[P, Mk]`` / ``klens int32[P]`` — the pattern (simple) or
    the key pattern (key-value), zero-padded to the plan-wide max ``Mk``;
  * ``vals  int32[P, Mv]`` / ``vlens int32[P]`` — the value pattern
    (key-value only; zeros otherwise);
  * ``kinds int32[P]`` — 0 = simple any-position, 1 = key-value;
  * ``unbounded int32[P]`` — key-value degraded to unbounded suffix
    search (value pattern contains a delimiter);
  * ``membership int32[C, P]`` — clause c contains predicate p.

Padding rows (R padded up to R_blk) are masked via the dynamic ``n_valid``
scalar, so jit specializations key on the *bucketed* shape only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .substring_match import DELIM_BRACE, DELIM_COMMA

WORD_BITS = 32
_BIG = 1 << 30          # "no occurrence" sentinel for next-position scans


def _shl(x, pos, n, fill):
    """``x[j + n]`` along the byte axis (axis 0), ``fill`` past the end.

    ``n`` may be static or a traced scalar in ``[0, L)``.  One sublane
    rotation plus a select that restores the zero-fill semantics of a
    true shift (wrapped rows never leak into a window)."""
    L = x.shape[0]
    rolled = pltpu.roll(x, (L - n) % L, 0)
    return jnp.where(pos < L - n, rolled, fill)


def _window_eq(data, pos, pat_ref, p, m, max_len: int):
    """(L, R_blk) bool: window at byte j equals ``pat[p, :m]`` (dynamic m).

    Positions where the pattern is already exhausted (i >= m) stay valid,
    so one compilation serves every pattern length up to ``max_len``."""
    acc = data == pat_ref[p, 0]
    for i in range(1, max_len):
        eq = _shl(data, pos, i, 0) == pat_ref[p, i]
        acc = jnp.logical_and(acc, jnp.logical_or(eq, i >= m))
    return acc


def _next_at_or_after(flag, pos):
    """min{v >= j : flag[v]} per byte j (``_BIG`` if none): a log-step
    suffix-min scan over sublane rotations."""
    L = flag.shape[0]
    nxt = jnp.where(flag, pos, _BIG)
    step = 1
    while step < L:
        nxt = jnp.minimum(nxt, _shl(nxt, pos, step, _BIG))
        step *= 2
    return nxt


def _any_rows(x):
    """(L, R_blk) bool -> (1, R_blk) int32 'any over bytes'."""
    return jnp.max(x.astype(jnp.int32), axis=0, keepdims=True)


def _clause_bitvectors_kernel(
    key_ref, klen_ref, val_ref, vlen_ref, kind_ref, unb_ref, mem_ref, nv_ref,
    data_ref, bv_ref, or_ref, cnt_ref, acc_ref, *, max_key_len: int,
    max_val_len: int, n_clauses: int, r_blk: int,
):
    rb = pl.program_id(0)
    p = pl.program_id(1)
    n_p = pl.num_programs(1)

    @pl.when(p == 0)
    def _fresh_tile():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(p == 0, rb == 0))
    def _fresh_chunk():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    data = data_ref[...].astype(jnp.int32)     # (L, R_blk)
    pos = lax.broadcasted_iota(jnp.int32, data.shape, 0)
    mk = klen_ref[p]
    mv = vlen_ref[p]
    is_kv = kind_ref[p] > 0
    no_hit = jnp.zeros((1, r_blk), jnp.int32)

    def _eval_predicate():
        key_hit = _window_eq(data, pos, key_ref, p, mk, max_key_len)

        def _simple():
            return jnp.maximum(_any_rows(key_hit), (mk == 0).astype(jnp.int32))

        def _key_value():
            val_hit = _window_eq(data, pos, val_ref, p, mv, max_val_len)

            def _have_values():
                # a value occurrence at/after j inside j's delimiter
                # segment <=> the next usable value hit precedes the next
                # delimiter (unbounded search: no delimiters)
                delim = jnp.logical_and(
                    jnp.logical_or(data == DELIM_COMMA, data == DELIM_BRACE),
                    unb_ref[p] == 0,
                )
                nv = _next_at_or_after(
                    jnp.logical_and(val_hit, jnp.logical_not(delim)), pos)
                nd = _next_at_or_after(delim, pos)
                cond = (nv < nd).astype(jnp.int32)
                # value region starts mk bytes after the key (dynamic mk)
                region = _shl(cond, pos, mk, 0) > 0
                return _any_rows(jnp.logical_and(key_hit, region))

            # second prefilter: no value window in the tile -> no match,
            # skip the scans + shift (the expensive stages)
            return lax.cond(jnp.max(_any_rows(val_hit)) > 0,
                            _have_values, lambda: no_hit)

        return lax.cond(is_kv, _key_value, _simple)

    # first-character prefilter: the found/not-found cost asymmetry — a
    # tile with zero candidate windows skips the O(M) inner reduction.
    first = jnp.max(_any_rows(data == key_ref[p, 0]))
    hit = lax.cond(jnp.logical_or(first > 0, mk == 0),
                   _eval_predicate, lambda: no_hit)      # (1, R_blk)

    for c in range(n_clauses):                 # C is static
        acc_ref[c:c + 1, :] = acc_ref[c:c + 1, :] | (mem_ref[c, p] * hit)

    @pl.when(p == n_p - 1)
    def _emit():
        row = rb * r_blk + lax.broadcasted_iota(jnp.int32, (1, r_blk), 1)
        bits = acc_ref[...] * (row < nv_ref[0]).astype(jnp.int32)

        # pack 32 lanes per word as two exact f32 matmuls: weight 2^b on
        # bit b of each 16-bit half (every operand and partial sum is an
        # integer < 2^16, exact at any MXU precision)
        w_blk = r_blk // WORD_BITS
        r = lax.broadcasted_iota(jnp.int32, (r_blk, w_blk), 0)
        w = lax.broadcasted_iota(jnp.int32, (r_blk, w_blk), 1)
        b = r - w * WORD_BITS
        one = jnp.ones_like(b)
        lo_w = jnp.where((b >= 0) & (b < 16), one << jnp.clip(b, 0, 15), 0)
        hi_w = jnp.where((b >= 16) & (b < 32),
                         one << jnp.clip(b - 16, 0, 15), 0)
        bf = bits.astype(jnp.float32)
        lo = jnp.dot(bf, lo_w.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        hi = jnp.dot(bf, hi_w.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        words = lo.astype(jnp.int32) | (hi.astype(jnp.int32) << 16)
        bv_ref[0] = words                      # (C, w_blk)
        or_words = words[0:1, :]
        for c in range(1, n_clauses):
            or_words = or_words | words[c:c + 1, :]
        or_ref[0] = or_words

        cnt_ref[...] += jnp.sum(bits, axis=1, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("r_blk", "interpret")
)
def clause_bitvectors_fused(
    data: jnp.ndarray,        # uint8[R, L]    (R % r_blk == 0)
    keys: jnp.ndarray,        # int32[P, Mk]
    klens: jnp.ndarray,       # int32[P]
    vals: jnp.ndarray,        # int32[P, Mv]
    vlens: jnp.ndarray,       # int32[P]
    kinds: jnp.ndarray,       # int32[P]
    unbounded: jnp.ndarray,   # int32[P]
    membership: jnp.ndarray,  # int32[C, P]
    n_valid: jnp.ndarray,     # int32[1]
    *,
    r_blk: int = 256,
    interpret: bool = False,
):
    """(words uint32[C, R/32], or_words uint32[R/32], counts int32[C])."""
    R, L = data.shape
    P, Mk = keys.shape
    Mv = vals.shape[1]
    C = membership.shape[0]
    if R % r_blk or r_blk % 128:
        raise ValueError(f"R={R} not a multiple of r_blk={r_blk} "
                         f"(a multiple of 128)")
    nb = R // r_blk
    w_blk = r_blk // WORD_BITS
    kernel = functools.partial(
        _clause_bitvectors_kernel,
        max_key_len=Mk, max_val_len=Mv, n_clauses=C, r_blk=r_blk,
    )
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    words, or_words, counts = pl.pallas_call(
        kernel,
        grid=(nb, P),
        in_specs=[smem] * 8 + [
            pl.BlockSpec((L, r_blk), lambda rb, p: (0, rb)),  # record tile
        ],
        out_specs=[
            pl.BlockSpec((1, C, w_blk), lambda rb, p: (rb, 0, 0)),
            pl.BlockSpec((1, 1, w_blk), lambda rb, p: (rb, 0, 0)),
            pl.BlockSpec((C, 1), lambda rb, p: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, C, w_blk), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1, w_blk), jnp.int32),
            jax.ShapeDtypeStruct((C, 1), jnp.int32),
        ],
        scratch_shapes=[
            # clause accumulator for the current record tile
            pltpu.VMEM((C, r_blk), jnp.int32),
        ],
        interpret=interpret,
        name="clause_bitvectors_fused",   # the trace's name for the kernel
    )(keys, klens, vals, vlens, kinds, unbounded, membership, n_valid,
      data.T)
    words = jnp.transpose(words, (1, 0, 2)).reshape(C, R // WORD_BITS)
    return (lax.bitcast_convert_type(words, jnp.uint32),
            lax.bitcast_convert_type(or_words.reshape(-1), jnp.uint32),
            counts[:, 0])
