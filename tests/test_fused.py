"""Fused single-pass pushdown pipeline: engine equivalence + launch count.

The engine-equivalence contract (DESIGN.md §4): every engine — the
paper-faithful bytes.find oracle, the vectorized numpy engine, the jnp
oracle, the Pallas kernel in interpret mode, and the fused single-launch
path they all back — must produce bit-identical packed bitvectors, load
masks, and popcounts, and must never produce a false negative w.r.t. exact
semantics on the parsed record.
"""
import json

import numpy as np
import pytest

from repro.core import bitvector
from repro.core.client import NumpyEngine, PythonEngine, encode_chunk
from repro.core.predicates import (
    Clause, SimplePredicate, clause, exact, key_value, presence, substring,
)
from repro.kernels.engine import KernelEngine, compile_plan

BACKENDS = ("xla", "pallas_interpret")

_KEYS = ["name", "age", "tags", "city", "note"]
_WORDS = ["bob", "ann", "x", "par,is", "ab}c", "tok", "zz", "a b"]


def _random_record(rng) -> dict:
    obj = {}
    for k in _KEYS:
        if rng.random() < 0.4:
            continue
        r = rng.random()
        if r < 0.35:
            obj[k] = int(rng.integers(0, 30))
        elif r < 0.7:
            n = int(rng.integers(1, 4))
            obj[k] = " ".join(_WORDS[int(i)] for i in rng.integers(0, len(_WORDS), n))
        elif r < 0.85:
            obj[k] = bool(rng.integers(0, 2))
        else:
            obj[k] = None
    return obj


def _random_term(rng) -> SimplePredicate:
    k = _KEYS[int(rng.integers(0, len(_KEYS)))]
    kind = int(rng.integers(0, 4))
    if kind == 0:
        return exact(k, _WORDS[int(rng.integers(0, len(_WORDS)))])
    if kind == 1:
        return substring(k, _WORDS[int(rng.integers(0, len(_WORDS)))])
    if kind == 2:
        return presence(k)
    r = rng.random()
    if r < 0.4:
        return key_value(k, int(rng.integers(0, 30)))
    if r < 0.6:
        return key_value(k, bool(rng.integers(0, 2)))
    # delimiter-containing values exercise the unbounded degradation
    return key_value(k, _WORDS[int(rng.integers(0, len(_WORDS)))])


def _random_clauses(rng, n: int) -> list[Clause]:
    out = []
    for _ in range(n):
        terms = tuple(_random_term(rng) for _ in range(int(rng.integers(1, 4))))
        out.append(Clause(terms))
    return out


@pytest.mark.parametrize("seed", range(6))
def test_differential_all_engines_bit_identical(seed):
    """Random chunks x random clause sets: all engines, same packed bits."""
    rng = np.random.default_rng(1000 + seed)
    objs = [_random_record(rng) for _ in range(24)]
    recs = [json.dumps(o, separators=(",", ":")).encode() for o in objs]
    chunk = encode_chunk(recs)
    clauses = _random_clauses(rng, int(rng.integers(2, 7)))

    ref_engine = PythonEngine()
    expected_fused = ref_engine.eval_fused(chunk, clauses)
    engines = [NumpyEngine()] + [KernelEngine(backend=b) for b in BACKENDS]
    for eng in engines:
        fused = eng.eval_fused(chunk, clauses)
        assert np.array_equal(fused.words, expected_fused.words), eng.name
        assert np.array_equal(fused.or_words, expected_fused.or_words), eng.name
        assert np.array_equal(fused.counts, expected_fused.counts), eng.name
        assert fused.n_records == chunk.n_records
        # packed path must agree with the fused words exactly
        assert np.array_equal(eng.eval_packed(chunk, clauses), fused.words)

    # THE invariant (paper §IV-B): exact match on the parsed record
    # implies the client bit is set — false positives allowed, false
    # negatives never.
    bits = bitvector.unpack(expected_fused.words, chunk.n_records)
    for ci, cl in enumerate(clauses):
        for ri, obj in enumerate(objs):
            if cl.matches_exact(obj):
                assert bits[ci, ri], (cl.describe(), obj)


@pytest.mark.parametrize("backend", BACKENDS)
def test_multi_block_accumulation(backend):
    """Several record tiles per chunk: pack, load-mask OR and popcount
    accumulate correctly across grid blocks (and the word slice drops the
    padding tile)."""
    rng = np.random.default_rng(5)
    objs = [_random_record(rng) for _ in range(300)]
    recs = [json.dumps(o, separators=(",", ":")).encode() for o in objs]
    chunk = encode_chunk(recs)
    clauses = _random_clauses(rng, 5)
    expected = PythonEngine().eval_fused(chunk, clauses)
    # record tiles are lane-dense: r_blk is a multiple of 128
    eng = KernelEngine(backend=backend, r_blk=128)  # 300 -> 3 tiles of 128
    fused = eng.eval_fused(chunk, clauses)
    assert np.array_equal(fused.words, expected.words)
    assert np.array_equal(fused.or_words, expected.or_words)
    assert np.array_equal(fused.counts, expected.counts)


def test_empty_patterns_engines_agree():
    """Empty substring / empty key-value value: match-all / key-presence
    semantics, bit-identical across ALL engines (regression: NumpyEngine
    returned all-False for zero-length patterns)."""
    chunk = encode_chunk([b'{"note":"hi","age":3}', b'{"age":4}'])
    cls = [clause(substring("note", "")), clause(key_value("note", ""))]
    expected = PythonEngine().eval(chunk, cls)
    assert expected[0].all()          # empty substring matches everything
    assert expected[1].tolist() == [True, False]  # '"note"' presence
    for eng in [NumpyEngine()] + [KernelEngine(backend=b) for b in BACKENDS]:
        assert np.array_equal(eng.eval(chunk, cls), expected), eng.name


def test_ops_clause_bitvectors_empty_plan():
    """The public kernels.clause_bitvectors handles degenerate inputs."""
    from repro.kernels import clause_bitvectors
    from repro.kernels.plan import compile_plan as cp

    data = encode_chunk([b'{"a":1}']).data
    for backend in BACKENDS:
        words, or_words, counts = clause_bitvectors(
            data, cp([]), backend=backend)
        assert words.shape == (0, 1) and counts.shape == (0,)
        assert not or_words.any()
        words, or_words, counts = clause_bitvectors(
            np.zeros((0, 128), np.uint8), cp([clause(presence("a"))]),
            backend=backend)
        assert words.shape == (1, 0) and or_words.shape == (0,)
        assert counts.tolist() == [0]


def test_ingest_mismatch_leaves_stats_untouched():
    """A rejected ingest must not corrupt n_records / selectivities."""
    from repro.core.server import CiaoStore, PushdownPlan

    clauses = [clause(presence("age"))]
    store = CiaoStore(PushdownPlan(clauses=clauses))
    eng = KernelEngine(backend="xla")
    good = encode_chunk([b'{"age":1}', b'{"age":2}'])
    store.ingest_chunk(good, eng.eval_fused(good, clauses))
    before = (store.stats.n_records, store.clause_counts.copy())
    with pytest.raises(ValueError):
        store.ingest_chunk(encode_chunk([b'{"x":0}']),
                           eng.eval_fused(good, clauses))
    # clause-dimension mismatch (stale client plan), both ingest forms
    stale = [clause(presence("age")), clause(presence("x"))]
    with pytest.raises(ValueError):
        store.ingest_chunk(good, eng.eval_fused(good, stale))
    with pytest.raises(ValueError):
        store.ingest_chunk(good, eng.eval_packed(good, stale))
    # raw-array word width covering a different record count
    short = encode_chunk([b'{"age":%d}' % i for i in range(40)])
    with pytest.raises(ValueError):
        store.ingest_chunk(short, eng.eval_packed(good, clauses))
    assert store.stats.n_records == before[0]
    assert np.array_equal(store.clause_counts, before[1])


def test_wide_record_stride_no_false_negative():
    """Strides past the int16 sentinel must not wrap the position scan.

    Regression: the xla oracle's value-confinement scan uses int16
    positions for normal chunks; a record wider than 0x7FFF bytes must
    fall back to int32 (a wrapped iota made a key-value match near the
    record end a FALSE NEGATIVE — forbidden)."""
    tail = b'"name":"bob","age":7}'
    rec = b'{"pad":"' + b"x" * 33000 + b'",' + tail
    chunk = encode_chunk([rec, b'{"age":8}'])
    assert chunk.stride > 0x7FFF
    clauses = [clause(key_value("age", 7))]
    expected = PythonEngine().eval(chunk, clauses)
    assert expected[0, 0]  # the match near the record end must be found
    for b in BACKENDS:
        out = KernelEngine(backend=b).eval(chunk, clauses)
        assert np.array_equal(out, expected), b


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_edge_cases(backend):
    eng = KernelEngine(backend=backend)
    recs = [b'{"a":1}', b'{"b":2}']
    chunk = encode_chunk(recs)
    # empty plan — every protocol method, including unpack-based eval
    # (regression: bitvector.unpack crashed reshaping (0, W) words)
    fused = eng.eval_fused(chunk, [])
    assert fused.words.shape == (0, 1)
    assert fused.or_words.shape == (1,)
    assert not fused.or_words.any()
    assert eng.eval(chunk, []).shape == (0, 2)
    assert eng.eval_packed(chunk, []).shape == (0, 1)
    # empty chunk
    empty = encode_chunk([])
    fused = eng.eval_fused(empty, [clause(presence("a"))])
    assert fused.words.shape == (1, 0)
    assert fused.counts.tolist() == [0]


def test_compile_plan_dedups_shared_disjuncts():
    """A disjunct shared by several clauses occupies ONE predicate slot."""
    shared = substring("note", "tok")
    cls = [clause(shared, presence("age")), clause(shared),
           clause(shared, key_value("age", 7))]
    plan = compile_plan(cls)
    assert plan.n_preds == 3  # shared, presence, key_value — not 5
    assert plan.membership.shape == (3, 3)
    assert plan.membership.sum() == 5
    assert plan.kinds.sum() == 1  # exactly one key-value predicate


def test_numpy_engine_dedups_evaluation(monkeypatch):
    """NumpyEngine evaluates a shared disjunct once per chunk, not per clause."""
    from repro.core import client as client_mod

    calls = []
    real = client_mod.eval_simple

    def counting(data, pred, **kw):
        calls.append(pred)
        return real(data, pred, **kw)

    monkeypatch.setattr(client_mod, "eval_simple", counting)
    shared = substring("note", "tok")
    cls = [clause(shared), clause(shared, presence("age")), clause(shared)]
    chunk = encode_chunk([b'{"note":"a tok b","age":3}', b'{"note":"x"}'])
    out = NumpyEngine().eval(chunk, cls)
    assert len(calls) == 2  # shared + presence, despite 3 clauses
    assert np.array_equal(out, PythonEngine().eval(chunk, cls))


@pytest.mark.parametrize("backend", BACKENDS)
def test_single_kernel_launch_per_chunk(backend, monkeypatch):
    """The whole plan — simple AND key-value mixed — is ONE pallas_call.

    Counted at trace time: a fresh (plan, chunk-bucket) specialization must
    stage exactly one kernel launch for the pallas backend and exactly zero
    host round-trips in between (the xla oracle stages none).  Repeat
    evaluations hit the jit cache: zero further launches.
    """
    from jax.experimental import pallas as pl

    from repro.kernels import fused as fused_mod

    counted = []
    real = pl.pallas_call

    def counting(*args, **kwargs):
        counted.append(kwargs.get("grid"))
        return real(*args, **kwargs)

    monkeypatch.setattr(fused_mod.pl, "pallas_call", counting)

    rng = np.random.default_rng(7)
    # unique record count/stride so no previous jit specialization matches
    objs = [_random_record(rng) for _ in range(41)]
    recs = [json.dumps(o, separators=(",", ":")).encode() for o in objs]
    chunk = encode_chunk(recs)
    # mixed plan: simple patterns + several distinct key-value pairs
    clauses = [
        clause(exact("name", "bob"), key_value("age", 7)),
        clause(key_value("age", 11)),
        clause(substring("note", "zz"), key_value("city", 3)),
        clause(presence("tags")),
    ]
    eng = KernelEngine(backend=backend)
    out1 = eng.eval_fused(chunk, clauses)
    n_trace = len(counted)
    if backend == "pallas_interpret":
        assert n_trace == 1, f"expected ONE fused launch, traced {n_trace}"
    else:
        assert n_trace == 0  # xla oracle: no pallas at all
    out2 = eng.eval_fused(chunk, clauses)
    assert len(counted) == n_trace, "re-evaluation must reuse the jit cache"
    assert np.array_equal(out1.words, out2.words)
    expected = PythonEngine().eval_fused(chunk, clauses)
    assert np.array_equal(out1.words, expected.words)


@pytest.mark.parametrize("seed", range(3))
def test_differential_post_replan_plan_bit_identical(seed):
    """Engine equivalence must hold PER EPOCH: after a replan evolves the
    plan (dropped + surviving + fresh clauses, new local row order), every
    engine still produces bit-identical packed bitvectors for the new
    epoch's clause list."""
    from repro.core.server import PushdownPlan, evolve_plan

    rng = np.random.default_rng(4000 + seed)
    objs = [_random_record(rng) for _ in range(24)]
    recs = [json.dumps(o, separators=(",", ":")).encode() for o in objs]
    chunk = encode_chunk(recs)
    clauses0 = _random_clauses(rng, 5)
    plan0 = PushdownPlan(clauses=clauses0)
    # replan: drop two, keep three (shuffled rows), push two fresh clauses
    survivors = [clauses0[4], clauses0[1], clauses0[2]]
    plan1 = evolve_plan(plan0, survivors + _random_clauses(rng, 2))
    assert plan1.remap_from(plan0).tolist()[:3] == [4, 1, 2]

    expected = PythonEngine().eval_fused(chunk, plan1.clauses)
    engines = [NumpyEngine()] + [KernelEngine(backend=b) for b in BACKENDS]
    for eng in engines:
        fused = eng.eval_fused(chunk, plan1.clauses)
        assert np.array_equal(fused.words, expected.words), eng.name
        assert np.array_equal(fused.or_words, expected.or_words), eng.name
        assert np.array_equal(fused.counts, expected.counts), eng.name


def test_hot_swap_same_bucket_epoch_no_retrace(monkeypatch):
    """A replan whose compiled plan lands in the SAME (P, Mk, Mv) shape
    bucket must not retrace the fused kernel (epoch hot-swap without
    jit-thrash): only the first epoch's evaluation stages a pallas_call."""
    from jax.experimental import pallas as pl

    from repro.core.server import PushdownPlan, evolve_plan
    from repro.kernels import fused as fused_mod

    counted = []
    real = pl.pallas_call

    def counting(*args, **kwargs):
        counted.append(kwargs.get("grid"))
        return real(*args, **kwargs)

    monkeypatch.setattr(fused_mod.pl, "pallas_call", counting)

    rng = np.random.default_rng(11)
    # unique record count so no previous jit specialization matches
    objs = [_random_record(rng) for _ in range(37)]
    recs = [json.dumps(o, separators=(",", ":")).encode() for o in objs]
    chunk = encode_chunk(recs)
    plan0 = PushdownPlan(clauses=[
        clause(key_value("age", 7)), clause(presence("tags")),
    ])
    # same predicate count, same key, value in the same 8-byte width
    # bucket -> identical compiled shapes, different constants
    plan1 = evolve_plan(plan0, [
        clause(key_value("age", 23)), clause(presence("city")),
    ])
    eng = KernelEngine(backend="pallas_interpret")
    out0 = eng.eval_fused(chunk, plan0.clauses)
    n_trace = len(counted)
    assert n_trace <= 1  # one fresh specialization at most
    out1 = eng.eval_fused(chunk, plan1.clauses)
    assert len(counted) == n_trace, "same-bucket epoch swap retraced"
    expected0 = PythonEngine().eval_fused(chunk, plan0.clauses)
    expected1 = PythonEngine().eval_fused(chunk, plan1.clauses)
    assert np.array_equal(out0.words, expected0.words)
    assert np.array_equal(out1.words, expected1.words)


def test_server_ingest_consumes_fused_outputs():
    """CiaoStore accepts ChunkBitvectors directly (no host OR re-reduce)."""
    from repro.core.server import CiaoStore, PushdownPlan

    rng = np.random.default_rng(3)
    objs = [_random_record(rng) for _ in range(60)]
    recs = [json.dumps(o, separators=(",", ":")).encode() for o in objs]
    chunk = encode_chunk(recs)
    clauses = _random_clauses(rng, 4)
    plan = PushdownPlan(clauses=clauses)
    eng = KernelEngine(backend="xla")

    s1 = CiaoStore(plan)
    s1.ingest_chunk(chunk, eng.eval_fused(chunk, plan.clauses))
    s2 = CiaoStore(plan)
    s2.ingest_chunk(chunk, eng.eval_packed(chunk, plan.clauses))
    assert s1.stats.n_loaded == s2.stats.n_loaded
    assert sum(b.n_rows for b in s1.blocks) == sum(b.n_rows for b in s2.blocks)
    for b1, b2 in zip(s1.blocks, s2.blocks):
        assert b1.rows == b2.rows
        assert np.array_equal(b1.bitvectors, b2.bitvectors)
    # per-clause popcounts feed the store's observed selectivities,
    # identically for the fused and the raw-array ingest path
    exact_counts = PythonEngine().eval(chunk, clauses).sum(axis=1)
    assert np.array_equal(s1.clause_counts, exact_counts)
    assert np.array_equal(s2.clause_counts, exact_counts)
    assert np.allclose(
        s1.observed_selectivities(), exact_counts / chunk.n_records)
    # n_records mismatch is rejected
    other = encode_chunk(recs[:10])
    with pytest.raises(ValueError):
        s1.ingest_chunk(other, eng.eval_fused(chunk, plan.clauses))