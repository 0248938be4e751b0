"""Device-resident scan plane (DESIGN.md §15): differential sweeps.

The contract under test everywhere: :class:`DeviceScanner` /
:class:`ShardedDeviceScanner` results are BIT-IDENTICAL to the host
``DataSkippingScanner`` / ``ShardedScanner`` — not just counts, but the
full accounting surface (rows_scanned / rows_skipped / raw_parsed /
segments_pruned and every per-(epoch, tier) group) — across backends
(xla / numpy reference / pallas interpret), shard counts (1 / 4 / 8),
mixed epochs and tiers, dictionary strings, NaN zone bounds, cache
eviction under a starved byte budget, and batched vs one-at-a-time
launches.  Plus the cache-plane residency contract (zero steady-state
uploads) and the ``kernels.residual`` pow2-bucket jit-cache pin.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.client import NumpyEngine, encode_chunk
from repro.core.device_scan import DeviceScanner, ShardedDeviceScanner
from repro.core.predicates import (
    Query, clause, exact, key_value, presence, substring,
)
from repro.core.server import (
    CiaoStore, DataSkippingScanner, PlanFamily, PushdownPlan, evolve_family,
)
from repro.core.shard import ShardedCiaoStore, ShardedScanner, ShardRouter
from repro.core.workload import estimate_selectivities
from repro.data.datasets import generate_records, predicate_pool

CHUNK = 256
N_RECORDS = 2048


def _accounting(r) -> tuple:
    return (r.count, r.rows_scanned, r.rows_skipped, r.raw_parsed,
            r.segments_pruned, r.shards_pruned, r.used_skipping,
            tuple(sorted(
                (k, (g.count, g.rows_scanned, g.rows_skipped, g.raw_parsed,
                     g.segments_pruned))
                for k, g in r.groups.items())))


@pytest.fixture(scope="module")
def ycsb():
    recs = generate_records("ycsb", N_RECORDS, seed=7)
    pool = predicate_pool("ycsb")
    sel = estimate_selectivities(pool, recs[:300])
    ranked = sorted(pool, key=lambda c: abs(sel[c] - 0.2))
    objs = [json.loads(r) for r in recs]
    return recs, objs, ranked


def _families(ranked):
    fam0 = PlanFamily(plan=PushdownPlan(clauses=ranked[:8]),
                      tier_sizes=(2, 4, 8))
    fam1 = evolve_family(fam0, ranked[:4] + ranked[8:12], (2, 4, 8))
    return fam0, fam1


def _build(store, recs, fam0, fam1, *, jit=True):
    """Mixed-epoch / mixed-tier ingest, replan at the halfway point."""
    eng = NumpyEngine()

    def ingest(lo, hi, epoch):
        fam = store.family
        for i, start in enumerate(range(lo, hi, CHUNK)):
            tier = i % fam.n_tiers
            chunk = encode_chunk(recs[start: start + CHUNK])
            bv = eng.eval_fused_prefix(chunk, fam.plan.clauses,
                                       fam.tier_sizes[tier])
            store.ingest_chunk(chunk, bv, epoch=epoch, tier=tier)

    half = (len(recs) // 2) // CHUNK * CHUNK
    ingest(0, half, epoch=0)
    store.advance_epoch(fam1)
    ingest(half, len(recs), epoch=1)
    if jit:
        store.jit_load_raw()   # promotions done up front -> scans idempotent
    return store


def _workload(fam0, fam1, ranked):
    qs = [Query((c,)) for c in fam0.plan.clauses[:3] + fam1.plan.clauses[:3]]
    qs += [Query((fam0.plan.clauses[0], ranked[13]))]   # pushed + residual
    qs += [Query((c,)) for c in ranked[14:17]]          # residual-only
    for v in (3, 55, 97, 250):                          # 250: no match
        qs.append(Query((clause(key_value("linear_score", v)),)))
    qs.append(Query((clause(key_value("phone_country", "ZZ")),)))
    return qs


# ---------------------------------------------------------------------------
# backend sweep, unsharded
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "numpy", "pallas_interpret"])
def test_device_backends_bit_identical_to_host(ycsb, backend):
    recs, objs, ranked = ycsb
    fam0, fam1 = _families(ranked)
    store = _build(CiaoStore(fam0, segment_capacity=512), recs, fam0, fam1)
    host = DataSkippingScanner(store, log_queries=False)
    dev = DeviceScanner(store, backend=backend, log_queries=False)
    queries = _workload(fam0, fam1, ranked)
    if backend == "pallas_interpret":
        queries = queries[:5]      # the interpreter walks the grid in python
    got = dev.scan_batch(queries)
    for q, r in zip(queries, got):
        oracle = sum(1 for o in objs if q.matches_exact(o))
        h = host.scan(q)
        assert r.count == oracle, q.describe()
        assert _accounting(r) == _accounting(h), q.describe()
    assert len(dev.cache.slots) >= 2      # the plane actually engaged


def test_batch_matches_one_at_a_time(ycsb):
    recs, objs, ranked = ycsb
    fam0, fam1 = _families(ranked)
    queries = _workload(fam0, fam1, ranked)
    # two identical stores: raw NOT promoted up front, so per-scan
    # promotion accounting must interleave identically in both orders
    a = _build(CiaoStore(fam0, segment_capacity=512), recs, fam0, fam1,
               jit=False)
    b = _build(CiaoStore(fam0, segment_capacity=512), recs, fam0, fam1,
               jit=False)
    batched = DeviceScanner(a, backend="xla",
                            log_queries=False).scan_batch(queries)
    sc = DeviceScanner(b, backend="xla", log_queries=False)
    singles = [sc.scan(q) for q in queries]
    for q, rb, rs in zip(queries, batched, singles):
        assert _accounting(rb) == _accounting(rs), q.describe()


def test_multi_query_batch_vs_host_with_raw_promotion(ycsb):
    """Un-promoted store: the batch's raw promotions and jit-segment
    visibility snapshots must reproduce a sequential host run exactly."""
    recs, objs, ranked = ycsb
    fam0, fam1 = _families(ranked)
    queries = _workload(fam0, fam1, ranked)
    a = _build(CiaoStore(fam0, segment_capacity=512), recs, fam0, fam1,
               jit=False)
    b = _build(CiaoStore(fam0, segment_capacity=512), recs, fam0, fam1,
               jit=False)
    dev_res = DeviceScanner(a, backend="xla",
                            log_queries=False).scan_batch(queries)
    host = DataSkippingScanner(b, log_queries=False)
    for q, r in zip(queries, dev_res):
        assert _accounting(r) == _accounting(host.scan(q)), q.describe()


# ---------------------------------------------------------------------------
# sharded sweeps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 4, 8])
def test_sharded_device_bit_identical(ycsb, n_shards):
    recs, objs, ranked = ycsb
    fam0, fam1 = _families(ranked)
    if n_shards > 1:
        router = ShardRouter(n_shards=n_shards, key="linear_score",
                             mode="hash")
    else:
        router = None
    s_host = _build(ShardedCiaoStore(fam0, router=router, n_shards=n_shards,
                                     segment_capacity=512),
                    recs, fam0, fam1)
    s_dev = _build(ShardedCiaoStore(fam0, router=router, n_shards=n_shards,
                                    segment_capacity=512),
                   recs, fam0, fam1)
    queries = _workload(fam0, fam1, ranked)
    dev = ShardedDeviceScanner(s_dev, backend="xla", log_queries=False)
    got = dev.scan_batch(queries)
    with ShardedScanner(s_host, log_queries=False) as sc:
        for q, r in zip(queries, got):
            oracle = sum(1 for o in objs if q.matches_exact(o))
            h = sc.scan(q)
            assert r.count == oracle, q.describe()
            assert _accounting(r) == _accounting(h), q.describe()
            assert list(r.groups) == sorted(r.groups)


def test_sharded_device_range_router_prunes_shards(ycsb):
    recs, objs, ranked = ycsb
    fam0, fam1 = _families(ranked)
    router = ShardRouter.from_samples(4, "linear_score", objs[:400])
    s_host = _build(ShardedCiaoStore(fam0, router=router,
                                     segment_capacity=512),
                    recs, fam0, fam1)
    s_dev = _build(ShardedCiaoStore(fam0, router=router,
                                    segment_capacity=512),
                   recs, fam0, fam1)
    queries = _workload(fam0, fam1, ranked)
    dev = ShardedDeviceScanner(s_dev, backend="xla", log_queries=False)
    got = dev.scan_batch(queries)
    pruned = 0
    with ShardedScanner(s_host, log_queries=False) as sc:
        for q, r in zip(queries, got):
            assert _accounting(r) == _accounting(sc.scan(q)), q.describe()
            pruned += r.shards_pruned
    assert pruned > 0   # partition metadata demonstrably engaged


# ---------------------------------------------------------------------------
# edge cases: empty store, all-pruned, dictionary strings, NaN bounds
# ---------------------------------------------------------------------------

def test_empty_store_and_all_pruned_segments(ycsb):
    recs, objs, ranked = ycsb
    fam0, fam1 = _families(ranked)
    empty = CiaoStore(fam0, segment_capacity=512)
    dev = DeviceScanner(empty, backend="xla", log_queries=False)
    r = dev.scan(Query((ranked[0],)))
    assert (r.count, r.rows_scanned, r.rows_skipped) == (0, 0, 0)
    # populated store, query whose zone maps refute EVERY segment: the
    # launch sees no active (query, slot) pair yet accounting still
    # matches the host's all-pruned path
    store = _build(CiaoStore(fam0, segment_capacity=512), recs, fam0, fam1)
    host = DataSkippingScanner(store, log_queries=False)
    dev = DeviceScanner(store, backend="xla", log_queries=False)
    q = Query((clause(key_value("linear_score", 250)),))
    got, want = dev.scan(q), host.scan(q)
    assert got.count == 0
    assert _accounting(got) == _accounting(want)
    assert got.segments_pruned == len(store.blocks) + len(store.jit_blocks)


def _tiny_plan(clauses):
    return PlanFamily(plan=PushdownPlan(clauses=tuple(clauses)),
                      tier_sizes=(len(clauses),))


def test_dictionary_strings_and_nan_zone_bounds():
    """Exotic dictionary strings + NaN numerics: the device dictionary
    codes and zone verdicts must reproduce host semantics exactly (a NaN
    among a key's values poisons numeric pruning; NaN equals nothing)."""
    objs = []
    words = ["par,is", "ab}c", "a b", "", "tokén", "zz"]
    for i in range(256):
        o = {"s": words[i % len(words)], "n": 10.0 * (i % 7)}
        if i % 5 == 0:
            o["n"] = float("nan")
        if i % 3 == 0:
            o["extra"] = "x%d" % (i % 4)
        objs.append(o)
    recs = [json.dumps(o).encode() for o in objs]
    cl = [clause(exact("s", "par,is")), clause(substring("s", "b"))]
    fam = _tiny_plan(cl)
    store = CiaoStore(fam, segment_capacity=128)
    eng = NumpyEngine()
    for start in range(0, len(recs), 64):
        chunk = encode_chunk(recs[start: start + 64])
        bv = eng.eval_fused_prefix(chunk, fam.plan.clauses, len(cl))
        store.ingest_chunk(chunk, bv, epoch=0, tier=0)
    store.jit_load_raw()
    host = DataSkippingScanner(store, log_queries=False)
    dev = DeviceScanner(store, backend="xla", log_queries=False)
    queries = [
        Query((clause(exact("s", "par,is")),)),
        Query((clause(exact("s", "")),)),
        Query((clause(substring("s", "b")),)),
        Query((clause(substring("s", "é")),)),
        Query((clause(presence("extra")),)),
        Query((clause(key_value("extra", "x1")),)),
        Query((clause(key_value("n", 30)),)),          # int vs 30.0 rows
        Query((clause(key_value("n", 30.0)),)),
        Query((clause(key_value("n", float("nan"))),)),  # matches nothing
        Query((clause(key_value("n", 7.5)),)),           # no match
    ]
    got = dev.scan_batch(queries)
    for q, r in zip(queries, got):
        oracle = sum(1 for o in objs if q.matches_exact(o))
        assert r.count == oracle, q.describe()
        assert _accounting(r) == _accounting(host.scan(q)), q.describe()


# ---------------------------------------------------------------------------
# cache residency: steady-state uploads, eviction under pressure
# ---------------------------------------------------------------------------

def test_steady_state_zero_uploads_and_ingest_resync(ycsb):
    recs, objs, ranked = ycsb
    fam0, fam1 = _families(ranked)
    store = _build(CiaoStore(fam0, segment_capacity=512), recs, fam0, fam1)
    twin = _build(CiaoStore(fam0, segment_capacity=512), recs, fam0, fam1)
    host = DataSkippingScanner(twin, log_queries=False)
    dev = DeviceScanner(store, backend="xla", log_queries=False)
    queries = _workload(fam0, fam1, ranked)
    dev.scan_batch(queries)
    warm = dev.cache.uploads
    assert warm > 0
    dev.scan_batch(queries)
    dev.scan_batch(queries[:4])
    assert dev.cache.uploads == warm      # plane resident: zero transfers
    # ingest invalidates the open tail -> resync, still bit-identical to
    # a sequential host run over a twin store with the same ingest
    eng = NumpyEngine()
    chunk = encode_chunk(recs[:CHUNK])
    bv = eng.eval_fused_prefix(chunk, store.family.plan.clauses,
                               store.family.tier_sizes[0])
    store.ingest_chunk(chunk, bv, epoch=1, tier=0)
    twin.ingest_chunk(chunk, bv, epoch=1, tier=0)
    for q, r in zip(queries, dev.scan_batch(queries)):
        assert _accounting(r) == _accounting(host.scan(q)), q.describe()
    assert dev.cache.uploads > warm


def test_cache_eviction_mid_sweep_stays_bit_identical(ycsb):
    recs, objs, ranked = ycsb
    fam0, fam1 = _families(ranked)
    store = _build(CiaoStore(fam0, segment_capacity=512), recs, fam0, fam1)
    host = DataSkippingScanner(store, log_queries=False)
    dev = DeviceScanner(store, backend="xla", byte_budget=200 << 10,
                        log_queries=False)
    queries = _workload(fam0, fam1, ranked)
    for q in queries:                     # one at a time: LRU churns
        assert _accounting(dev.scan(q)) == _accounting(host.scan(q)), \
            q.describe()
    assert dev.cache.evictions > 0        # budget demonstrably starved
    assert len(dev.cache.slots) >= 1      # but the plane never went dark
    # evicted segments fell back to the host path, accounted identically
    got = dev.scan_batch(queries)
    for q, r in zip(queries, got):
        assert _accounting(r) == _accounting(host.scan(q)), q.describe()


# ---------------------------------------------------------------------------
# kernels.residual: pow2 buckets pin the jit cache
# ---------------------------------------------------------------------------

def test_residual_pow2_buckets_pin_trace_count():
    from repro.kernels.residual import (
        _and_reduce, _popcount, bv_and_many_xla, popcount_xla,
    )
    from repro.core.bitvector import bv_and_many, popcount

    rng = np.random.default_rng(3)
    base_and = _and_reduce._cache_size()
    base_pop = _popcount._cache_size()
    buckets = set()
    for p in (1, 2, 3, 5, 8, 9, 13):
        for w in (1, 2, 6, 7, 16, 17):
            words = rng.integers(0, 2**32, (p, w), dtype=np.uint32)
            assert np.array_equal(bv_and_many_xla(words),
                                  bv_and_many(words))
            got = popcount_xla(words[0])
            assert got == popcount(words[0])
            buckets.add((1 << (p - 1).bit_length(),
                         1 << (w - 1).bit_length()))
    grown_and = _and_reduce._cache_size() - base_and
    grown_pop = _popcount._cache_size() - base_pop
    # the AND cache grows with DISTINCT pow2 buckets, not with the 42
    # raw shapes; popcount flattens, so it grows with row buckets only
    assert 0 < grown_and <= len(buckets)
    assert 0 < grown_pop <= len({b[0] * b[1] for b in buckets}) + 1
    # replaying every shape mints no new traces
    for p in (3, 9, 13):
        for w in (6, 17):
            bv_and_many_xla(rng.integers(0, 2**32, (p, w), dtype=np.uint32))
    assert _and_reduce._cache_size() - base_and == grown_and


# ---------------------------------------------------------------------------
# packed launch: one parameter upload, one result fetch
# ---------------------------------------------------------------------------

P_KEYS, P_ROWS, P_TERMS, P_CLAUSES, P_LUT = 8, 256, 8, 4, 64


def _synthetic_launch(n_queries: int, n_slots: int, substring: bool):
    """A random plane and parameter tables of valid ranges: every kind
    (substring terms only where asked), pushed words with bits in both
    16-bit halves, padding rows and slots."""
    from repro.kernels.scan_fused import (
        KIND_EXACT, KIND_KV, KIND_PRESENCE, KIND_SUBSTRING,
        DevicePlaneArrays, ScanParams,
    )
    rng = np.random.default_rng([n_queries, n_slots, int(substring)])
    K, N, T, C, L = P_KEYS, P_ROWS, P_TERMS, P_CLAUSES, P_LUT
    Q, S1 = n_queries, n_slots
    pick = [KIND_PRESENCE, KIND_EXACT, KIND_KV]
    kinds = rng.choice(pick + [KIND_SUBSTRING] * 2 * substring, T)
    if substring:
        kinds[:2] = KIND_SUBSTRING
    kinds[-1] = -1                                   # a padding row
    import jax.numpy as jnp
    u8 = lambda shape: rng.integers(0, 2, shape).astype(np.uint8)  # noqa: E731
    plane = DevicePlaneArrays(
        *(jnp.asarray(u8((K, N))) for _ in range(4)),
        jnp.asarray(rng.integers(-1, 6, (K, N)).astype(np.int32)),
        jnp.asarray(rng.integers(-1, 6, (K, N)).astype(np.int32)),
        jnp.asarray(rng.integers(-1, S1 - 1, N).astype(np.int32)),
        jnp.asarray(rng.integers(0, 2**32, N, dtype=np.uint32)))
    pushed = np.zeros((Q, S1), np.uint32)
    for bit in (0, 5, 17, 31):
        pushed |= (rng.random((Q, S1)) < 0.3).astype(np.uint32) << \
            np.uint32(bit)
    params = ScanParams(
        key_ids=rng.integers(0, K, T).astype(np.int32),
        kinds=kinds.astype(np.int32),
        code_a=rng.integers(-2, 6, (T, S1)).astype(np.int32),
        num_codes=rng.integers(-2, 6, (T, 3, S1)).astype(np.int32),
        lut_off=rng.integers(-1, L - 8, (T, S1)).astype(np.int32),
        lut_flat=u8(L), is_null=u8(T), is_boolv=u8(T),
        membership=u8((C, T)), query_clause=u8((Q, C)),
        pushed_tbl=pushed, active=u8((Q, S1)))
    return plane, params


def _tables_one_by_one(params, n_keys: int) -> tuple:
    """The parameter tables of ``_scan_core_pallas`` built one at a time,
    each in its own dtype: what the packed buffer has to carry."""
    from repro.kernels.scan_fused import KIND_SUBSTRING, bucket_pow2
    T = params.kinds.shape[0]
    f32 = np.float32
    keym = np.zeros((T, n_keys), f32)
    keym[np.arange(T), params.key_ids] = 1.0
    sub_rows = np.flatnonzero(params.kinds == KIND_SUBSTRING)
    Ts = bucket_pow2(len(sub_rows))
    subsel = np.zeros((T, Ts), f32)
    subsel[sub_rows, np.arange(len(sub_rows))] = 1.0
    sub_keys = np.zeros((Ts,), np.int32)
    sub_keys[:len(sub_rows)] = params.key_ids[sub_rows]
    sub_off = np.full((Ts, params.lut_off.shape[1]), -1, np.int32)
    sub_off[:len(sub_rows)] = params.lut_off[sub_rows]
    col = lambda a: a.astype(np.int32).reshape(-1, 1)  # noqa: E731
    return (
        keym, col(params.kinds), params.code_a.astype(f32),
        params.num_codes.transpose(1, 0, 2).astype(f32),
        sub_keys, sub_off, subsel, params.lut_flat,
        col(params.is_null), col(params.is_boolv),
        params.membership.astype(f32), params.query_clause.astype(f32),
        (params.pushed_tbl & np.uint32(0xFFFF)).astype(f32),
        (params.pushed_tbl >> np.uint32(16)).astype(f32),
        params.active.astype(f32),
    )


@pytest.mark.parametrize("substring", [False, True])
@pytest.mark.parametrize("n_slots", [2, 64])
@pytest.mark.parametrize("n_queries", [1, 8])
def test_packed_launch_bit_identical(n_queries, n_slots, substring):
    """The packed ``pallas_interpret`` launch against the unpacked kernel
    call and the numpy reference: the same counts and cands, bit for bit,
    from one parameter transfer and one fetch."""
    import jax.numpy as jnp
    from repro.kernels.scan_fused import (
        LaunchCounters, _scan_core_pallas, scan_core_numpy, scan_counts,
    )
    plane, params = _synthetic_launch(n_queries, n_slots, substring)
    want = scan_core_numpy(*(np.asarray(a) for a in plane), params)
    counters = LaunchCounters()
    packed = scan_counts(plane, params, backend="pallas_interpret",
                         r_blk=128, counters=counters)
    unpacked = _scan_core_pallas(
        *plane, *(jnp.asarray(a) for a in _tables_one_by_one(params, P_KEYS)),
        r_blk=128, interpret=True)
    for got, one_by_one, ref in zip(packed, unpacked, want):
        assert got.dtype == np.int32
        assert got.shape == (n_queries, n_slots)
        np.testing.assert_array_equal(got, np.asarray(one_by_one))
        np.testing.assert_array_equal(got, ref)
    assert want[1].sum() > 0                  # candidates were counted
    assert (counters.launches, counters.param_transfers,
            counters.fetches) == (1, 1, 1)
    assert counters.param_bytes > 0


@pytest.mark.parametrize("substring", [False, True])
def test_pack_layout_round_trips_every_table(substring):
    """Every table comes back out of the packed buffer, on the host (the
    SPMD launch's per-table path) and on the device, with its own bytes
    and dtype; the layout is static, hashable and leaves no gap."""
    import jax.numpy as jnp
    from repro.kernels.scan_fused import (
        pack_scan_args, pallas_scan_args, unpack_scan_args,
    )
    _, params = _synthetic_launch(8, 64, substring)
    want = _tables_one_by_one(params, P_KEYS)
    buf, layout = pack_scan_args(params, P_KEYS)
    assert buf.dtype == np.int32 and buf.ndim == 1
    assert hash(layout) == hash(pack_scan_args(params, P_KEYS)[1])
    ends = [off + int(np.prod(shape)) for off, shape, _ in layout]
    assert [off for off, _, _ in layout] == [0] + ends[:-1]
    assert ends[-1] == buf.size
    host = pallas_scan_args(params, P_KEYS)
    device = unpack_scan_args(jnp.asarray(buf), layout)
    assert len(want) == len(host) == len(device) == len(layout)
    for w, h, d in zip(want, host, device):
        for got in (h, np.asarray(d)):
            assert got.dtype == w.dtype and got.shape == w.shape
            assert got.tobytes() == w.tobytes()


def test_device_scanner_one_transfer_and_fetch_per_launch(ycsb):
    """Each launch of a ``DeviceScanner`` uploads its parameters once and
    fetches its results once, and queries in the same shape buckets
    reuse one trace of the packed program."""
    from repro.kernels.scan_fused import _scan_core_pallas_packed
    recs, objs, ranked = ycsb
    fam0, fam1 = _families(ranked)
    store = _build(CiaoStore(fam0, segment_capacity=512), recs, fam0, fam1)
    host = DataSkippingScanner(store, log_queries=False)
    dev = DeviceScanner(store, backend="pallas_interpret", log_queries=False)
    queries = [Query((clause(key_value("linear_score", v)),))
               for v in (3, 55, 97)]
    base = _scan_core_pallas_packed._cache_size()
    traced = []
    for q in queries:
        assert _accounting(dev.scan(q)) == _accounting(host.scan(q)), \
            q.describe()
        traced.append(_scan_core_pallas_packed._cache_size() - base)
    assert traced[0] <= 1 and traced == [traced[0]] * len(queries)
    stats = dev.plane_stats()
    assert stats["launches"] == len(queries)
    assert stats["param_transfers"] == stats["launches"]
    assert stats["fetches"] == stats["launches"]
    assert stats["param_bytes"] > 0


# ---------------------------------------------------------------------------
# SPMD shard_map path (subprocess: 4 host devices)
# ---------------------------------------------------------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _spmd_subprocess(backend: str, n_queries: int) -> dict:
    """SPMD vs sequential sharded device scans on 4 host devices."""
    code = textwrap.dedent(f"""
        import json
        import numpy as np
        import jax
        assert len(jax.devices()) == 4
        from tests.test_device_scan import (
            _accounting, _build, _families, _workload,
        )
        from repro.core import device_scan
        from repro.core.device_scan import ShardedDeviceScanner
        from repro.core.server import CiaoStore, DataSkippingScanner
        from repro.core.shard import ShardedCiaoStore, ShardRouter
        from repro.core.workload import estimate_selectivities
        from repro.data.datasets import generate_records, predicate_pool

        recs = generate_records("ycsb", 2048, seed=7)
        pool = predicate_pool("ycsb")
        sel = estimate_selectivities(pool, recs[:300])
        ranked = sorted(pool, key=lambda c: abs(sel[c] - 0.2))
        fam0, fam1 = _families(ranked)
        router = ShardRouter(n_shards=4, key="linear_score", mode="hash")
        s_dev = _build(ShardedCiaoStore(fam0, router=router,
                                        segment_capacity=512),
                       recs, fam0, fam1)
        s_seq = _build(ShardedCiaoStore(fam0, router=router,
                                        segment_capacity=512),
                       recs, fam0, fam1)
        queries = _workload(fam0, fam1, ranked)[:{n_queries}]
        spmd = ShardedDeviceScanner(s_dev, backend="{backend}",
                                    log_queries=False, spmd=True)
        seq = ShardedDeviceScanner(s_seq, backend="{backend}",
                                   log_queries=False, spmd=False)
        a = spmd.scan_batch(queries)
        b = seq.scan_batch(queries)
        same = all(_accounting(x) == _accounting(y) for x, y in zip(a, b))
        # each shard's plane stays on its own device
        homes = [c.plane.pres.devices() for c in spmd.caches]
        print(json.dumps({{
            "same": same, "n": len(a),
            "homes": len(set().union(*homes)),
            "one_home_each": all(len(h) == 1 for h in homes),
            "spmd_programs": device_scan._spmd_program.cache_info().misses,
        }}))
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + os.path.join(SRC, "..")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_spmd_shard_map_bit_identical_subprocess():
    payload = _spmd_subprocess("xla", 8)
    assert payload == {"same": True, "n": 8, "homes": 4,
                       "one_home_each": True, "spmd_programs": 1}


def test_spmd_pallas_interpret_bit_identical_subprocess():
    """The compiled-kernel backend's SPMD launch (interpreted here) over
    per-device shard planes matches the sequential per-shard launches."""
    payload = _spmd_subprocess("pallas_interpret", 4)
    assert payload == {"same": True, "n": 4, "homes": 4,
                       "one_home_each": True, "spmd_programs": 1}
