"""The benchmark's generators, pools, query mixes and plain reference
against the program's own definitions."""
import json

import numpy as np
import pytest

from bench import reference, sut, workload
from repro.core.predicates import Query, clause, exact, key_value
from repro.core.workload import generate_workload
from repro.data import datasets

DATASETS = ("ycsb", "winlog")


@pytest.mark.parametrize("name", DATASETS)
def test_generator_keeps_the_program_schema(name):
    """Every key a predicate names has the program's types; WinLog keeps
    the program's keys, in order."""
    ds = sut.dataset(name)
    mine = [json.loads(r) for r in ds.generate(300, 7).json()]
    rng = np.random.default_rng(7)
    theirs = [datasets._GENERATORS[name](rng) for _ in range(300)]
    assert all(list(o) == list(mine[0]) for o in mine)
    for key in {c[1] for c in ds.pool()}:
        assert {type(o[key]) for o in mine} == {type(o[key]) for o in theirs}
    if name == "winlog":
        assert list(mine[0]) == list(theirs[0])


def test_ycsb_value_domains():
    objs = [json.loads(r) for r in sut.dataset("ycsb").generate(2000, 3)
            .json()]
    for o in objs:
        assert 0 <= o["linear_score"] < 100 and 0 <= o["age_by_group"] < 100
        site, dom = o["url_site"].split(".")[1:]
        assert o["url_domain"] == dom
        assert o["email"].endswith(f"@{site}.{dom}")
        assert o["first_name"].lower() == o["email"].split("@")[0].rstrip(
            "0123456789")
        assert o["url"].startswith(f"{o['url_protocol']}://{o['url_site']}/")
    share = np.mean([o["phone_country"] == "US" for o in objs])
    assert 0.45 < share < 0.55


def test_ycsb_documents_are_nested_at_fakeit_width():
    table = sut.dataset("ycsb").generate(2000, 4)
    records = table.json()
    assert 1100 < np.mean([len(r) for r in records]) < 1500
    assert table.nested == {"devices", "linked_devices", "address",
                            "children", "visited_places", "order_list"}
    for o in map(json.loads, records):
        assert set(o["address"]) == {"street", "city", "zip", "country",
                                     "prev_address"}
        assert 1 <= len(o["devices"]) <= 3 and len(o["linked_devices"]) <= 2
        assert all(set(c) == {"first_name", "gender", "age"}
                   for c in o["children"]) and len(o["children"]) <= 4
        assert all(1 <= len(p["cities"]) <= 3 for p in o["visited_places"])
        assert 1 <= len(o["order_list"]) <= 4


def test_winlog_value_domains():
    records = sut.dataset("winlog").generate(2000, 3).json()
    objs = [json.loads(r) for r in records]
    for o in objs:
        assert len(o["time"]) == 23 and o["time"].startswith("2016-")
        assert o["level"] in ("Info", "Warning", "Error")
        assert any(o["info"].startswith(t.split("{n}")[0])
                   for t in datasets._LOG_TEMPLATES)
    # Loghub's Windows log: 26.09 GB over 114,608,388 lines
    text = np.mean([sum(map(len, o.values())) for o in objs])
    assert abs(text / (26.09e9 / 114_608_388) - 1) < 0.1


@pytest.mark.parametrize("name", DATASETS)
def test_json_rows_equal_their_columns(name):
    table = sut.dataset(name).generate(500, 2**31 + 3)
    for i, r in enumerate(table.json()):
        o = json.loads(r)
        for key, col in table.cols.items():
            if key in table.nested:
                v = json.loads(col[i])
            elif col.dtype.kind == "S":
                v = col[i].decode()
            else:
                v = col[i].item()
            assert o[key] == v and type(o[key]) is type(v)


@pytest.mark.parametrize("name", DATASETS)
def test_same_seed_same_records(name):
    ds = sut.dataset(name)
    assert ds.generate(64, 5).json() == ds.generate(64, 5).json()
    assert ds.generate(64, 5).json() != ds.generate(64, 6).json()


@pytest.mark.parametrize("name", DATASETS)
def test_pool_is_table_ii(name):
    pool = sut.dataset(name).pool()
    assert [sut.program_clause(c) for c in pool] == \
        datasets.predicate_pool(name)


@pytest.mark.parametrize("name", DATASETS)
@pytest.mark.parametrize("dist", ["zipf", "uniform"])
def test_workload_rule_is_the_program_rule(name, dist):
    pool = sut.dataset(name).pool()
    mine = workload.generate(len(pool), n_queries=50, distribution=dist,
                             seed=3)
    theirs = generate_workload(
        datasets.predicate_pool(name), n_queries=50, distribution=dist,
        rng=np.random.default_rng(3))
    assert sut.program_queries(pool, mine) == \
        [Query(q.clauses) for q in theirs.queries]


def _kinds(pool):
    seen = {}
    for c in pool:
        seen.setdefault((c[0], c[1]), c)
    return list(seen.values())


# cross-type operands the pools do not hold, beside one of each kind
EXTRA = [("key_value", "linear_score", "7"), ("key_value", "url_domain", 7),
         ("key_value", "isActive", 1), ("exact", "children", 2),
         ("exact", "isActive", "true"), ("substring", "linear_score", "1"),
         ("key_value", "age_group", "adult"), ("presence", "nothere", None),
         ("key_value", "children", True)]


@pytest.mark.parametrize("name", DATASETS)
def test_reference_agrees_with_matches_exact(name):
    ds = sut.dataset(name)
    table = ds.generate(1500, 11)
    objs = [json.loads(r) for r in table.json()]
    ref = reference.Reference(table.cols, table.nested)
    pool = ds.pool()
    clauses = _kinds(pool) + pool[::17] + (EXTRA if name == "ycsb" else [])
    for c in clauses:
        theirs = sut.program_clause(c)
        want = [theirs.matches_exact(o) for o in objs]
        assert ref.clause_mask(c).tolist() == want, c
    q = (pool[0], pool[-1])
    assert ref.count(q) == sum(Query(tuple(sut.program_clause(c)
                                           for c in q)).matches_exact(o)
                               for o in objs)


@pytest.mark.parametrize("name", DATASETS)
def test_raw_match_agrees_with_the_client_semantics(name):
    ds = sut.dataset(name)
    records = ds.generate(800, 12).json()
    pool = ds.pool()
    clauses = _kinds(pool) + pool[::23] + (EXTRA if name == "ycsb" else [])
    bits = reference.raw_bits(records, clauses)
    for c, row in zip(clauses, bits):
        theirs = sut.program_clause(c)
        assert row.tolist() == [theirs.matches_raw(r) for r in records], c


def test_raw_key_value_stops_at_the_next_delimiter():
    rec = b'{"a":12,"b":"x3"}'
    assert reference.raw_match(rec, ("key_value", "a", 1))      # "12" holds 1
    assert not reference.raw_match(rec, ("key_value", "a", 3))  # 3 is in b
    assert clause(key_value("a", 3)).matches_raw(rec) is False
    assert reference.raw_match(rec, ("exact", "b", "x3"))
    assert clause(exact("b", "x3")).matches_raw(rec)
