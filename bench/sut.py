"""From the benchmark's own clauses and query mixes to the program's.

Turns ``(kind, key, value)`` pool clauses and pool-index queries into
the program's ``Clause`` and ``Query`` objects, and builds the client
pushdown plan the way the paper does (§III step 1): budgeted selection
over the query workload, with selectivities from the first records of
the stream and the program's default cost model.
"""
from __future__ import annotations

import importlib
from typing import Sequence


def dataset(name: str):
    """The benchmark's generator module for a dataset (``bench/data``)."""
    return importlib.import_module(f"bench.data.{name}")


def program_clause(c: tuple):
    from repro.core.predicates import clause, exact, key_value, presence, \
        substring
    kind, key, value = c
    if kind == "presence":
        return clause(presence(key))
    make = {"exact": exact, "substring": substring,
            "key_value": key_value}[kind]
    return clause(make(key, value))


def program_queries(pool: Sequence[tuple],
                    queries: Sequence[tuple[int, ...]]) -> list:
    """One program ``Query`` per benchmark query (pool indices)."""
    from repro.core.predicates import Query
    clauses = {i: program_clause(pool[i]) for q in queries for i in q}
    return [Query(tuple(clauses[i] for i in q)) for q in queries]


def build_plan(pool: Sequence[tuple], queries: Sequence[tuple[int, ...]],
               sample: Sequence[bytes], budget_us: float):
    """The pushdown plan for ``queries`` at ``budget_us`` per record."""
    from repro.core.planner import build_plan as plan_for
    from repro.core.workload import Workload
    wl = Workload(name="bench", queries=program_queries(pool, queries))
    return plan_for(wl, list(sample), budget_us=budget_us).plan


def pushed_pool_indices(plan, pool: Sequence[tuple]) -> list[int]:
    """Pool index of each pushed clause, in the plan's clause order."""
    index = {program_clause(c): i for i, c in enumerate(pool)}
    return [index[c] for c in plan.clauses]
