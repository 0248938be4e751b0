"""Query workloads over a predicate pool (CIAO §VII-C, Table III).

A copy of the program's ``core/workload.py:generate_workload`` rule,
kept with the benchmark so that the query mix does not move with the
program.  Each pool clause gets inclusion probability
``w_i * E[#clauses] / sum(w)`` with ``w`` uniform (workload C) or Zipf
ranked (A: exponent 1.5, B: 2) over a random permutation of the pool;
a query is one Bernoulli draw over the pool, and empty draws are drawn
again.  The same ``seed`` gives the same queries as the program's rule
given ``np.random.default_rng(seed)``.
"""
from __future__ import annotations

import numpy as np


def generate(n_pool: int, *, n_queries: int, distribution: str,
             zipf_a: float = 1.5, expected_clauses: float = 3.0,
             seed: int = 0) -> list[tuple[int, ...]]:
    """``n_queries`` conjunctions, each a sorted tuple of pool indices."""
    rng = np.random.default_rng(seed)
    if distribution == "uniform":
        w = np.ones(n_pool)
    elif distribution == "zipf":
        w = np.arange(1, n_pool + 1, dtype=np.float64) ** (-zipf_a)
        w = w[rng.permutation(n_pool)]
    else:
        raise ValueError(f"unknown distribution {distribution!r}")
    probs = np.clip(w / w.sum() * expected_clauses, 0.0, 1.0)
    queries: list[tuple[int, ...]] = []
    while len(queries) < n_queries:
        idx = np.nonzero(rng.random(n_pool) < probs)[0]
        if len(idx):
            queries.append(tuple(int(i) for i in idx))
    return queries
