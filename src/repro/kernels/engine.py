"""Kernel-backed client engine (implements the core engine protocol).

The whole plan is compiled ONCE into a flat predicate table + clause
membership matrix (:func:`compile_plan`), and a chunk is evaluated with a
single fused device pass (``ops.clause_bitvectors``): simple and key-value
predicates batch into one grid dimension with masked dynamic lengths, the
clause OR-combine, bit-packing, load-mask OR and popcounts all happen on
device.  No per-key-value-pair launches, no host-side OR/pack
(DESIGN.md §3.4).
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from repro.core import bitvector
from repro.core.bitvector import ChunkBitvectors
from repro.core.client import Chunk
from repro.core.predicates import Clause
from repro.core.telemetry import span

from . import ops
from .plan import CompiledPlan, compile_plan, tier_view  # noqa: F401 (re-export)


class KernelEngine:
    def __init__(self, backend: str = "pallas", r_blk: int = 256):
        self.backend = backend
        self.r_blk = r_blk
        self.name = backend
        self._plan_cache: dict[tuple[Clause, ...], CompiledPlan] = {}
        # (full clause tuple, tier size) -> neutralized subset view; the
        # views share the full plan's shapes, hence its jit trace
        self._tier_cache: dict[tuple[tuple[Clause, ...], int], CompiledPlan] = {}

    def _compiled(self, clauses: tuple[Clause, ...]) -> CompiledPlan:
        plan = self._plan_cache.get(clauses)
        if plan is None:
            plan = compile_plan(clauses)
            if len(self._plan_cache) > 64:  # plans change rarely; bound it
                self._plan_cache.clear()
                self._tier_cache.clear()
            self._plan_cache[clauses] = plan
        return plan

    def _compiled_tier(self, clauses: tuple[Clause, ...],
                       n_clauses: int) -> CompiledPlan:
        key = (clauses, n_clauses)
        view = self._tier_cache.get(key)
        if view is None:
            view = tier_view(self._compiled(clauses), n_clauses)
            if len(self._tier_cache) > 256:
                self._tier_cache.clear()
            self._tier_cache[key] = view
        return view

    def eval_fused(self, chunk: Chunk, clauses: Sequence[Clause]) -> ChunkBitvectors:
        """One device launch: packed bitvectors + load mask + popcounts."""
        C, R = len(clauses), chunk.n_records
        W = bitvector.num_words(R)
        if C == 0 or R == 0:
            return ChunkBitvectors(
                words=np.zeros((C, W), np.uint32),
                or_words=np.zeros((W,), np.uint32),
                counts=np.zeros((C,), np.int32),
                n_records=R,
            )
        with span("ciao.client.pushdown"):
            plan = self._compiled(tuple(clauses))
            words, or_words, counts = ops.clause_bitvectors(
                chunk.data, plan, backend=self.backend, r_blk=self.r_blk,
            )
        return ChunkBitvectors(
            words=words, or_words=or_words, counts=counts, n_records=R
        )

    def eval_fused_prefix(self, chunk: Chunk, clauses: Sequence[Clause],
                          n_clauses: int) -> ChunkBitvectors:
        """Tiered evaluation: the first ``n_clauses`` of ``clauses``.

        Unlike ``eval_fused(chunk, clauses[:k])`` — which would compile a
        smaller plan and trigger a fresh jit specialization per tier —
        this evaluates a neutralized subset VIEW of the full compiled
        plan (:func:`repro.kernels.plan.tier_view`), so every tier of a
        family shares one trace per chunk shape bucket; out-of-tier
        predicates retire at the kernel's first-char prefilter.  The
        returned bitvectors carry exactly ``n_clauses`` rows and are
        bit-identical to a direct evaluation of the subset.
        """
        clauses = tuple(clauses)
        C, R = len(clauses), chunk.n_records
        if not 0 <= n_clauses <= C:
            raise ValueError(f"prefix {n_clauses} out of range 0..{C}")
        if n_clauses == C:
            return self.eval_fused(chunk, clauses)
        W = bitvector.num_words(R)
        if n_clauses == 0 or R == 0:
            return ChunkBitvectors(
                words=np.zeros((n_clauses, W), np.uint32),
                or_words=np.zeros((W,), np.uint32),
                counts=np.zeros((n_clauses,), np.int32),
                n_records=R,
            )
        with span("ciao.client.pushdown"):
            view = self._compiled_tier(clauses, n_clauses)
            words, or_words, counts = ops.clause_bitvectors(
                chunk.data, view, backend=self.backend, r_blk=self.r_blk,
            )
        # out-of-tier clause rows are all-zero by construction: slice them
        # off so the store sees exactly the tier's coverage
        return ChunkBitvectors(
            words=words[:n_clauses], or_words=or_words,
            counts=counts[:n_clauses], n_records=R,
        )

    def eval(self, chunk: Chunk, clauses: Sequence[Clause]) -> np.ndarray:
        fused = self.eval_fused(chunk, clauses)
        return bitvector.unpack(fused.words, chunk.n_records)

    def eval_packed(self, chunk: Chunk, clauses: Sequence[Clause]) -> np.ndarray:
        return self.eval_fused(chunk, clauses).words
