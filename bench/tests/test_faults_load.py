"""The load cell's check catches the faults its timed path can have, and
its control; the sound program passes it."""
import dataclasses

import numpy as np
import pytest

from bench.tests import tiny
from repro.core.server import CiaoStore
from repro.kernels.engine import KernelEngine
from repro.serve.store_engine import CiaoServeEngine


def _state_unchanged(mp):
    mp.setattr(CiaoStore, "ingest_chunk", lambda self, *a, **k: self.stats)


def _half_left_out(mp):
    apply = CiaoServeEngine._apply
    seen = []

    def every_other(self, item):
        seen.append(item)
        if len(seen) % 2:
            apply(self, item)
    mp.setattr(CiaoServeEngine, "_apply", every_other)


def _bit_altered(mp):
    fused = KernelEngine.eval_fused

    def flipped(self, chunk, clauses):
        bv = fused(self, chunk, clauses)
        words, or_words = bv.words.copy(), bv.or_words.copy()
        words[0, 0] ^= np.uint32(1)              # record 0, clause 0
        or_words[0] = np.bitwise_or.reduce(words[:, 0])
        return dataclasses.replace(bv, words=words, or_words=or_words)
    mp.setattr(KernelEngine, "eval_fused", flipped)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_left_out,
                                   _bit_altered])
def test_fault_is_not_correct(fault, monkeypatch):
    c = tiny.cell("ycsb.load-a")
    tiny.run(c)                              # warm, unbroken
    fault(monkeypatch)
    out = tiny.run(c)
    assert any(v > lim for v, lim in out.checks.values()), out.checks


def test_control_fails_and_program_passes():
    out = tiny.run(tiny.cell("ycsb.load-a"), control=True)
    assert all(v <= lim for v, lim in out.checks.values())
    limits = {k: lim for k, (_, lim) in out.checks.items()}
    assert any(out.control[k] > limits[k] for k in limits)
    assert out.control["rows_unaccounted"] == 1024
