"""The scan cells' check catches the faults their timed path can have,
and their control; the sound program passes it."""
import numpy as np
import pytest

from bench.tests import tiny
from repro.core.device_scan import DeviceScanner
from repro.core.server import CiaoStore

CELLS = ["ycsb.scan-a"]


def _state_unchanged(mp):
    mp.setattr(CiaoStore, "ingest_chunk", lambda self, *a, **k: self.stats)


def _half_left_out(mp):
    launch = DeviceScanner._launch

    def half(self, prep):
        counts, cands = launch(self, prep)
        if counts is not None:              # as if half the rows were left
            counts = np.asarray(counts) // 2
        return counts, cands
    mp.setattr(DeviceScanner, "_launch", half)


def _answer_altered(mp):
    scan_batch = DeviceScanner.scan_batch

    def plus_one(self, queries):
        out = scan_batch(self, queries)
        for r in out:
            r.count += 1
        return out
    mp.setattr(DeviceScanner, "scan_batch", plus_one)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_left_out,
                                   _answer_altered])
def test_fault_is_not_correct(name, fault, monkeypatch):
    c = tiny.cell(name)
    fault(monkeypatch)
    out = tiny.run(c)
    assert any(v > lim for v, lim in out.checks.values()), out.checks


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_and_program_passes(name):
    out = tiny.run(tiny.cell(name), control=True)
    assert all(v <= lim for v, lim in out.checks.values())
    limits = {k: lim for k, (_, lim) in out.checks.items()}
    assert any(out.control[k] > limits[k] for k in limits)
