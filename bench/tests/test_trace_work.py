"""The trace reduction, the work counts and the peak table."""
import pytest

from bench import trace, work
from bench.peaks import peaks
from bench.trace import Event, Timeline

MS = 1e6  # ns


def _timeline():
    dev = "/device:TPU:0"
    return Timeline(
        ops={dev: [Event("%fusion.1 = u8[8] fusion(u8[8] %a)", 0, 2 * MS),
                   Event("fusion.1", 1 * MS, 2 * MS),      # overlaps
                   Event("pallas_scan", 5 * MS, 1 * MS),
                   Event("fusion.2", 9 * MS, 4 * MS)]},   # runs past close
        modules={dev: [Event("jit__scan_core_pallas", 4.5 * MS, 2 * MS),
                       Event("jit__scan_core_pallas", 9 * MS, 4 * MS),
                       Event("jit_other", 20 * MS, 1 * MS)]},   # outside
        spans=[Event("bench.window", 0, 10 * MS),
               Event("bench.query", 2.5 * MS, 1 * MS),
               Event("bench.sleep", 3.5 * MS, 1.5 * MS),
               Event("bench.query", 6 * MS, 3 * MS)],
    )


def test_busy_union_clipped_to_window():
    s = trace.reduce(_timeline())
    # busy: [0,3] + [5,6] + [9,10] (clipped) = 5 ms of a 10 ms window
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx(0.005)


def test_program_time_counts_runs_started_in_window():
    s = trace.reduce(_timeline())
    assert s.program("_scan_core_pallas") == (2, pytest.approx(0.006))
    assert s.program("jit_other") == (0, 0)


def test_idle_gaps_are_shared_by_the_spans_open_in_them():
    s = trace.reduce(_timeline())
    idle = dict(s.idle_by_span)
    # gap [3,5]: query 0.5 ms, sleep 1.5 ms; gap [6,9]: query 3 ms
    assert idle == {"bench.sleep": pytest.approx(0.0015),
                    "bench.query": pytest.approx(0.0035)}


def test_overlapping_threads_share_a_gap_and_the_rest_has_no_span():
    dev = "/device:TPU:0"
    tl = Timeline(ops={dev: [Event("k", 0, 1 * MS), Event("k", 5 * MS, 1 * MS)]},
                  spans=[Event("bench.window", 0, 8 * MS),
                         Event("bench.encode", 1 * MS, 4 * MS),    # thread 1
                         Event("bench.ingest_chunk", 1 * MS, 4 * MS),  # 2
                         Event("bench.encode", 6 * MS, 1 * MS)])
    idle = dict(trace.reduce(tl).idle_by_span)
    assert idle == {"bench.encode": pytest.approx(0.003),
                    "bench.ingest_chunk": pytest.approx(0.002),
                    trace.NO_SPAN: pytest.approx(0.001)}


def test_top_ops_sum_by_name():
    s = trace.reduce(_timeline())
    assert s.top_ops[0] == ("fusion.1", pytest.approx(0.004))
    assert [n for n, _ in s.top_ops] == ["fusion.1", "fusion.2",
                                         "pallas_scan"]


def test_no_device_events_leave_the_whole_window_idle():
    tl = Timeline(spans=[Event("bench.window", 0, 4 * MS),
                         Event("bench.encode", 1 * MS, 1 * MS)])
    s = trace.reduce(tl)
    assert s.busy_s == 0
    assert dict(s.idle_by_span) == {"bench.encode": pytest.approx(0.001),
                                    trace.NO_SPAN: pytest.approx(0.003)}


def test_one_window_span_required():
    with pytest.raises(RuntimeError):
        trace.reduce(Timeline(spans=[Event("bench.query", 0, 1)]))


def test_union():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


@pytest.mark.parametrize("raw,records,clauses,want", [
    (1000, 64, 2, 1000 + 4 * (2 * 3 + 2)),     # 2 words a clause row
    (300, 1, 1, 300 + 4 * (1 * 2 + 1)),        # one partial word
    (4096 * 300, 4096, 0, 4096 * 300 + 4 * 128),
])
def test_client_bytes(raw, records, clauses, want):
    assert work.client_bytes(raw, records, clauses) == want


def test_scan_bytes():
    assert work.scan_bytes(1000, 3) == 12000
    assert work.SCAN_BYTES_PER_ROW_KEY == 4


def test_peaks_table():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks("TPU v99")
