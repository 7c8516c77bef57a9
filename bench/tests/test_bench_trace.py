"""The trace reduction on a trace built by hand: busy union, kernel time,
top ops and idle gaps charged to the host span that covers them."""
import sys
from pathlib import Path

import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]
from bench.harness import trace as T  # noqa: E402

MS = 1e6


def built():
    dev = "/device:TPU:0"
    ops = [T.Event("fusion.1", 0 * MS, 4 * MS),
           T.Event("fusion.2", 2 * MS, 4 * MS),          # overlaps: 0-6
           T.Event("%encode_codes_pallas.3 = u32[2048,1] custom-call(...), "
                   "custom_call_target=\"tpu_custom_call\"", 10 * MS, 2 * MS),
           T.Event("fusion.1", 15 * MS, 1 * MS),
           T.Event("fusion.1", 19 * MS, 3 * MS)]        # clipped at 20
    host = [T.Event(T.WINDOW_SPAN, 0, 20 * MS),
            T.Event("bench/round", 0, 14 * MS),
            T.Event("bench/merge_stats", 6 * MS, 4 * MS)]
    other = [T.Event("fusion.9", 0, 20 * MS)]
    return T.Trace(devices={dev: ops, "/device:TPU:1": other}, host=host)


def test_busy_union_and_window():
    r = T.reduce(built(), ["/device:TPU:0"], ["%encode_codes_pallas"])
    assert r.window_s == pytest.approx(0.020)
    # 0-6, 10-12, 15-16, 19-20 -> 10 ms busy
    assert r.busy_s == pytest.approx(0.010)
    assert r.idle_share == pytest.approx(0.5)


def test_kernel_time_and_top_ops():
    r = T.reduce(built(), ["/device:TPU:0"], ["%encode_codes_pallas"])
    assert r.kernel_seconds("%encode_codes_pallas") == pytest.approx(0.002)
    assert r.kernel_seconds("%decode_codes_pallas") is None
    ops = dict(r.device_ops)
    assert ops["fusion.1"] == pytest.approx(0.006)   # 4 + 1 + 1 (clipped)
    assert r.device_ops[0][0] == "fusion.1"


def test_idle_gaps_charged_to_innermost_span():
    r = T.reduce(built(), ["/device:TPU:0"])
    gaps = dict(r.idle_gaps)
    # 6-10 lies in merge_stats (inside round); 12-15 in round; 16-19 none
    assert gaps["bench/merge_stats"] == pytest.approx(0.004)
    assert gaps["bench/round"] == pytest.approx(0.003)
    assert gaps[T.NO_SPAN] == pytest.approx(0.003)


def test_busy_averaged_over_devices():
    r = T.reduce(built(), ["/device:TPU:0", "/device:TPU:1"])
    assert r.busy_s == pytest.approx((0.010 + 0.020) / 2)


def test_union_merges_touching_intervals():
    assert T.union([(5, 7), (0, 2), (2, 3), (6, 9)]) == [(0, 3), (5, 9)]


def test_a_trace_without_the_window_span_is_refused():
    tr = built()
    tr.host = [e for e in tr.host if e.name != T.WINDOW_SPAN]
    with pytest.raises(ValueError):
        T.reduce(tr, ["/device:TPU:0"])
