"""The trace reduction on hand-made planes (every number checked by hand)
and on a small trace recorded on a TPU v5e (tests/data/small_trace).

    PYTHONPATH=perfbench python -m pytest -q perfbench/tests
"""
import pathlib

import pytest

import trace_reduce as T
from trace_reduce import Event, Plane

DATA = pathlib.Path(__file__).parent / "data"


def _planes():
    host = Plane("/host:CPU", {
        "main": [Event("bench.window", 1000, 2000),
                 Event("bench.call", 1000, 1500),
                 Event("bench.call", 1500, 2000),
                 Event("stage", 1100, 1200)],
    })
    ops0 = [Event("fusion.1", 1000, 1100),       # busy 1000-1150
            Event("sort_columns", 1050, 1150),
            Event("all-reduce.3", 1300, 1400),   # collective alone 1300-1400
            Event("fusion.2", 1600, 1700),
            Event("all-gather.1", 1650, 1800),   # collective alone 1700-1800
            Event("fusion.0", 900, 1010)]        # starts before the window
    ops1 = [Event("fusion.1", 1000, 1500)]
    return [host,
            Plane("/device:TPU:0", {"XLA Ops": ops0}),
            Plane("/device:TPU:1", {"XLA Ops": ops1})]


def test_union_subtract_clip():
    assert T.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T.clip([(0, 5), (8, 20)], 3, 10) == [(3, 5), (8, 10)]
    assert T.length([(0, 2), (5, 6)]) == 3


def test_reduce_hand_checked():
    s = T.reduce_planes(_planes())
    assert s.window_s == pytest.approx(1000e-9)
    # chip 0: 1000-1150, 1300-1400, 1600-1800 -> 450 ns; chip 1: 500 ns
    assert s.busy_per_chip == pytest.approx([450e-9, 500e-9])
    assert s.busy_s == pytest.approx(475e-9)
    # op time is clipped to the window and averaged over the 2 chips
    assert s.op_s["fusion.1"] == pytest.approx((100 + 500) / 2 * 1e-9)
    assert s.op_s["fusion.0"] == pytest.approx(10 / 2 * 1e-9)
    assert s.op_s["sort_columns"] == pytest.approx(100 / 2 * 1e-9)
    # chip 0 idle 1150-1300 (mid 1225: bench.call), 1400-1600 (mid 1500:
    # second bench.call), 1800-2000 (bench.call); chip 1 idle 1500-2000
    assert s.gap_s == pytest.approx({"bench.call": (150 + 200 + 200 + 500) * 1e-9})
    # collectives: chip 0 runs 100 + 150 ns of them, 200 ns with nothing else
    assert s.collective_s == pytest.approx(250e-9 / 2)
    assert s.collective_only_s == pytest.approx(200e-9 / 2)
    assert s.top_ops(1) == [["fusion.1", pytest.approx(300e-9)]]


def test_gap_label_is_innermost_host_span():
    planes = _planes()
    planes[1].lines["XLA Ops"] = [Event("fusion.1", 1000, 1100),
                                  Event("fusion.2", 1250, 2000)]
    s = T.reduce_planes(planes, devices=1)
    assert s.gap_s == pytest.approx({"stage": 150e-9})


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        T.reduce_planes(_planes(), window=("missing",))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        T.peaks("TPU v99")
    assert T.peaks("TPU v5 lite")["bf16_flops"] == 197e12


@pytest.mark.skipif(not (DATA / "small_trace").exists(),
                    reason="no recorded trace")
def test_recorded_trace():
    s = T.reduce_dir(str(DATA / "small_trace"))
    assert s.chips == 1
    assert 0 < s.busy_s <= s.window_s
    assert sum(s.op_s.values()) >= s.busy_s * (1 - 1e-9)
    # three matmul + sort pairs separated by host sleeps: the chip idles
    # most of the window, and the gaps are labelled by host spans
    assert s.busy_s < 0.5 * s.window_s
    assert sum(s.gap_s.values()) == pytest.approx(s.window_s - s.busy_s)
