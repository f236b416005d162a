"""The trace reduction, on a small trace recorded on one TPU v5e chip
(``bench/record_trace.py``): three calls of the program's plane
aggregation (the Pallas fedagg kernel on an (8, 2^20) plane) and of a
(64, 32, 32, 128) convolution, a 20 ms sleep, one more aggregation."""
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
TRACE = ROOT / "tests" / "bench" / "data" / "window.xplane.pb"


class Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


def test_union_length():
    from bench.tracefile import union_length
    assert union_length([(0, 10), (5, 15), (20, 30), (21, 22)]) == 25
    assert union_length([]) == 0


def test_leaf_ops_drop_loops_that_hold_their_body():
    from bench.tracefile import leaf_ops
    evs = [Ev("while", 0, 100), Ev("conv", 10, 30), Ev("add", 50, 20),
           Ev("copy", 120, 5)]
    assert sorted(leaf_ops(evs)) == [("add", 50, 70), ("conv", 10, 40),
                                     ("copy", 120, 125)]


@pytest.fixture(scope="module")
def reduced():
    from bench import tracefile
    return tracefile.reduce_trace(str(TRACE))


def test_window_and_busy(reduced):
    assert reduced["devices"] == 1
    # the window holds the 20 ms sleep, and the device idles through it
    assert 0.020 < reduced["window_s"] < 0.030
    assert 0.0 < reduced["busy_s"] < reduced["window_s"] - 0.019
    longest = max(b - a for a, b in reduced["gaps"])
    assert 0.019e9 < longest < reduced["window_s"] * 1e9


def test_busy_and_gaps_cover_the_window(reduced):
    gaps = sum(b - a for a, b in reduced["gaps"]) / 1e9
    assert reduced["busy_s"] + gaps == pytest.approx(reduced["window_s"],
                                                     rel=1e-9)


def test_ops_sum_to_busy(reduced):
    # operations on one device do not overlap once loops are dropped
    assert sum(reduced["ops"].values()) == pytest.approx(reduced["busy_s"],
                                                         rel=1e-9)


def test_kernel_found_and_its_roofline(reduced):
    from bench import harness
    reader = harness.reader("fedagg_roofline")
    kernel = [n for n in reduced["ops"] if reader.KERNEL in n]
    assert len(kernel) == 1 and reduced["calls"][kernel[0]] == 3
    # result (1, 2^20) and plane (8, 2^20) in HBM, the (8, 1) weights in
    # vector memory
    assert reader.call_bytes(kernel[0]) == (4 * 9 * (1 << 20), 4 * 8)
    win = type("W", (), {"ops": reduced["ops"], "calls": reduced["calls"],
                         "peaks": harness.peaks_for("TPU v5 lite")})
    share = reader.read(win)
    # 37.7 MB a call at 819 GB/s is 46 us; the chip took 54 us
    assert 80.0 < share < 90.0


def test_window_marks_are_required(tmp_path):
    from bench import tracefile
    with pytest.raises(FileNotFoundError):
        tracefile.find_xplane(str(tmp_path))
