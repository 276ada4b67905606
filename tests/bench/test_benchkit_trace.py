"""The reduction from a device trace to metrics."""
import pytest

from benchkit import trace as T

# Two devices; window [100, 1100) ns.  Device 0 runs ops over
# [50, 150) (clipped to [100, 150)), [140, 300) (overlapping), [500, 600)
# and [1000, 1200) (clipped to [1000, 1100)): busy 200 + 100 + 100 = 400.
# Device 1 runs [200, 800): busy 600.
SMALL = {
    "window": [100, 1100],
    "device": [
        ["fusion.1", "", 50, 100, 0],
        ["custom-call.7", "lut_cascade kernel", 140, 160, 0],
        ["custom-call.7", "lut_cascade kernel", 500, 100, 0],
        ["copy.3", "", 1000, 200, 0],
        ["fusion.2", "", 200, 600, 1],
        ["fusion.9", "", 2000, 10, 1],     # outside the window
    ],
    "host": [["bench.submit", 300, 150], ["bench.eval", 650, 100]],
}


def test_busy_and_idle_share():
    assert T.busy_intervals(SMALL, 0) == [(100, 300), (500, 600),
                                          (1000, 1100)]
    assert T.busy_s(SMALL) == pytest.approx((400 + 600) / 2 / 1e9)
    assert T.window_s(SMALL) == pytest.approx(1000 / 1e9)
    assert T.idle_share(SMALL) == pytest.approx(1 - 500 / 1000)


def test_kernel_time_by_name_or_label():
    secs, calls = T.kernel_s(SMALL, r"lut_cascade")
    assert calls == 2 and secs == pytest.approx(260 / 1e9)
    assert T.kernel_s(SMALL, r"no_such_kernel") == (0.0, 0)


def test_breakdown():
    top = T.top_ops(SMALL, 2)
    assert top[0][0] == "fusion.2" and top[0][1] == pytest.approx(6e-7)
    # device 0 idles over [300, 500) (mid 400: in bench.submit) and
    # [600, 1000) (mid 800: in no span)
    gaps = dict(T.idle_gaps(SMALL))
    assert gaps == pytest.approx({"outside bench spans": 4e-7,
                                  "bench.submit": 2e-7})


def test_no_device_ops_reads_nothing():
    empty = dict(SMALL, device=[])
    assert T.busy_s(empty) is None and T.idle_share(empty) is None
    assert T.idle_gaps(empty) == []
