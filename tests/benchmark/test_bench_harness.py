"""The yardstick's own arithmetic: schedule, latency from the due time,
lateness, percentiles, peaks."""
import numpy as np
import pytest

from benchmark.harness import loadgen, peaks, stats

MIX = {"1": 0.60, "2": 0.15, "4": 0.10, "8": 0.10, "16": 0.05}


def test_schedule_is_a_function_of_the_seed():
    a = loadgen.schedule(7, 200.0, 5.0, MIX)
    b = loadgen.schedule(7, 200.0, 5.0, MIX)
    c = loadgen.schedule(8, 200.0, 5.0, MIX)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0][:50], c[0][:50])
    due, rows = a
    assert np.all(np.diff(due) > 0) and due[-1] < 5.0
    # Poisson at 200/s over 5 s: 1000 expected, sd 32
    assert 850 < len(due) < 1150
    assert set(rows) <= {1, 2, 4, 8, 16}
    # mean rows of the mix: 0.6 + 0.3 + 0.4 + 0.8 + 0.8 = 2.9
    assert abs(rows.mean() - 2.9) < 0.4


class FakeTime:
    """A clock that only sleep() advances: one sender, no real waiting."""

    def __init__(self):
        self.now = 100.0

    def clock(self):
        return self.now

    def sleep(self, s):
        self.now += s


def test_latency_runs_from_the_due_time_and_lateness_is_reported():
    t = FakeTime()
    due = np.array([0.0, 0.010, 0.020, 0.030])
    service = [0.005, 0.050, 0.005, 0.005]   # request 1 stalls the sender

    def send(i):
        t.sleep(service[i])
        if i == 3:
            raise TimeoutError("refused")

    out = loadgen.drive(due, send, threads=1, timeout_s=1.0, clock=t.clock,
                        sleep=t.sleep)
    # t0 = 100.05.  Request 0: due 0, sent on time, answered at +5 ms.
    # Request 1: due 10, answered at 60.  Request 2: due 20 but the one
    # sender is busy until 60: sent 40 ms late, answered at 65, and its
    # latency is 45 ms - counted from when it was DUE, not from when it
    # was sent.  Request 3 fails: the timeout stands for its latency.
    assert out["ok"] == [True, True, True, False]
    assert out["latency_s"] == pytest.approx([0.005, 0.050, 0.045, 1.0])
    assert out["late_s"] == pytest.approx([0.0, 0.0, 0.040, 0.035])
    assert len(out["errors"]) == 1 and "refused" in out["errors"][0]
    assert out["senders_stuck"] == 0


def test_percentile_needs_ten_samples_beyond_it():
    values = list(range(1, 1001))
    assert stats.percentile(values, 99.0) == 990     # ten lie beyond
    assert stats.median(values) == 500
    with pytest.raises(ValueError, match="ten are needed"):
        stats.percentile(values[:999], 99.0)
    with pytest.raises(ValueError):
        stats.percentile([], 50.0)
    assert stats.median([3.0, 1.0, 2.0]) == 2.0


def test_spread_is_quartile_distance_over_median():
    # quartiles of 1..5 are 2 and 4, the median 3
    assert stats.spread([5, 1, 4, 2, 3]) == pytest.approx(2.0 / 3.0)
    with pytest.raises(ValueError):
        stats.spread([1, 2])


def test_peaks_table_and_unknown_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                   "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9}
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peaks_for("TPU v9")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
