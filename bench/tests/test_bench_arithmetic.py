"""Percentile, rate and span arithmetic, and the TTL work count, against
hand counts."""

import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, stats  # noqa: E402
from bench.device import peaks, ttl_work  # noqa: E402


@pytest.mark.parametrize("q", [0, 1, 50, 95, 99, 100])
def test_percentile_is_numpys_linear_over_all_samples(q):
    x = list(np.random.default_rng(3).lognormal(0.0, 1.0, 1001))
    assert stats.percentile(x, q) == pytest.approx(np.percentile(x, q),
                                                   rel=1e-12)


def test_percentile_by_hand():
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], 50) == 2.5
    assert stats.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 99) == pytest.approx(4.96)
    assert stats.percentile([], 50) is None


def test_rate_is_all_work_over_all_time():
    assert stats.rate(3 * 25_000, 30.0) == 2500.0


def test_span_total_merges_nested_and_clips_to_the_window():
    s = harness.Spans()
    for a, b in [(0.0, 1.0), (0.5, 0.8), (2.0, 3.0), (2.5, 4.0), (9.0, 12.0)]:
        s.add("x", a, b)
    assert s.total("x", (0.0, 10.0)) == pytest.approx(1.0 + 2.0 + 1.0)


def test_wrapped_method_is_timed_and_restored():
    class Box:
        def work(self, n):
            time.sleep(0.01)
            return n + 1

    s = harness.Spans()
    orig = Box.work
    s.wrap(Box, "work", "bench.work")
    assert Box().work(1) == 2
    s.unwrap()
    assert Box.work is orig
    (a, b), = s.intervals["bench.work"]
    assert b - a >= 0.01


def test_ttl_work_by_hand():
    # 9 regions: 8 incoming edges; 800 cells.  Inputs: 3 x 8 x 800 values,
    # 800 cell edges, 3 x 8 prices and first-read bytes; out: 8 indices.
    assert ttl_work.refresh_bytes(8, 800) == 4 * (19_200 + 800 + 24 + 8)
    assert ttl_work.refresh_ops(8, 800) == 16 * 6_400
    v5e = peaks.peaks("TPU v5 lite")
    t = ttl_work.least_seconds(1000, 8, 800, v5e)
    assert t == pytest.approx(1000 * 80_128 / 819e9)     # memory-bound
    assert t > 1000 * 102_400 / 197e12


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
