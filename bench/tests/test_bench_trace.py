"""The device trace reduction: interval arithmetic by hand, and the whole
reduction on a small trace recorded on one TPU v5e (one T65 replay window
of the ``sim9_t65_mixE`` cell, cut to 12 objects and one month)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.device import trace  # noqa: E402

RECORDED = Path(__file__).with_name("data") / "small_trace.xplane.pb.gz"


def test_merge_and_complement():
    busy = trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert trace.complement(busy, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert trace.complement([], 0, 4) == [(0, 4)]


def test_innermost_names_the_deepest_event_of_a_thread():
    events = [(0, 10, "bench.replay"), (2, 6, "bench.ttl"),
              (3, 4, "DevicePut"), (8, 9, "bench.ttl")]
    assert trace.innermost(events) == [
        (0, 2, 1, "bench.replay"), (2, 3, 2, "bench.ttl"),
        (3, 4, 3, "DevicePut"), (4, 6, 2, "bench.ttl"),
        (6, 8, 1, "bench.replay"), (8, 9, 2, "bench.ttl"),
        (9, 10, 1, "bench.replay")]


def test_deepest_prefers_jax_events_then_the_span_order():
    client = [(0, 10, 2, "bench.http")]
    server = trace.innermost([(1, 9, "bench.dispatch"), (3, 5, "bench.ttl"),
                              (4, 5, "PjitFunction(argmin)")])
    assert trace.deepest([client, server]) == [
        (0, 1, "bench.http"), (1, 3, "bench.dispatch"), (3, 4, "bench.ttl"),
        (4, 5, "PjitFunction(argmin)"), (5, 9, "bench.dispatch"),
        (9, 10, "bench.http")]


def test_idle_gaps_by_label():
    labels = [(0, 4, "bench.replay"), (4, 8, "bench.ttl")]
    gaps = [(1, 2), (3, 6), (9, 11)]
    assert trace.overlap_by_label(gaps, labels) == {
        "bench.replay": 2, "bench.ttl": 2, "(no host span)": 2}


def test_timeline_by_hand():
    tl = trace.Timeline(
        window=(0, 100e3),             # 0.1 ms, in ns
        programs=[[("jit_ttl_cost_surface", 10e3, 30e3),
                   ("jit_convert_element_type", 25e3, 40e3),
                   ("jit_argmin", 70e3, 80e3)]],
        spans={"bench.ttl": [(5e3, 45e3)]},
        host=[(0, 5e3, "bench.replay"), (5e3, 45e3, "bench.ttl"),
              (45e3, 100e3, "bench.replay")])
    assert tl.window_s == pytest.approx(1e-4)
    assert tl.busy_s == pytest.approx(40e3 * 1e-9)
    assert tl.seconds_inside("bench.ttl") == pytest.approx(35e3 * 1e-9)
    assert tl.program_seconds()["jit_argmin"] == pytest.approx(1e-5)
    idle = tl.idle_by_label()
    assert idle["bench.ttl"] == pytest.approx(10e3 * 1e-9)      # 5-10, 40-45
    assert idle["bench.replay"] == pytest.approx(50e3 * 1e-9)   # 0-5, 45-70, 80-100


def test_program_name_drops_the_fingerprint():
    assert trace.program_name("jit_concatenate(5529791715300710225)") == \
        "jit_concatenate"


@pytest.fixture(scope="module")
def recorded():
    return trace.load(RECORDED, chips=1)


def test_recorded_window_and_busy_time(recorded):
    assert recorded.window_s == pytest.approx(0.209316723, rel=1e-9)
    assert recorded.busy_s == pytest.approx(0.000667723, rel=1e-6)
    idle = recorded.idle_by_label()
    assert sum(idle.values()) == pytest.approx(
        recorded.window_s - recorded.busy_s, rel=1e-9)
    assert max(idle, key=idle.get) == "bench.ttl"
    assert idle["DevicePut"] > 0          # host-to-device transfers


def test_recorded_refreshes_are_29_programs_each(recorded):
    from collections import Counter

    counts = Counter(name for name, _a, _b in recorded.programs[0])
    assert counts["jit_ttl_cost_surface"] == 15
    assert sum(counts.values()) == 15 * 29
    assert len(recorded.spans["bench.ttl"]) == 334
    inside = recorded.seconds_inside("bench.ttl")
    assert 0.9 * recorded.busy_s < inside <= recorded.busy_s
