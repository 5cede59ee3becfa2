"""``bench/layers.py``, the per-layer reading of the program's own spans: its
numbers on hand-built recordings, and the program's annotated spans in a
CPU ``jax.profiler`` capture, under the same names, in the same number and
with the same durations as the recorder keeps them."""

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import layers  # noqa: E402
from repro.core import tracing  # noqa: E402
from repro.core.tracing import Aggregate, Snapshot, SpanRecord  # noqa: E402

COST = {"aggregated": 0.0, "kept": 0.0}


def _snapshot(spans, kept=(), counters=None, end=100.0):
    return Snapshot(0.0, end, {k: Aggregate(1, v, v) for k, v in spans.items()},
                    list(kept), counters or {}, COST,
                    {"aggregated": 0, "kept": 0})


def test_replay_readings():
    kept = [
        SpanRecord("skystore.replay.run", 0.0, 90.0, 5.0, -1, 0),
        # a refresh stopped at the warm-up gate: a merge, no scan
        SpanRecord("skystore.ttl.refresh", 1.0, 1.001, 0.0, 0, 0),
        SpanRecord("skystore.ttl.merge", 1.0, 1.001, 0.001, 1, 0),
        SpanRecord("skystore.ttl.refresh", 2.0, 2.012, 0.001, 0, 0),
        SpanRecord("skystore.ttl.merge", 2.0, 2.001, 0.001, 3, 0),
        SpanRecord("skystore.ttl.scan", 2.001, 2.011, 0.010, 3, 0),
        SpanRecord("skystore.ttl.refresh", 3.0, 3.014, 0.001, 0, 0),
        SpanRecord("skystore.ttl.scan", 3.001, 3.013, 0.012, 6, 0),
    ]
    snap = _snapshot({"skystore.replay.run": 5.0, "skystore.spine.data": 20.0,
                      "skystore.store.get": 10.0, "skystore.meta.holders": 8.0,
                      "skystore.expiry.drain": 2.0,
                      "skystore.ledger.charge_op": 5.0,
                      "skystore.policy.ttl_on_access": 30.0,
                      "skystore.ttl.scan": 18.0},
                     kept, {"routing.get_hinted": 30, "routing.get_scalar": 10,
                            "expiry.pops": 25, "expiry.stale": 75})
    r = layers.readings(snap)
    assert r["spine_share"] == pytest.approx(25.0)
    assert r["store_share"] == pytest.approx(10.0)
    assert r["control_share"] == pytest.approx(10.0)
    assert r["charges_share"] == pytest.approx(5.0)
    assert r["policy_share"] == pytest.approx(48.0)
    assert r["uncovered_share"] == pytest.approx(2.0)
    assert r["ttl_refreshes"] == 2
    assert r["ttl_refresh_p50_ms"] == pytest.approx(13.0)
    assert r["ttl_phase_p50_ms"]["scan"] == pytest.approx(11.0)
    assert r["ttl_phase_p50_ms"]["merge"] == pytest.approx(1.0)
    assert r["ttl_phase_p50_ms"]["inputs"] is None
    assert r["route_scalar_share"] == pytest.approx(25.0)
    assert r["expiry_stale_share"] == pytest.approx(75.0)
    # nothing of the served path to read in a replay
    assert r["codec_p50_ms"] is None and r["stalls"] is None


def test_served_readings():
    kept, http = [], []
    for i, (before, inside, dispatch, after) in enumerate(
            [(0.1, 0.3, 0.1, 0.2), (0.1, 0.5, 0.2, 0.2), (30.0, 0.4, 0.2, 5.0)]):
        t = 100.0 * i
        a = t + before * 1e-3
        b = a + inside * 1e-3
        kept.append(SpanRecord("skystore.s3.request", a, b,
                               (inside - dispatch) * 1e-3, -1, 1))
        kept.append(SpanRecord("skystore.store.dispatch", a, a + dispatch * 1e-3,
                               dispatch * 1e-3, len(kept) - 1, 1))
        if i == 2:      # the stalled request solved a refresh in its dispatch
            kept.append(SpanRecord("skystore.ttl.refresh", a, a + 0.15e-3,
                                   0.15e-3, len(kept) - 1, 1))
        http.append((t, b + after * 1e-3))
    snap = _snapshot({"skystore.s3.request": 0.7e-3}, kept, end=300.0)
    r = layers.readings(snap, http)
    assert r["requests"] == 3
    assert r["codec_p50_ms"] == pytest.approx(0.2)
    assert r["request_p50_ms"] == pytest.approx(0.4)
    assert r["dispatch_p50_ms"] == pytest.approx(0.2)
    assert r["wire_p50_ms"] == pytest.approx(0.3)
    (stall,) = r["stalls"]
    assert stall["round_trip_ms"] == pytest.approx(35.4)
    assert stall["before_ms"] == pytest.approx(30.0)
    assert stall["inside_ms"] == pytest.approx(0.4)
    assert stall["refresh_ms"] == pytest.approx(0.15)
    assert stall["after_ms"] == pytest.approx(5.0)
    # nothing of a replay to read in a served window
    assert r["ttl_refresh_p50_ms"] is None
    assert r["route_scalar_share"] is None and r["expiry_stale_share"] is None


def test_annotated_spans_share_the_profiles_clock(tmp_path):
    import jax

    from bench.device import trace as dtrace

    names = ["skystore.replay.run", "skystore.ttl.refresh", "skystore.ttl.scan"]
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=dtrace.profiler_options())
    try:
        tracing.start(annotate=True)
        try:
            with tracing.span(names[0]):
                for _ in range(3):
                    with tracing.span(names[1]):
                        time.sleep(0.002)
                        with tracing.span(names[2]):
                            time.sleep(0.003)
        finally:
            snap = tracing.stop()
    finally:
        jax.profiler.stop_trace()
    pd = dtrace.read_profile(dtrace.find_profile(tmp_path))
    profiled = {n: [] for n in names}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in profiled:
                    profiled[e.name].append(e.duration_ns * 1e-9)
    for name in names:
        kept = sorted(s.seconds for s in snap.named(name))
        seen = sorted(profiled[name])
        assert len(seen) == len(kept) == (1 if name == names[0] else 3)
        for a, b in zip(kept, seen):
            assert abs(a - b) < 50e-6
