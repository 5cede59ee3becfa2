"""The cell ``sim9x1000_ycsb_b_sweep``: its files load, its generator gives
every seed the same work, the program matches the sweep reference exactly at
a small size, and ``correct`` comes out false when a fault is planted in
the reference's or in the program's place.  Runs skip the harness's look
for a chip and use the numpy engine of this CPU host."""

import hashlib
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, runner, system  # noqa: E402
from bench.reference import skystore_fb_sweep  # noqa: E402
from repro.core.traces import OP_GET, OP_PUT  # noqa: E402

CELL = "sim9x1000_ycsb_b_sweep"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
REGIONS = tuple(f"r{i}" for i in range(9))
DRV = harness.load_module(ROOT / "bench/drivers/replay_sweep.py")


def small(n_buckets=20):
    """The cell at ``n_buckets`` buckets, each keeping its 250 requests."""
    cell = harness.load_cell(CELL)
    cell.traffic["params"].update(n_buckets=n_buckets,
                                  n_requests=250 * n_buckets)
    return cell


def trace_of(cell, seed):
    cost = system.cost_model(cell.config)
    return harness.generator(cell.traffic)(cost.region_names(), seed,
                                           **cell.traffic["params"])


def test_cell_loads():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1
    assert cell.traffic["driver"] == "replay_sweep"
    assert cell.config["policy_params"]["refresh_schedule"] == "tick"
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "replay_events_per_s"}
    assert {m["name"] for m in cell.per_layer} == {"tick_share.replay",
                                                   "sweep_roofline"}
    p = cell.traffic["params"]
    assert p["n_requests"] == 250 * p["n_buckets"] == 250_000


def test_generator_gives_every_seed_the_same_work():
    cell = small(40)
    a, b = trace_of(cell, 1), trace_of(cell, 2**31 + 11)
    assert hashlib.sha256(a.events.tobytes()).digest() != \
        hashlib.sha256(b.events.tobytes()).digest()
    for tr in (a, b):
        ev = tr.events
        assert len(ev) == 40 * 10 + 40 * 250
        assert (np.diff(ev["t"]) > 0).all()
        assert (ev["size"] == 1024).all()
        assert (ev["op"] == OP_PUT).sum() == 400 + round(0.05 * 10_000)
        assert (ev["op"] == OP_GET).sum() == 10_000 - 500
        per_bucket = np.bincount(ev["bucket"], minlength=40)
        assert (per_bucket == 10 + 250).all()
        assert (ev["obj"] // 10 == ev["bucket"]).all()
        # Each bucket is read from 3 regions and written only at its base.
        for bk in range(40):
            mine = ev[ev["bucket"] == bk]
            assert len(np.unique(mine["region"])) <= 3
            assert len(np.unique(mine["region"][mine["op"] == OP_PUT])) == 1
    assert a.duration == pytest.approx(b.duration, rel=1e-3)
    assert a.duration == pytest.approx(31.25 * 86400.0, rel=1e-3)


def test_generator_is_deterministic_in_the_seed():
    gen = harness.generator({"generator": "ycsb_tenants"})
    kw = dict(n_buckets=8, n_requests=800)
    a, b = gen(REGIONS, 7, **kw), gen(REGIONS, 7, **kw)
    assert np.array_equal(a.events, b.events)
    assert a.buckets == b.buckets and len(a.buckets) == 8


@pytest.mark.parametrize("plane", ["sim", "live"])
def test_program_matches_the_sweep_reference(plane):
    cell = small(20)
    cfg = dict(cell.config, plane=plane)
    trace = trace_of(cell, 2**31 + 5)
    run = system.run_plane(cfg, trace, system.cost_model(cfg))
    ref = skystore_fb_sweep.replay(cfg, trace.events, trace.regions,
                                   trace.buckets)
    assert run.policy.ctl.resolve_engine() == "numpy"
    assert ref["sweep_pairs"] > 100 and ref["sweeps"] > 10
    assert run.policy.ctl.n_refreshes == 0
    for c in DRV.compare(ref, [DRV.result(run)]):
        assert c.ok, (c.name, c.value)


@pytest.mark.parametrize("fault", [{"precision": "bfloat16"},
                                   {"skip_pair": True}])
def test_fault_in_the_references_place_fails(fault):
    """The reference with its surface in bfloat16, or with one pair of
    every sweep left out, in the program's place."""
    cell = small(60)
    trace = trace_of(cell, 3)
    args = (cell.config, trace.events, trace.regions, trace.buckets)
    ref = skystore_fb_sweep.replay(*args)
    bad = skystore_fb_sweep.replay(*args, **fault)
    assert all(c.ok for c in DRV.compare(ref, [ref]))
    failed = {c.name for c in DRV.compare(ref, [bad]) if not c.ok}
    assert "edge_ttls_differing" in failed, failed


def run(cell, seed=11):
    return runner.run_cell(cell, seed, 0.5, False, dict(CPU),
                           time.perf_counter())


def test_sound_run_is_correct():
    out = run(small(20))
    assert out["correct"], [(c.name, c.value) for c in out["checks"]]
    assert {c.name for c in out["checks"]} >= {"sweep_pairs_differing",
                                               "edge_ttls_differing",
                                               "decisions_differing"}
    assert set(out["metrics"]) == {"setup_s", "replay_events_per_s"}


def test_sweep_that_skips_a_pair_is_caught(monkeypatch):
    from repro.core.ttl_policy import AdaptiveTTLController

    orig = AdaptiveTTLController._sweep_batched

    def skipped(self, merged, dsts, engine):
        ttls, costs = orig(self, merged, dsts, engine)
        ttls = np.array(ttls)
        ttls[0] = [self.cost.t_even_seconds(s, dsts[0])
                   for s in self._srcs(dsts[0])]
        return ttls, costs

    monkeypatch.setattr(AdaptiveTTLController, "_sweep_batched", skipped)
    out = run(small(20))
    assert not out["correct"]
    assert not next(c for c in out["checks"]
                    if c.name == "edge_ttls_differing").ok


def test_traced_window_times_the_tick_and_reports_no_refreshes():
    from repro.core.policies import SkyStorePolicy

    cell = small(8)
    orig = SkyStorePolicy.__dict__["periodic"]
    spans = harness.Spans()
    ctx = runner.Context(cell, 5, 0.1, True, spans,
                         harness.generator(cell.traffic))
    st = DRV.setup(ctx)
    try:
        win = DRV.window(ctx, st)
        assert SkyStorePolicy.__dict__["periodic"] is not orig
    finally:
        spans.unwrap()
    assert SkyStorePolicy.__dict__["periodic"] is orig
    assert "ttl_refreshes" not in win["counters"]
    assert win["counters"]["sweep_pairs"] > 0
    assert len(spans.intervals["bench.tick"]) == 31 * win["counters"]["replays"]


class Device:
    def __init__(self, inside):
        self.inside = inside

    def seconds_inside(self, span):
        return self.inside.get(span, 0.0)


def record(counters, inside, spans=()):
    cfg = harness.load_cell(CELL).config
    s = harness.Spans()
    for a, b in spans:
        s.add("bench.tick", a, b)
    return runner.RunRecord("replay_sweep", cfg, (0.0, 10.0), s, counters,
                            {}, Device(inside), "TPU v5 lite")


def test_metric_readers():
    from bench.device import peaks, ttl_work

    tick = harness.metric_reader("tick_share.replay")
    roof = harness.metric_reader("sweep_roofline")
    rec = record({"sweep_pairs": 3000}, {"bench.tick": 0.01},
                 [(1.0, 2.0), (1.5, 2.5), (9.5, 11.0)])
    assert tick(rec) == pytest.approx(100.0 * 2.0 / 10.0)
    least = ttl_work.least_seconds(3000, 8, 800, peaks.peaks("TPU v5 lite"))
    assert roof(rec) == pytest.approx(100.0 * least / 0.01)
    assert 0 < roof(rec) < 100
    # Nothing to read: the metrics fall out of the line.
    assert tick(record({}, {})) is None
    assert roof(record({"sweep_pairs": 0}, {"bench.tick": 0.01})) is None
    assert roof(record({"sweep_pairs": 5}, {})) is None
