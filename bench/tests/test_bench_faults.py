"""``correct`` comes out false when it should.

Each test skips the harness's look for a chip and drives the rest of a run
(set-up, window, comparison with the reference) at a small size on this CPU
host, with one fault planted in the timed path, or with the control in the
program's place.  Faults that the cells can have:

* a TTL refresh that returns its state unchanged;
* half of a refresh's edges left out;
* an answer altered where it is produced (a TTL; a GET body; a PUT body).

The exchange between chips has no place here: every cell runs on one chip.
"""

import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, readings, runner  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def small(cell_name):
    cell = harness.load_cell(cell_name)
    if cell.traffic["generator"] == "t_profile":
        cell.traffic["params"].update(n_objects=40, months=2.0)
    elif cell.traffic["driver"] == "replay":
        cell.traffic["params"].update(n_objects=400, n_requests=4000,
                                      n_buckets=4, requests_per_bucket_day=500)
    else:
        cell.traffic.update(rate_per_s=400, warm_http_requests=20)
        cell.traffic["params"].update(n_objects=300,
                                      requests_per_bucket_day=1000)
    return cell


def run(cell, seed=11, seconds=0.5):
    return runner.run_cell(cell, seed, seconds, False, dict(CPU),
                           time.perf_counter())


@pytest.mark.parametrize("cell", ["sim9_t65_mixE", "store9_ycsb_b_replay",
                                  "store9_ycsb_b_served"])
def test_sound_run_is_correct(cell):
    out = run(small(cell))
    assert out["correct"], [(c.name, c.value) for c in out["checks"]]


@pytest.fixture
def ctl_class():
    from repro.core.ttl_policy import AdaptiveTTLController
    return AdaptiveTTLController


def test_refresh_that_returns_its_state_unchanged(monkeypatch, ctl_class):
    def stamp_only(self, bucket, dst, now):
        if now - self.last_refresh.get((bucket, dst), -np.inf) >= self.refresh_period:
            self.last_refresh[(bucket, dst)] = now

    monkeypatch.setattr(ctl_class, "_maybe_refresh", stamp_only)
    assert not run(small("sim9_t65_mixE"))["correct"]


def test_half_of_the_edges_left_out(monkeypatch, ctl_class):
    orig = ctl_class._refresh_batched

    def half(self, merged, dst, srcs, engine):
        ttls, costs = orig(self, merged, dst, srcs, engine)
        ttls = np.array(ttls, dtype=float)
        for i in range(len(srcs) // 2, len(srcs)):
            ttls[i] = self.cost.t_even_seconds(srcs[i], dst)
        return ttls, costs

    monkeypatch.setattr(ctl_class, "_refresh_batched", half)
    assert not run(small("store9_ycsb_b_replay"))["correct"]


@pytest.mark.parametrize("cell", ["sim9_t65_mixE", "store9_ycsb_b_served"])
def test_ttl_altered_where_it_is_produced(monkeypatch, ctl_class, cell):
    orig = ctl_class._refresh_batched

    def altered(self, merged, dst, srcs, engine):
        ttls, costs = orig(self, merged, dst, srcs, engine)
        ttls = np.array(ttls, dtype=float)
        ttls[0] = ttls[0] * 1.5 + 60.0
        return ttls, costs

    monkeypatch.setattr(ctl_class, "_refresh_batched", altered)
    out = run(small(cell))
    assert not out["correct"]
    if cell == "store9_ycsb_b_served":
        failed = {c.name for c in out["checks"] if not c.ok}
        assert failed == {"edge_ttls_differing"}, failed


def test_get_body_altered_where_it_is_produced(monkeypatch):
    import dataclasses

    from repro.core.virtual_store import VirtualStore

    orig = VirtualStore._handle_get
    calls = []

    def altered(self, op, *a, **k):
        resp = orig(self, op, *a, **k)
        calls.append(1)
        if len(calls) % 97 == 0:
            body = bytes([resp.body[0] ^ 1]) + resp.body[1:]
            resp = dataclasses.replace(resp, body=body)
        return resp

    monkeypatch.setattr(VirtualStore, "_handle_get", altered)
    out = run(small("store9_ycsb_b_served"))
    assert not out["correct"]


def test_put_body_altered_where_it_is_produced(monkeypatch):
    import dataclasses

    from repro.core.virtual_store import VirtualStore

    orig = VirtualStore._handle_put
    calls = []

    def altered(self, op):
        calls.append(1)
        if op.body and len(calls) > 1 and len(calls) % 25 == 0:
            op = dataclasses.replace(op, body=op.body[:-1] + b"\x00")
        return orig(self, op)

    monkeypatch.setattr(VirtualStore, "_handle_put", altered)
    assert not run(small("store9_ycsb_b_served"))["correct"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_the_replay_comparison(seed):
    vals = readings.replay_readings(small("sim9_t65_mixE"), seed, control=True)
    assert vals["decisions_differing"] > 0 or vals["bill_rel_gap"] > 1e-9


def test_control_fails_the_served_comparison():
    cell = small("store9_ycsb_b_served")
    drv = harness.driver(cell.traffic)
    ctx = runner.Context(cell, 5, 0.5, False, harness.Spans(),
                         harness.generator(cell.traffic))
    st = drv.setup(ctx)
    win = drv.window(ctx, st)
    stale = readings.stale_answers(st["rows"], win["answers"], st["acked"])
    checks = drv.compare(st["rows"], stale, st["acked"])
    assert not all(c.ok for c in checks)
    assert all(c.ok for c in drv.compare(st["rows"], win["answers"],
                                         st["acked"]))
    ref = drv.reference_ttls(st)
    assert all(c.ok for c in drv.compare_ttls(ref, drv.program_ttls(st)))
    low = drv.reference_ttls(st, "bfloat16")
    assert not all(c.ok for c in drv.compare_ttls(ref, low))
