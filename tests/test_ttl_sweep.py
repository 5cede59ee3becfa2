"""The ``tick`` refresh schedule: at every maintenance tick one sweep
re-solves every (bucket, region) pair past warm-up, in one engine call.

* The sweep's chosen TTLs are each pair's float64 argmin on every engine
  (numpy, python, jax, the Pallas kernel under its interpreter), at 3 and 9
  regions and 1 to 40 pairs, with a pair under warm-up and a near tie.
* The zero rows a sweep is padded with never reach a decision.
* Reads never refresh; the cached table follows the sweeps.
* The simulator and the live store agree exactly under ``tick``.
* ``access``, the default, leaves the skystore golden fixtures
  byte-identical.
"""

import functools
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.costmodel import pick_regions
from repro.core.replay import (
    COST_RTOL, GOLDEN_SEED, GOLDEN_WORKLOADS, golden_path, rel_delta,
    replay_differential, run_live_plane, run_sim_plane,
)
from repro.core import ttl_policy
from repro.core.ttl_policy import AdaptiveTTLController, choose_ttl, sweep_prices
from repro.core.workloads import make_workload
from repro.kernels import ops
from repro.kernels.ttl_scan import BLOCK_E

DAY = 24 * 3600.0
WARMUP = 8
GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden", "replay")


@pytest.fixture(autouse=True)
def interpret_kernel(monkeypatch):
    """Off-TPU the kernel engine's sweeps and refreshes run the Pallas
    kernel under its interpreter; the program itself never picks that."""
    monkeypatch.setattr(ops, "ttl_sweep", functools.partial(
        ops.ttl_sweep, interpret=True))
    monkeypatch.setattr(ops, "ttl_scan_from_histograms", functools.partial(
        ops.ttl_scan_from_histograms, interpret=True))


def _fill(ctl, cost, n_pairs, seed):
    """``n_pairs`` (bucket, region) pairs with replay-like statistics:
    pair 0 re-reads every byte at exactly one incoming edge's T_even (TTL=0
    and keeping tie on that edge), pair 1 stays under warm-up, the others
    hold random gaps, paused-byte censuses and first reads."""
    rng = np.random.default_rng(seed)
    names = cost.region_names()
    pairs = []
    for i in range(n_pairs):
        bucket, dst = f"b{i // len(names)}", names[i % len(names)]
        pairs.append((bucket, dst))
        if i == 0:
            src = next(s for s in names if s != dst)
            gaps = np.full(40, cost.t_even_seconds(src, dst))
            ctl.record_gaps(bucket, dst, gaps, np.full(40, 4096.0))
            continue
        k = WARMUP - 1 if i == 1 else int(rng.integers(WARMUP, 300))
        ctl.record_gaps(bucket, dst, rng.lognormal(9, 3, k),
                        rng.uniform(1e3, 1e9, k))
        ctl.set_last_snapshot(bucket, dst, rng.lognormal(10, 3, k // 2 + 1),
                              rng.uniform(1e3, 1e9, k // 2 + 1))
        ctl.record_first_read(bucket, dst, float(rng.uniform(1e6, 1e9)),
                              remote=True)
    return pairs


def _device_sweep(ctl, pairs):
    """The jax engine's sweep of ``pairs``, priced as the controller
    prices it."""
    hists = [ctl.hists[p].merged() for p in pairs]
    s, n = sweep_prices(ctl.cost, [p[1] for p in pairs])
    rows = np.asarray([(h.hist, h.time_weight, h.last) for h in hists])
    first = np.asarray([h.first_read_remote_bytes for h in hists])
    return ops.ttl_sweep(rows, first, s, n, hists[0].edges, engine="jax")


@pytest.mark.parametrize("n_pairs", [1, 2, 17, 40])
@pytest.mark.parametrize("n_regions", [3, 9])
@pytest.mark.parametrize("engine", ["numpy", "python", "jax", "kernel"])
def test_sweep_chooses_each_pairs_float64_argmin(monkeypatch, engine,
                                                 n_regions, n_pairs):
    cost = pick_regions(n_regions)
    ctl = AdaptiveTTLController(cost, warmup_min_samples=WARMUP,
                                engine=engine, refresh_schedule="tick")
    pairs = _fill(ctl, cost, n_pairs, seed=n_pairs * n_regions)
    merged = {p: ctl.hists[p].merged() for p in pairs}
    exact_rows = []
    orig = ttl_policy.batched_cost_curves

    def counted(hist, *args, **kw):
        exact_rows.append(hist.shape[0])
        return orig(hist, *args, **kw)

    monkeypatch.setattr(ttl_policy, "batched_cost_curves", counted)
    warm = [p for p in pairs if merged[p].n_samples >= WARMUP]
    assert ctl.sweep(DAY) == len(warm)
    k = n_regions - 1
    assert (ctl.n_sweeps, ctl.n_sweep_pairs, ctl.n_sweep_rows) == (
        1, len(warm), len(warm) * k)
    assert ctl.n_refreshes == 0
    assert ctl.n_device_scans == (engine in ("jax", "kernel"))
    for bucket, dst in pairs:
        for src in cost.region_names():
            if src == dst:
                continue
            got = ctl.edge_ttls.get((bucket, src, dst))
            if (bucket, dst) not in warm:
                assert got is None
                assert ctl.edge_ttl(bucket, src, dst, DAY) == \
                    cost.t_even_seconds(src, dst)
                continue
            want = choose_ttl(merged[(bucket, dst)], cost.storage_price(dst),
                              cost.egress_price(src, dst))
            assert got.ttl_seconds == want, (bucket, src, dst)
            assert got.chosen_at == DAY
    assert set(ctl.last_refresh) == set(warm)
    if engine in ("jax", "kernel"):
        # The T_even pair's tie is resolved in float64.
        assert exact_rows


@pytest.mark.parametrize("n_regions", [3, 9])
@pytest.mark.parametrize("schedule", ["tick", "access"])
@pytest.mark.parametrize("engine", ["jax", "kernel"])
def test_device_engines_decide_as_numpy(engine, schedule, n_regions):
    """A sweep (``tick``) and one refresh a pair (``access``), decided on
    the device, leave the numpy engine's TTL on every edge, and its cost:
    exactly on the rows re-solved in float64 on the host, within the
    float32 rounding the near-tie band rests on elsewhere."""
    cost = pick_regions(n_regions)
    ctls = {}
    for eng in ("numpy", engine):
        ctl = ctls[eng] = AdaptiveTTLController(
            cost, warmup_min_samples=WARMUP, engine=eng,
            refresh_schedule=schedule)
        pairs = _fill(ctl, cost, 17, seed=n_regions)
        if schedule == "tick":
            ctl.sweep(DAY)
        else:
            for bucket, dst in pairs:
                ctl.edge_ttl_table(bucket, dst, DAY)
    want, got = ctls["numpy"].edge_ttls, ctls[engine].edge_ttls
    assert want and set(got) == set(want)
    for key, e in want.items():
        assert got[key].ttl_seconds == e.ttl_seconds, key
        np.testing.assert_allclose(got[key].expected_cost, e.expected_cost,
                                   rtol=ops.REFINE_BAND / 2)
    same = sum(got[k].expected_cost == e.expected_cost
               for k, e in want.items())
    # The T_even pair's tie is re-solved on the host.
    assert 1 <= ctls[engine].n_near_tie_rows <= same
    assert ctls["numpy"].n_near_tie_rows == 0


def test_padding_rows_never_reach_a_decision(monkeypatch):
    """Poison every padding row of the device's decision with a -inf
    minimum and runner-up, which would win any argmin and force a float64
    re-solve if it reached one: the decisions do not move."""
    cost = pick_regions(9)
    ctl = AdaptiveTTLController(cost, warmup_min_samples=WARMUP,
                                engine="jax", refresh_schedule="tick")
    pairs = _fill(ctl, cost, 7, seed=3)
    want = _device_sweep(ctl, pairs)
    orig = ops.ttl_refresh_decision
    seen = []

    def poisoned(packed, edges, n_rows, **kw):
        out = orig(packed, edges, n_rows=n_rows, **kw)
        seen.append((n_rows, out.shape))
        pad = slice(len(pairs) * 8, None)
        return out.at[0, pad].set(0.0).at[1:, pad].set(-jnp.inf)

    monkeypatch.setattr(ops, "ttl_refresh_decision", poisoned)
    got = _device_sweep(ctl, pairs)
    assert seen == [(BLOCK_E // 8, (3, BLOCK_E))]
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    assert np.isfinite(got[1]).all()


def test_sweeps_run_a_handful_of_programs():
    """Sweeps of 1 to 3,000 pairs run at most eight padded programs, none
    over ``SWEEP_MAX_EDGES`` edge rows, and padding at most doubles a
    program's rows (or fills one ``BLOCK_E`` tile)."""
    for k in (2, 5, 8):
        sizes = set()
        for p in range(1, 3001):
            chunks = ops.sweep_chunks(p, k)
            assert chunks[0][0] == 0 and chunks[-1][1] == p
            assert all(a[1] == b[0] for a, b in zip(chunks, chunks[1:]))
            for lo, hi in chunks:
                r = ops.sweep_rows(hi - lo, k)
                sizes.add(r)
                assert hi - lo <= r and r * k <= ops.SWEEP_MAX_EDGES
                assert r * k <= 2 * max(BLOCK_E, (hi - lo) * k)
        assert sorted(sizes) == ops.sweep_programs(3000, k)
        assert len(sizes) <= 8
    assert ops.sweep_programs(3000, 8) == [32 << i for i in range(7)]
    assert ops.sweep_chunks(3000, 8) == [(0, 2048), (2048, 3000)]


def test_reads_never_refresh_on_the_tick_schedule():
    cost = pick_regions(3)
    a, b, c = cost.region_names()
    ctl = AdaptiveTTLController(cost, warmup_min_samples=WARMUP,
                                engine="numpy", refresh_schedule="tick")
    rng = np.random.default_rng(0)
    ctl.record_gaps("b", a, rng.uniform(60, 1e5, 50), rng.uniform(1e3, 1e6, 50))
    even = {s: cost.t_even_seconds(s, a) for s in (b, c)}
    assert ctl.edge_ttl_table("b", a, 30 * DAY) == even
    assert ctl.edge_ttl("b", b, a, 30 * DAY) == even[b]
    assert ctl.n_refreshes == 0 and not ctl.last_refresh
    assert ctl.sweep(DAY) == 1
    solved = {s: ctl.edge_ttls[("b", s, a)].ttl_seconds for s in (b, c)}
    tbl = ctl.edge_ttl_table("b", a, DAY)
    assert tbl == solved
    ctl.record_gaps("b", a, rng.uniform(1e5, 1e6, 50), rng.uniform(1e6, 1e8, 50))
    # No read refreshes, however late: the cached table stands.
    assert ctl.edge_ttl_table("b", a, 90 * DAY) is tbl
    ctl.sweep(2 * DAY)
    assert ctl.edge_ttl_table("b", a, 2 * DAY) == {
        s: ctl.edge_ttls[("b", s, a)].ttl_seconds for s in (b, c)}
    assert ctl.n_sweeps == 2 and ctl.n_refreshes == 0


def test_unknown_refresh_schedule_is_refused():
    with pytest.raises(ValueError, match="refresh schedule"):
        AdaptiveTTLController(pick_regions(3), refresh_schedule="daily")


@pytest.mark.parametrize("engine", ["numpy", "kernel"])
def test_planes_agree_on_the_tick_schedule(engine):
    """Both planes call the policy's maintenance tick at the same instants,
    so the live store sweeps exactly where the simulator does."""
    cost = pick_regions(3)
    trace = make_workload("zipfian", cost.region_names(), seed=7,
                          n_objects=150, n_requests=4000, n_buckets=4)
    kw = dict(refresh_schedule="tick", warmup_min_samples=WARMUP,
              engine=engine)
    sim = run_sim_plane(trace, cost, "skystore", **kw)
    live = run_live_plane(trace, cost, "skystore", **kw)
    assert sim.policy.ctl.n_sweep_pairs > 0
    for attr in ("n_sweeps", "n_sweep_pairs", "n_sweep_rows", "n_refreshes"):
        assert getattr(sim.policy.ctl, attr) == getattr(live.policy.ctl, attr)
    assert sim.decisions == live.decisions
    assert sim.holders == live.holders
    assert sim.report.counters() == live.report.counters()
    a, b = sim.report.components(), live.report.components()
    assert all(rel_delta(a[k], b[k]) <= COST_RTOL for k in a)
    r = replay_differential(trace, cost, "skystore", **kw)
    assert r.ok(), r.summary_line()


@pytest.mark.parametrize("workload", GOLDEN_WORKLOADS)
def test_access_schedule_leaves_the_skystore_fixtures_byte_identical(workload):
    cost = pick_regions(3)
    trace = make_workload(workload, cost.region_names(), seed=GOLDEN_SEED)
    r = replay_differential(trace, cost, "skystore", workload=workload,
                            refresh_schedule="access")
    with open(golden_path(GOLDEN_DIR, workload, "skystore")) as f:
        want = f.read()
    assert json.dumps(r.to_json(), indent=1, sort_keys=True) + "\n" == want


def test_a_sweep_split_over_programs_decides_the_same(monkeypatch):
    """A sweep larger than one program holds runs one program per chunk,
    all started before the first copy back, and decides as one would."""
    cost = pick_regions(9)
    ctl = AdaptiveTTLController(cost, warmup_min_samples=WARMUP,
                                engine="jax", refresh_schedule="tick")
    pairs = _fill(ctl, cost, 40, seed=5)
    want = _device_sweep(ctl, pairs)
    calls = []
    orig = ops.ttl_refresh_decision

    def counted(packed, edges, n_rows, **kw):
        calls.append(n_rows)
        return orig(packed, edges, n_rows=n_rows, **kw)

    monkeypatch.setattr(ops, "ttl_refresh_decision", counted)
    monkeypatch.setattr(ops, "SWEEP_MAX_EDGES", BLOCK_E)
    got = _device_sweep(ctl, pairs)
    assert calls == [32, 32]
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
