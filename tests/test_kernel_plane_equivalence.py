"""Kernel <-> plane decision equivalence (the PR-7 tentpole contract).

The Pallas/jax batched TTL engines are only allowed into the storage planes
because their *decisions* -- not just their cost surfaces -- are pinned to
the scalar pure-Python reference (``engine="python"``, the always-available
oracle).  This suite enforces that on replay-harvested histograms (the real
distribution: sparse cells, censored tails, exact cost-tie plateaus), not
just synthetic random problems:

* controller-level: every engine's ``edge_ttls`` table after a refresh is
  identical (TTL values are exact float64 candidate boundaries);
* plane-level: an end-to-end sim replay with ``engine="kernel"`` produces
  the identical decision stream and cost report as ``engine="python"``;
* edge cases: all-empty histograms (warmup must hold every engine back) and
  surfaces where TTL=0 (evict immediately) wins exactly.
"""

import functools

import numpy as np
import pytest

from repro.core.costmodel import pick_regions
from repro.core.histogram import AccessHistogram, RollingHistogram
from repro.core.simulator import Simulator
from repro.core.policies import make_policy
from repro.core.ttl_policy import AdaptiveTTLController, TTL_ENGINES
from repro.core.workloads import make_workload
from repro.kernels import ops

BATCHED_ENGINES = ("kernel", "jax", "numpy")


@pytest.fixture(autouse=True)
def interpret_kernel(monkeypatch):
    """Off-TPU the controller's ``engine="kernel"`` refreshes run the Pallas
    kernel under its interpreter; the program itself never picks that."""
    monkeypatch.setattr(ops, "ttl_scan_from_histograms", functools.partial(
        ops.ttl_scan_from_histograms, interpret=True))


@pytest.fixture(scope="module")
def harvest():
    """Histograms harvested from a real replay: run the skystore policy
    through a zipfian trace and keep every (bucket, region) collection
    window it accumulated."""
    cat = pick_regions(3)
    tr = make_workload("zipfian", cat.region_names(), seed=11,
                       n_objects=150, n_requests=4000)
    policy = make_policy("skystore", cat)
    sim = Simulator(cat, policy, mode="FB")
    sim.run(tr)
    hists = {key: roll.merged() for key, roll in policy.ctl.hists.items()
             if roll.merged().n_samples > 0}
    assert len(hists) >= 3, "harvest produced too few histograms"
    return cat, hists


def _controller_with(cat, hists, engine, **kw):
    """A fresh controller preloaded with clones of the harvested windows."""
    ctl = AdaptiveTTLController(cat, warmup_min_samples=1, engine=engine,
                                **kw)
    for (bucket, region), h in hists.items():
        roll = RollingHistogram(h.edges)
        roll.current = AccessHistogram(
            h.edges, h.hist.copy(), h.time_weight.copy(), h.last.copy(),
            h.first_read_remote_bytes, h.n_samples)
        ctl.hists[(bucket, region)] = roll
    return ctl


def _refresh_all(ctl, cat, hists):
    """Force one refresh per harvested (bucket, dst) pair; returns the full
    edge-TTL table as {(bucket, src, dst): (ttl, expected_cost)}."""
    for (bucket, dst) in list(hists):
        for src in cat.region_names():
            if src != dst:
                ctl.edge_ttl(bucket, src, dst, now=1.0)
    return {k: (e.ttl_seconds, e.expected_cost)
            for k, e in ctl.edge_ttls.items()}


@pytest.mark.parametrize("engine", BATCHED_ENGINES)
def test_engine_decisions_match_python_on_replay_corpus(engine, harvest):
    """Every batched engine's refresh decisions == the scalar reference's,
    on every replay-harvested histogram and every directed edge."""
    cat, hists = harvest
    want = _refresh_all(_controller_with(cat, hists, "python"), cat, hists)
    got = _refresh_all(_controller_with(cat, hists, engine), cat, hists)
    assert set(got) == set(want)
    for key in want:
        ttl_w, cost_w = want[key]
        ttl_g, cost_g = got[key]
        # TTLs resolve by argmin index onto the float64 candidate grid:
        # equality is exact, never approximate.
        assert ttl_g == ttl_w, (
            f"{engine} chose TTL {ttl_g!r} != python {ttl_w!r} on {key}")
        if engine == "numpy":
            # float64 batched path: bit-identical expected costs too.
            assert cost_g == cost_w, key
        else:
            # float32 engines surface ~1e-6-relative cost wobble; decisions
            # (above) must not.
            assert cost_g == pytest.approx(cost_w, rel=1e-4), key


def test_auto_engine_resolves_to_batched_member():
    cat = pick_regions(3)
    ctl = AdaptiveTTLController(cat)
    assert ctl.engine == "auto"
    assert ctl.resolve_engine() in BATCHED_ENGINES
    assert set(BATCHED_ENGINES) < set(TTL_ENGINES)


def test_plane_level_kernel_vs_python_decision_stream(harvest):
    """End-to-end: a sim replay with the kernel engine in the refresh loop
    emits the identical decision stream and cost report as the scalar
    reference -- the whole-plane version of the contract."""
    cat, _hists = harvest
    tr = make_workload("zipfian", cat.region_names(), seed=13,
                       n_objects=100, n_requests=2500)

    def run(engine):
        policy = make_policy("skystore", cat, engine=engine)
        sim = Simulator(cat, policy, mode="FB", track_decisions=True)
        report = sim.run(tr)
        return report, sim.decisions

    rep_py, dec_py = run("python")
    rep_k, dec_k = run("kernel")
    assert dec_k == dec_py
    assert rep_k.components() == rep_py.components()
    assert rep_k.counters() == rep_py.counters()


@pytest.mark.parametrize("engine", BATCHED_ENGINES)
def test_all_empty_histogram_stays_in_warmup(engine):
    """An empty collection window must not produce TTLs on any engine: the
    warmup guard fires before the engine is ever consulted, and the edge
    query falls back to T_even."""
    cat = pick_regions(3)
    ctl = AdaptiveTTLController(cat, warmup_min_samples=1, engine=engine)
    dst, src = cat.region_names()[:2]
    ctl.hist_for("b", dst)          # materialize an all-zero window
    ttl = ctl.edge_ttl("b", src, dst, now=1.0)
    assert ctl.edge_ttls == {}
    assert ttl == cat.t_even_seconds(src, dst)


@pytest.mark.parametrize("engine", BATCHED_ENGINES)
def test_ttl_zero_wins_exactly(engine):
    """A histogram whose re-reads are all far-future (holding costs dwarf
    refetch egress) must pick candidate 0 -- TTL exactly 0.0, not a small
    float32 rounding -- on every engine, matching python."""
    cat = pick_regions(3)
    dst = cat.region_names()[0]
    src = cat.region_names()[1]
    h = AccessHistogram.empty()
    # one tiny object re-read once a year: storing it for the gap costs far
    # more than refetching it
    year = 365.0 * 24 * 3600.0
    h.add_gaps(np.array([year]), np.array([1024.0]))
    h.add_last(np.array([year]), np.array([1024.0]))

    for eng in ("python", engine):
        ctl = AdaptiveTTLController(cat, warmup_min_samples=1, engine=eng)
        ctl.hists[("b", dst)] = roll = RollingHistogram(h.edges)
        roll.current = AccessHistogram(
            h.edges, h.hist.copy(), h.time_weight.copy(), h.last.copy(),
            h.first_read_remote_bytes, 1)
        ttl = ctl.edge_ttl("b", src, dst, now=1.0)
        assert ttl == 0.0, f"engine {eng} chose {ttl!r}, want exactly 0.0"


def test_canonical_argmin_cuts_the_float32_plateau():
    """Past the last cell holding bytes every candidate costs exactly the
    plateau start in float64; float32 wobble there must not move the
    decision off the plateau start, however wide it is."""
    hist = np.zeros((1, 6))
    last = np.zeros((1, 6))
    hist[0, 1] = 5.0
    last[0, 2] = 1.0              # last held cell 2 -> plateau starts at 3
    wobble = 5.0 * (1.0 - 1e-5)
    surface = np.array([[9.0, 8.0, 7.0, 5.0, wobble, 5.0, wobble]])

    def exact(rows):
        raise AssertionError(f"no near tie to resolve, got rows {rows}")

    assert list(ops.canonical_argmin(surface, hist, last, exact)) == [3]


def test_canonical_argmin_resolves_near_ties_in_float64():
    """Candidates float32 cannot order (within REFINE_BAND of the minimum)
    are decided by the float64 surfaces of those rows only."""
    hist = np.ones((2, 4))
    last = np.zeros((2, 4))
    surface = np.array([[3.0, 1.0, 1.0 + 1e-7, 2.0, 2.5],
                        [3.0, 1.0, 1.5, 2.0, 2.5]])
    float64 = np.array([[3.0, 1.0 + 2e-8, 1.0, 2.0, 2.5]])
    calls = []

    def exact(rows):
        calls.append(list(rows))
        return float64

    assert list(ops.canonical_argmin(surface, hist, last, exact)) == [2, 1]
    assert calls == [[0]]
