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

import jax
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


#: A runner-up exactly at the band's edge above a minimum of 3 (exact in
#: float32 and in float64), and one float32 ulp above it.
AT_BAND = 3.0 * (1.0 + ops.REFINE_BAND)
ABOVE_BAND = float(np.nextafter(np.float32(AT_BAND), np.float32(np.inf)))
WOBBLE = 5.0 * (1.0 - 1e-5)

#: Planted float32 surfaces: each row's last held cell, the float64
#: surfaces the host must be asked for (``None``: none), the candidates
#: chosen and the rows flagged as near ties.
DECISION_CASES = [
    pytest.param([[9.0, 8.0, 7.0, 5.0, WOBBLE, 5.0, WOBBLE]], [2], None,
                 [3], [], id="plateau_cut"),
    pytest.param([[3.0, 1.0, 1.0 + 1e-7, 2.0, 2.5],
                  [3.0, 1.0, 1.5, 2.0, 2.5]], [3, 3],
                 [[3.0, 1.0 + 2e-8, 1.0, 2.0, 2.5]], [2, 1], [0],
                 id="near_tie_resolved_in_float64"),
    pytest.param([[3.0, 1.0, 2.0, 1.0, 2.5]], [3],
                 [[3.0, 1.0, 2.0, 1.0 - 1e-12, 2.5]], [3], [0],
                 id="exact_duplicate_minimum"),
    pytest.param([[5.0, 3.0, AT_BAND, 4.0]], [2],
                 [[5.0, 3.0, 3.0 - 1e-9, 4.0]], [2], [0],
                 id="runner_up_at_band"),
    pytest.param([[5.0, 3.0, ABOVE_BAND, 4.0]], [2], None, [1], [],
                 id="runner_up_one_ulp_above_band"),
    pytest.param([[9.0, 6.0, 5.0, 5.0 * (1 - 1e-6), 5.0 * (1 - 2e-6)]], [1],
                 None, [2], [], id="plateau_wobbles_past_last_held_cell"),
    pytest.param([[4.0, 1.0, 1.0, 1.0]], [-1], None, [0], [],
                 id="row_holding_nothing"),
    pytest.param([[3.0, 1.0, 2.0, 2.5], [0.0] * 4, [0.0] * 4], [2, -1, -1],
                 None, [1, 0, 0], [], id="padded_rows"),
]


@pytest.mark.parametrize("surface,last_cell,float64,want,flagged",
                         DECISION_CASES)
def test_device_decision_and_float64_resolve(surface, last_cell, float64,
                                             want, flagged):
    """The device's reduction of each planted float32 row (argmin of the
    row cut at its plateau, minimum, runner-up), then the host's decision:
    rows whose runner-up lies within REFINE_BAND of the minimum, and only
    those, are decided and priced by their float64 surfaces; the others
    keep the device's argmin and its float32 cost."""
    surface = np.asarray(surface, dtype=np.float32)
    decision = np.asarray(jax.jit(ops.ttl_row_decision)(
        surface, np.asarray(last_cell, dtype=np.float32)))
    assert decision.shape == (3, surface.shape[0])
    calls = []

    def exact(rows):
        calls.append(list(rows))
        return np.asarray(float64)

    grid = np.arange(surface.shape[1], dtype=np.float64)
    ttl, cost = ops.decide_rows(decision, grid, exact)
    assert list(ttl) == want
    assert calls == ([flagged] if flagged else [])
    for i, j in enumerate(want):
        if i in flagged:
            assert cost[i] == float64[flagged.index(i)][j]
        else:
            assert decision[0, i] == j
            assert cost[i] == surface[i, j]
