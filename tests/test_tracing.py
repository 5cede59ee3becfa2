"""The in-program span recorder (repro.core.tracing): recording changes no
decision, leaves no method patched, refuses a span table that names a
missing method, does exact self-time arithmetic, and sees the routing
counters, the TTL scan counters and the S3 request path."""

import http.client
import threading

import pytest

from repro.core import VirtualStore, make_backends, pick_regions, tracing
from repro.core.replay import run_live_plane, run_sim_plane
from repro.core.s3_proxy import S3Proxy
from repro.core.workloads import make_workload

PLANES = {"sim": run_sim_plane, "live": run_live_plane}


@pytest.fixture(scope="module")
def cost9():
    return pick_regions(9)


@pytest.fixture(scope="module")
def trace9(cost9):
    return make_workload("zipfian", cost9.region_names(), seed=7)


@pytest.fixture(autouse=True)
def no_recording_left():
    yield
    if tracing.recording():
        tracing.stop()
        pytest.fail("a test left the recorder running")


def _recorded(plane, trace, cost, **kw):
    tracing.start()
    try:
        run = PLANES[plane](trace, cost, "skystore", engine="numpy", **kw)
    finally:
        snap = tracing.stop()
    return run, snap


@pytest.mark.parametrize("plane", sorted(PLANES))
def test_recording_changes_no_result(plane, trace9, cost9):
    off = PLANES[plane](trace9, cost9, "skystore", engine="numpy")
    on, snap = _recorded(plane, trace9, cost9)
    assert on.decisions == off.decisions
    assert on.holders == off.holders
    assert on.report.counters() == off.report.counters()
    assert on.report.components() == off.report.components()
    assert on.policy.ctl.n_refreshes == off.policy.ctl.n_refreshes
    # The recording saw the replay and every layer it crosses.
    assert [s.name for s in snap.kept if s.parent < 0] == ["skystore.replay.run"]
    layers = snap.layer_seconds()
    want = {"event spine", "control plane", "charges", "policy TTL selection"}
    if plane == "live":
        want.add("typed ops")
    assert want <= {k for k, v in layers.items() if v > 0}
    assert sum(layers.values()) == pytest.approx(snap.interval_s)
    # Every solved refresh is one refresh span with a scan phase in it.
    kids = snap.children()
    solved = [i for i, s in enumerate(snap.kept)
              if s.name == "skystore.ttl.refresh"
              and any(snap.kept[c].name == "skystore.ttl.scan"
                      for c in kids.get(i, ()))]
    assert len(solved) == on.policy.ctl.n_refreshes > 0
    assert snap.counters["expiry.pops"] > 0


def test_stop_restores_every_patched_method():
    import importlib

    def current():
        out = {}
        for module, cls, attr, _name in tracing.SPANS + (tracing.SPINE + ("",),):
            owner = getattr(importlib.import_module(f"repro.core.{module}"), cls)
            out[(cls, attr)] = owner.__dict__[attr]
        return out

    before = current()
    tracing.start()
    during = current()
    tracing.stop()
    after = current()
    assert all(during[k] is not before[k] for k in before)
    assert all(after[k] is before[k] for k in before)


@pytest.mark.parametrize("entry", [
    ("metadata", "MetadataServer", "locate_renamed", "skystore.meta.locate"),
    ("metadata", "NoSuchClass", "locate", "skystore.meta.locate"),
])
def test_missing_span_method_refuses_to_start(entry, monkeypatch):
    monkeypatch.setattr(tracing, "SPANS", tracing.SPANS + (entry,))
    with pytest.raises(AttributeError, match=entry[2]):
        tracing.start()
    assert not tracing.recording()


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("cost_agg, cost_kept", [(0.0, 0.0), (0.5, 1.0)])
def test_self_time_arithmetic(cost_agg, cost_kept, monkeypatch):
    """Under a clock that moves only when told: an outer kept span holds an
    aggregated child with an aggregated grandchild, then a kept child; a
    second thread runs its own span while the outer one is open."""
    clock = FakeClock()
    monkeypatch.setattr(tracing, "perf", clock)

    def calibrate(rec):
        rec.cost_agg, rec.cost_kept = cost_agg, cost_kept

    monkeypatch.setattr(tracing._Recorder, "calibrate", calibrate)
    monkeypatch.setattr(tracing, "SPANS", ())
    tracing.start()
    rec = tracing._active

    def step(dt):
        clock.t += dt

    grandchild = rec.aggregated(lambda: step(4), "skystore.meta.b")

    def a():
        step(2)
        grandchild()

    child = rec.aggregated(a, "skystore.ledger.a")

    def other_thread():
        with tracing.span("skystore.s3.request"):
            step(7)

    with tracing.span("skystore.replay.run"):
        step(1)
        child()
        t = threading.Thread(target=other_thread)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        with tracing.span("skystore.ttl.refresh"):
            step(5)
    snap = tracing.stop()

    ca, ck = cost_agg, cost_kept
    spans = snap.spans
    assert spans["skystore.meta.b"].self_s == 4
    assert spans["skystore.ledger.a"].self_s == 6 - (4 + ca)
    assert spans["skystore.ledger.a"].total_s == 6
    assert spans["skystore.ttl.refresh"].self_s == 5
    assert spans["skystore.s3.request"].self_s == 7
    # The other thread's span is not a child of the open outer span.
    outer = spans["skystore.replay.run"]
    assert outer.self_s == 19 - (6 + ca) - (5 + ck)
    assert outer.total_s == 19
    assert snap.n_spans == {"aggregated": 2, "kept": 3}
    assert snap.interval_s == 19 - 2 * ca - 3 * ck
    by_name = {s.name: s for s in snap.kept}
    assert by_name["skystore.s3.request"].thread != by_name[
        "skystore.replay.run"].thread
    assert by_name["skystore.s3.request"].parent == -1
    assert snap.kept[by_name["skystore.ttl.refresh"].parent].name == (
        "skystore.replay.run")
    layers = snap.layer_seconds()
    assert sum(layers.values()) == pytest.approx(snap.interval_s)


@pytest.mark.parametrize("plane", sorted(PLANES))
@pytest.mark.parametrize("routing", ["python", "auto"])
def test_routing_counters(plane, routing, trace9, cost9):
    run, snap = _recorded(plane, trace9, cost9, routing=routing)
    hinted = snap.counters["routing.get_hinted"]
    scalar = snap.counters["routing.get_scalar"]
    gets = run.report.counters()["n_get"]
    assert hinted + scalar == gets
    if routing == "python":
        assert hinted == 0
    else:
        assert hinted > 0


@pytest.mark.parametrize("plane", sorted(PLANES))
@pytest.mark.parametrize("engine", ["jax", "numpy"])
def test_ttl_scan_counters(plane, engine, trace9, cost9):
    """Each refresh the jax engine solves is one device program, counted
    in ``ttl.device_scans``, and the edge rows it left to the host's
    float64 re-solve in ``ttl.near_tie_rows``; the numpy engine runs
    none."""
    tracing.start()
    try:
        run = PLANES[plane](trace9, cost9, "skystore", engine=engine)
    finally:
        snap = tracing.stop()
    n = run.policy.ctl.n_refreshes
    assert n > 0
    scans = snap.counters.get("ttl.device_scans", 0)
    compiles = snap.counters.get("ttl.scan_compiles", 0)
    near = snap.counters.get("ttl.near_tie_rows", 0)
    assert near == run.policy.ctl.n_near_tie_rows
    if engine == "jax":
        assert scans == n
        assert compiles <= 1
        assert near <= n * (len(cost9.region_names()) - 1)
    else:
        assert scans == compiles == near == 0


def test_s3_request_holds_the_dispatch_on_the_proxy_thread():
    cat = pick_regions(3)
    store = VirtualStore(cat, make_backends(list(cat.region_names()),
                                            "memory"), mode="FB")
    region = cat.region_names()[0]
    store.create_bucket("b")
    store.put_object("b", "k", b"payload", region)
    proxy = S3Proxy(store, region).start()
    try:
        tracing.start()
        conn = http.client.HTTPConnection(*proxy.httpd.server_address[:2],
                                          timeout=10)
        # The second request, on the same connection and so the same
        # handler thread, is answered only after the first span closed.
        for _ in range(2):
            conn.request("GET", "/b/k")
            resp = conn.getresponse()
            assert resp.status == 200 and resp.read() == b"payload"
        conn.close()
        snap = tracing.stop()
    finally:
        proxy.stop()
    requests = [i for i, s in enumerate(snap.kept)
                if s.name == "skystore.s3.request"]
    assert len(requests) == 2
    request = snap.kept[requests[0]]
    assert request.thread != 0       # thread 0 started the recording
    dispatch = [snap.kept[c] for c in snap.children().get(requests[0], ())
                if snap.kept[c].name == "skystore.store.dispatch"]
    assert len(dispatch) == 1 and dispatch[0].thread == request.thread
    assert request.start <= dispatch[0].start <= dispatch[0].end <= request.end
    assert request.self_s < request.seconds
