"""Served traffic: YCSB-shaped requests over HTTP into one S3Proxy endpoint
per region, open loop at a fixed rate, one request in flight.

Set-up loads the records in process through ``VirtualStore.dispatch``, then
runs the first ``warm_virtual_days`` of requests (virtual time from the
first request) the same way: every bucket's requests have begun, the
histograms fill and the daily TTL refreshes start.  The last
``warm_http_requests`` of those go over HTTP.  The window sends request ``i`` at ``t0 + i / rate_per_s`` through the
endpoint of its own region; a request that finds the previous one still
running waits, and its latency counts from its due time.  The store's
virtual clock follows the trace's timestamps; at each virtual day boundary
the deployment's daily work (eviction scan and the policy's census) runs
between two requests.

Every GET must return, byte for byte and with its MD5 ETag, the body of the
last acknowledged PUT of its key.  The TTL selection is held to the plain
reference replayed over the same requests: once the window has closed, every
edge's TTL must be the reference's.
"""

from __future__ import annotations

import bisect
import hashlib
import http.client
import math
import time

import numpy as np

from bench import system
from bench.harness import Check
from bench.reference import skystore_fb
from bench.stats import percentile

DAY = 24 * 3600.0
#: Finish the sleep to a request's due time by spinning for this long.
SPIN_S = 0.0005
#: Virtual days of requests generated beyond the window's last.
WINDOW_MARGIN_DAYS = 0.05


class VirtualClock:
    """The store's clock, set by the client before each request."""

    def __init__(self) -> None:
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def requests(trace, bodies_seed: int, record_bytes: int):
    """The trace as (t, op, bucket, key, region, body) rows, LISTs left out;
    each PUT carries a fresh seeded body."""
    from repro.core.traces import OP_GET, OP_PUT

    ev = trace.events
    keep = (ev["op"] == OP_GET) | (ev["op"] == OP_PUT)
    ev = ev[keep]
    n_put = int((ev["op"] == OP_PUT).sum())
    blob = np.random.default_rng(bodies_seed).bytes(n_put * record_bytes)
    rows, j = [], 0
    for t, op, obj, region, bucket in zip(
            ev["t"].tolist(), ev["op"].tolist(), ev["obj"].tolist(),
            ev["region"].tolist(), ev["bucket"].tolist()):
        body = None
        if op == OP_PUT:
            body = blob[j * record_bytes:(j + 1) * record_bytes]
            j += 1
        rows.append((t, "PUT" if op == OP_PUT else "GET",
                     trace.buckets[bucket], str(obj), trace.regions[region],
                     body))
    return rows


def setup(ctx) -> dict:
    from repro.core import VirtualStore, make_backends
    from repro.core.api import CreateBucketRequest, GetRequest, PutRequest
    from repro.core.policies import make_policy
    from repro.core.s3_proxy import S3Proxy
    from repro.core.traces import OP_GET, OP_PUT

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    cost = system.cost_model(cfg)
    p = dict(tr["params"])
    per_day = p["requests_per_bucket_day"] * p["n_buckets"]
    n_window = int(math.ceil(tr["rate_per_s"] * ctx.seconds))
    # Bucket 0's requests end first: they must outlast the window.
    p["n_requests"] = int(math.ceil(
        per_day * (tr["warm_virtual_days"] + WINDOW_MARGIN_DAYS) + n_window))
    trace = ctx.generator(cost.region_names(), ctx.seed, **p)
    rows = requests(trace, ctx.seed, p["size_range"][1])
    # The records' first PUTs come before every request.
    n_load = p["n_objects"]
    start = rows[n_load][0] + tr["warm_virtual_days"] * DAY
    n_warm = bisect.bisect_left([r[0] for r in rows], start) - n_load
    rows = rows[:n_load + n_warm + n_window]
    ev = trace.events
    served = ev[(ev["op"] == OP_GET) | (ev["op"] == OP_PUT)][:len(rows)]

    system.warm(ctx, cfg, cost)
    clock = VirtualClock()
    policy = make_policy(cfg["policy"], cost, **cfg["policy_params"])
    store = VirtualStore(cost, make_backends(list(cost.region_names()),
                                             cfg["backends"]),
                         mode=cfg["mode"], policy=policy, clock=clock)
    st = {"cfg": cfg, "store": store, "clock": clock, "next_day": DAY,
          "acked": {}, "rate": tr["rate_per_s"], "events": served,
          "regions": trace.regions, "buckets": trace.buckets}
    for b in trace.buckets:
        store.dispatch(CreateBucketRequest(b))
    n_http = tr["warm_http_requests"]
    for t, op, bucket, key, region, body in rows[:n_load + n_warm - n_http]:
        _advance(st, t)
        if op == "PUT":
            store.dispatch(PutRequest(bucket, key, region, body=body))
            st["acked"][(bucket, key)] = body
        else:
            store.dispatch(GetRequest(bucket, key, region))
    st["proxies"] = {r: S3Proxy(store, r).start() for r in cost.region_names()}
    st["conns"] = {r: http.client.HTTPConnection(*px.httpd.server_address[:2],
                                                 timeout=60)
                   for r, px in st["proxies"].items()}
    # The wire path's first requests, closed loop, before the window.
    for t, op, bucket, key, region, body in rows[n_load + n_warm - n_http:
                                                 n_load + n_warm]:
        _advance(st, t)
        if _send(st, op, bucket, key, region, body)[0] == 200 and op == "PUT":
            st["acked"][(bucket, key)] = body
    st["rows"] = rows[n_load + n_warm:]
    if len(st["rows"]) != n_window:
        raise RuntimeError(f"the trace holds {len(st['rows'])} requests for "
                           f"a window of {n_window}")
    return st


def _advance(st: dict, t: float, spans=None) -> None:
    """Run the daily work of every virtual day boundary up to ``t``, then
    set the store's clock to ``t``."""
    while st["next_day"] <= t:
        day = st["next_day"]
        st["clock"].t = day
        if spans is None:
            st["store"].policy_tick(day)
        else:
            with spans.span("bench.background"):
                st["store"].policy_tick(day)
        st["next_day"] = day + DAY
    st["clock"].t = t


def _send(st, op, bucket, key, region, body):
    conn = st["conns"][region]
    conn.request(op, f"/{bucket}/{key}", body=body)
    resp = conn.getresponse()
    data = resp.read()
    return resp.status, data, resp.getheader("ETag")


def window(ctx, st: dict) -> dict:
    store, rows = st["store"], st["rows"]
    rate = st["rate"]
    spans = ctx.spans
    if ctx.trace:
        spans.wrap(store, "dispatch", "bench.dispatch")
    ctl = getattr(store.policy, "ctl", None)
    refreshes0 = getattr(ctl, "n_refreshes", None)
    # Flat lists of numbers, bytes and strings: no container is allocated per
    # request, so the window adds little for the garbage collector to trace.
    lag, latency, statuses, bodies, etags = [], [], [], [], []
    perf = time.perf_counter
    try:
        with spans.span("bench.window"):
            t0 = perf() + 0.01
            for i, (t, op, bucket, key, region, body) in enumerate(rows):
                due = t0 + i / rate
                wait = due - perf()
                if wait > SPIN_S:
                    time.sleep(wait - SPIN_S)
                while perf() < due:
                    pass
                _advance(st, t, spans)
                sent = perf()
                with spans.span("bench.http"):
                    status, data, etag = _send(st, op, bucket, key, region,
                                               body)
                done = perf()
                lag.append(sent - due)
                latency.append(done - due)
                statuses.append(status)
                bodies.append(data)
                etags.append(etag)
            t1 = perf()
    finally:
        for conn in st["conns"].values():
            conn.close()
        for px in st["proxies"].values():
            px.stop()
    answers = list(zip(statuses, bodies, etags))
    gets = [s for row, s in zip(rows, latency) if row[1] == "GET"]
    refreshes = getattr(ctl, "n_refreshes", None)
    worst = max(range(len(lag)), key=lag.__getitem__)
    ctx.log(f"served {len(rows)} requests at {rate} req/s over {t1 - t0!r} s; "
            f"TTL refreshes {refreshes0} -> {refreshes}; latest send "
            f"{lag[worst]!r} s late at request {worst}; "
            f"{sum(x > 0.1 for x in lag)} sent over 100 ms late")
    return {"t0": t0, "t1": t1, "attempted": len(rows),
            "failed": sum(1 for s, _d, _e in answers if s != 200),
            "e2e": {"get_p50_ms": 1e3 * percentile(gets, 50)},
            "counters": {"ttl_refreshes": (None if refreshes is None
                                           else refreshes - refreshes0)},
            "samples": {"send_lag_s": lag, "get_latency_s": gets},
            "answers": answers}


def compare(rows, answers, acked: dict) -> list:
    """Read-your-writes over the wire: every GET returns the body (and MD5
    ETag) of the last acknowledged PUT of its key."""
    acked = dict(acked)
    bodies = etags = statuses = 0
    for (t, op, bucket, key, region, body), (status, data, etag) in zip(
            rows, answers):
        if status != 200:
            statuses += 1
            continue
        if op == "PUT":
            acked[(bucket, key)] = body
            continue
        want = acked.get((bucket, key))
        if data != want:
            bodies += 1
        elif etag != f'"{hashlib.md5(want).hexdigest()}"':
            etags += 1
    statuses += abs(len(rows) - len(answers))
    return [Check("get_bodies_differing", bodies, 0),
            Check("get_etags_differing", etags, 0),
            Check("requests_not_ok", statuses, 0)]


def reference_ttls(st: dict, precision: str = "float64") -> dict:
    """The plain reference replayed over every request the store served."""
    return skystore_fb.replay(st["cfg"], st["events"], st["regions"],
                              st["buckets"], precision=precision)


def program_ttls(st: dict) -> dict:
    """The store's TTL state once the window has closed: every edge's TTL."""
    ctl = st["store"].policy.ctl
    return {"edge_ttls": {k: e.ttl_seconds for k, e in ctl.edge_ttls.items()}}


def compare_ttls(ref: dict, got: dict) -> list:
    """Every edge's TTL, exactly."""
    a, b = ref["edge_ttls"], got["edge_ttls"]
    edges = sum(1 for k in set(a) | set(b) if a.get(k) != b.get(k))
    return [Check("edge_ttls_differing", edges, 0)]


def check(ctx, st: dict, win: dict) -> list:
    return (compare(st["rows"], win["answers"], st["acked"])
            + compare_ttls(reference_ttls(st), program_ttls(st)))
