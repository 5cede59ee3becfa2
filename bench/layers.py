"""Per-layer self time of one cell from the program's own spans.

    python bench/layers.py --workload <cell> --seed <n> --seconds <s> \\
        [--profile 1]

Runs the cell as ``bench/run.py`` does (same set-up, window and comparison
with the reference), with the program's span recorder
(``repro.core.tracing``) on around the window.  ``--profile 1`` also records
the window under ``jax.profiler`` with every kept program span annotated and
the benchmark's own ``bench.*`` spans in place, as a traced run has them,
and adds the device trace's breakdown.  Prints the recorder's largest self
times to stderr and one JSON line: ``correct``, the end-to-end metric as
the window measured it with the recorder on, each layer's share of the
recording, the recorder's cost, the numbers below and the breakdown.  Not
part of a benchmark run.

The numbers (:func:`readings`), each ``None`` where the cell has nothing
to read:

* ``<layer>_share``: self seconds of a layer's spans over the recording's
  interval, in %; ``uncovered_share`` is the interval's time in no span;
* ``ttl_refresh_p50_ms``: median duration of the refreshes that solved, and
  the median of each of their phases (merge, inputs, scan, resolve);
* ``route_scalar_share``: GETs routed by the scalar path over all GETs, %;
* ``expiry_stale_share``: stale expiry pops over all pops, %;
* ``codec_p50_ms``: median self time of the S3 requests (request time
  outside ``VirtualStore.dispatch``), with the medians of the request, the
  dispatch and the wire (the client's round trip outside the request);
* ``stalls``: each round trip over :data:`STALL_S`, longest first, and
  where its time went: before the request's handler started, inside it
  (and how much of that was a TTL refresh), or after it ended.
"""

from __future__ import annotations

import argparse
import bisect
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, runner  # noqa: E402
from bench.stats import percentile  # noqa: E402

#: A round trip this long is a stall.
STALL_S = 0.02
#: The recorder's layers and the name each share is printed under.
SHARES = {"event spine": "spine_share", "typed ops": "store_share",
          "control plane": "control_share", "charges": "charges_share",
          "policy TTL selection": "policy_share", "wire codec": "codec_share",
          "uncovered": "uncovered_share"}
PHASES = ("merge", "inputs", "scan", "resolve")


def _p50_ms(values):
    v = percentile(values, 50)
    return None if v is None else 1e3 * v


def _ratio(part, whole):
    return None if not whole else 100.0 * part / whole


def refreshes(snap, kids) -> dict:
    """Solved refreshes: their durations and those of their phases
    (``kids``: :meth:`Snapshot.children`)."""
    out = {"refresh": []}
    out.update({p: [] for p in PHASES})
    for i, s in enumerate(snap.kept):
        if s.name != "skystore.ttl.refresh":
            continue
        phases = {snap.kept[c].name.rsplit(".", 1)[1]: snap.kept[c].seconds
                  for c in kids.get(i, ())}
        if "scan" not in phases:
            continue                  # stopped at the warm-up gate
        out["refresh"].append(s.seconds)
        for p in PHASES:
            if p in phases:
                out[p].append(phases[p])
    return out


def _refresh_s(snap, kids, index: int) -> float:
    """Seconds of TTL refresh among the kept descendants of a span."""
    total = 0.0
    for c in kids.get(index, ()):
        if snap.kept[c].name == "skystore.ttl.refresh":
            total += snap.kept[c].seconds
        else:
            total += _refresh_s(snap, kids, c)
    return total


def served(snap, kids, http) -> dict:
    """The S3 requests, each beside the client's round trip around it
    (``http``: the ``bench.http`` intervals, sorted)."""
    reqs = [(i, s) for i, s in enumerate(snap.kept)
            if s.name == "skystore.s3.request"]
    starts = [a for a, _b in http]
    codec, request, dispatch, wire, stalls = [], [], [], [], []
    for i, s in reqs:
        codec.append(s.self_s)
        request.append(s.seconds)
        dispatch += [snap.kept[c].seconds for c in kids.get(i, ())
                     if snap.kept[c].name == "skystore.store.dispatch"]
        j = bisect.bisect_right(starts, s.start) - 1
        if j < 0 or http[j][1] < s.end:
            continue                  # no round trip around this request
        a, b = http[j]
        wire.append((b - a) - s.seconds)
        if b - a > STALL_S:
            stalls.append({"round_trip_ms": 1e3 * (b - a),
                           "before_ms": 1e3 * (s.start - a),
                           "inside_ms": 1e3 * s.seconds,
                           "refresh_ms": 1e3 * _refresh_s(snap, kids, i),
                           "after_ms": 1e3 * (b - s.end)})
    stalls.sort(key=lambda x: -x["round_trip_ms"])
    return {"codec_p50_ms": _p50_ms(codec), "request_p50_ms": _p50_ms(request),
            "dispatch_p50_ms": _p50_ms(dispatch), "wire_p50_ms": _p50_ms(wire),
            "requests": len(reqs), "stalls": stalls if reqs else None}


def readings(snap, http=()) -> dict:
    """The numbers of one recording (module docstring)."""
    out = {}
    layers = snap.layer_seconds()
    for layer, key in SHARES.items():
        out[key] = _ratio(layers.get(layer, 0.0), snap.interval_s)
    kids = snap.children()
    ref = refreshes(snap, kids)
    out["ttl_refresh_p50_ms"] = _p50_ms(ref["refresh"])
    out["ttl_refreshes"] = len(ref["refresh"])
    out["ttl_phase_p50_ms"] = {p: _p50_ms(ref[p]) for p in PHASES}
    c = snap.counters
    hinted, scalar = c.get("routing.get_hinted"), c.get("routing.get_scalar")
    out["route_scalar_share"] = (None if hinted is None
                                 else _ratio(scalar, hinted + scalar))
    pops, stale = c.get("expiry.pops"), c.get("expiry.stale")
    out["expiry_stale_share"] = (None if pops is None
                                 else _ratio(stale, pops + stale))
    out.update(served(snap, kids, sorted(http)))
    return out


def top_self(snap, n: int = 12) -> list:
    ranked = sorted(snap.spans.items(), key=lambda kv: -kv[1].self_s)
    return [[k, a.self_s, a.count] for k, a in ranked[:n]]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    device = harness.require_devices(cell.chips)
    harness.setup_compile_cache()
    from bench.device import trace as dtrace
    from repro.core import tracing

    profile = bool(args.profile)
    spans = harness.Spans(annotate=profile)
    ctx = runner.Context(cell, args.seed, args.seconds, profile, spans,
                         harness.generator(cell.traffic))
    drv = harness.driver(cell.traffic)
    st = drv.setup(ctx)
    gc.collect()
    if profile:
        runner._span_ttl(spans)
        dtrace.start(harness.TRACE_DIR)
    tracing.start(annotate=profile)
    try:
        win = drv.window(ctx, st)
    finally:
        snap = tracing.stop()
        if profile:
            dtrace.stop()
        spans.unwrap()
    checks = drv.check(ctx, st, win)
    out = {"workload": cell.name, "seed": args.seed, "device": device,
           "correct": all(c.ok for c in checks), "e2e_recording": win["e2e"],
           "recorder": {"span_cost_us": {k: 1e6 * v for k, v
                                         in snap.span_cost_s.items()},
                        "spans": snap.n_spans, "overhead_s": snap.overhead_s,
                        "interval_s": snap.interval_s,
                        "raw_s": snap.end - snap.start},
           "readings": readings(snap, spans.intervals.get("bench.http", ())),
           "counters": snap.counters}
    for name, own, n in top_self(snap):
        print(f"layers: {name} self {own!r} s over {n} spans", file=sys.stderr)
    if profile:
        timeline = dtrace.reduce(harness.TRACE_DIR, cell.chips)
        out["breakdown"] = {
            "window_s": timeline.window_s, "busy_s": timeline.busy_s,
            "idle_gaps": runner._top(timeline.idle_by_label()),
            "bench_ttl_idle_s": timeline.idle_by_label().get("bench.ttl", 0.0)}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
