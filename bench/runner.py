"""One run of a cell: set-up, the measured window, the comparison with the
reference, and the metrics the result line carries (``bench/run.py`` is the
command around it)."""

from __future__ import annotations

import dataclasses
import gc
import sys
import time
from typing import Dict, Optional, Tuple

from bench import harness

#: Entries kept in each list of a traced run's breakdown.
BREAKDOWN_TOP = 10


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's arguments and its spans."""

    cell: harness.Cell
    seed: int
    seconds: float
    trace: bool
    spans: harness.Spans
    generator: object

    @staticmethod
    def log(msg: str) -> None:
        print(f"bench: {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric reads (``bench/metrics/<name>.py``)."""

    kind: str
    config: dict
    window: Tuple[float, float]
    spans: harness.Spans
    counters: Dict[str, Optional[int]]
    samples: Dict[str, list]
    device: Optional[object]   # bench.device.trace.Timeline, traced runs
    device_kind: str

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


#: The program's TTL selection entry points, timed as span ``bench.ttl``.
TTL_ENTRY_POINTS = ("edge_ttl_table", "edge_ttl")


def _span_ttl(spans: harness.Spans) -> None:
    """Host spans around the program's TTL selection (traced runs only).
    Every entry point must exist: a renamed one would silence the TTL
    metrics, so the run stops instead."""
    from repro.core.ttl_policy import AdaptiveTTLController

    missing = [a for a in TTL_ENTRY_POINTS
               if a not in AdaptiveTTLController.__dict__]
    if missing:
        raise SystemExit(f"bench: AdaptiveTTLController has no {missing}; "
                         f"the TTL spans cannot be placed")
    for attr in TTL_ENTRY_POINTS:
        spans.wrap(AdaptiveTTLController, attr, "bench.ttl")


def _require_ttl_spans(record: "RunRecord") -> None:
    """A traced window that solved TTL refreshes has to show them: time in
    ``bench.ttl`` spans and device time inside them.  Otherwise the TTL
    metrics would drop out of the result line without a visible cause."""
    n = record.counters.get("ttl_refreshes")
    if not n:
        return
    if record.spans.total("bench.ttl", record.window) <= 0:
        raise SystemExit(f"bench: {n} TTL refreshes in the window, but no "
                         f"bench.ttl span recorded")
    if record.device.seconds_inside("bench.ttl") <= 0:
        raise SystemExit(f"bench: {n} TTL refreshes in the window, but no "
                         f"device time inside a bench.ttl span")


def run_cell(cell: harness.Cell, seed: int, seconds: float, trace: bool,
             device: dict, t_start: float) -> dict:
    """Set up, measure and check one run of ``cell``; returns the fields of
    the result line."""
    from bench.device import trace as dtrace

    spans = harness.Spans(annotate=trace)
    ctx = Context(cell, seed, seconds, trace, spans,
                  harness.generator(cell.traffic))
    kind = cell.traffic["driver"]
    drv = harness.driver(cell.traffic)
    compiles = harness.CompileCounter()
    st = drv.setup(ctx)
    # Collect the set-up's garbage now: a full collection (some 100 ms with a
    # served store's heap) would otherwise fall into some windows and not
    # others.
    gc.collect()
    setup_s = time.perf_counter() - t_start
    n_compiled = compiles.n
    if trace:
        _span_ttl(spans)
        dtrace.start(harness.TRACE_DIR)
    full_collections = []

    def on_gc(phase, info):
        if phase == "start" and info["generation"] == 2:
            full_collections.append(time.perf_counter())

    gc.callbacks.append(on_gc)
    try:
        win = drv.window(ctx, st)
    finally:
        gc.callbacks.remove(on_gc)
        if trace:
            dtrace.stop()
        spans.unwrap()
    ctx.log(f"full garbage collections in the window: {len(full_collections)}")
    if compiles.n != n_compiled:
        ctx.log(f"WARNING: {compiles.n - n_compiled} program(s) compiled "
                f"inside the window")
    device = dict(device,
                  memory_peak_bytes=harness.memory_peak_bytes(cell.chips))
    checks = drv.check(ctx, st, win)
    out = {"correct": all(c.ok for c in checks), "attempted": win["attempted"],
           "failed": win["failed"], "device": device, "checks": checks,
           "window_s": win["t1"] - win["t0"], "samples": win.get("samples", {})}
    if not trace:
        values = dict(win["e2e"], setup_s=setup_s)
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        return out
    timeline = dtrace.reduce(harness.TRACE_DIR, cell.chips)
    record = RunRecord(kind, cell.config, (win["t0"], win["t1"]), spans,
                       win.get("counters", {}), win.get("samples", {}),
                       timeline, device["kind"])
    _require_ttl_spans(record)
    metrics = {}
    for m in cell.per_layer:
        v = harness.metric_reader(m["name"])(record)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out["metrics"] = metrics
    device.update(busy_s=timeline.busy_s, window_s=timeline.window_s)
    out["breakdown"] = {"device_ops": _top(timeline.program_seconds()),
                        "idle_gaps": _top(timeline.idle_by_label())}
    return out


def _top(seconds: Dict[str, float]) -> list:
    """The ``BREAKDOWN_TOP`` largest entries, as ``[name, seconds]``."""
    ranked = sorted(seconds.items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in ranked[:BREAKDOWN_TOP]]
