"""Replay traffic: one seeded trace, replayed whole, back to back, through
the configuration's plane (the simulator or the live store).

The window starts replays until ``seconds`` have passed; the last one runs
to its end.  ``replay_events_per_s`` is every event of every replay over the
wall time from the first start to the last end.  Each replay's decisions,
holders, counters and bill are compared with the plain reference replay of
the same trace.
"""

from __future__ import annotations

import time

from bench import stats, system
from bench.harness import Check
from bench.reference import skystore_fb

#: Largest relative gap of a bill component to the reference's, set from the
#: program's and the control's readings (PERF.md, section 2).
BILL_REL_GAP_LIMIT = 1e-9


def setup(ctx) -> dict:
    cfg = ctx.cell.config
    cost = system.cost_model(cfg)
    trace = ctx.generator(cost.region_names(), ctx.seed,
                          **ctx.cell.traffic["params"])
    system.warm(ctx, cfg, cost)
    return {"cfg": cfg, "cost": cost, "trace": trace}


def window(ctx, st: dict) -> dict:
    cfg, cost, trace = st["cfg"], st["cost"], st["trace"]
    runs, seconds = [], []
    t0 = time.perf_counter()
    with ctx.spans.span("bench.window"):
        while True:
            t = time.perf_counter()
            with ctx.spans.span("bench.replay"):
                runs.append(result(system.run_plane(cfg, trace, cost)))
            seconds.append(time.perf_counter() - t)
            if time.perf_counter() - t0 >= ctx.seconds:
                break
    t1 = time.perf_counter()
    n = len(trace.events) * len(runs)
    ctx.log(f"replays {len(runs)} of {len(trace.events)} events, seconds "
            f"{seconds!r}")
    refreshes = [r["refreshes"] for r in runs]
    failed = sum(1 for r in runs for d in r["decisions"] if d[-1] == "error")
    return {"t0": t0, "t1": t1, "attempted": n, "failed": failed,
            "e2e": {"replay_events_per_s": stats.rate(n, t1 - t0)},
            "counters": {"ttl_refreshes": (sum(refreshes)
                                           if None not in refreshes else None),
                         "replays": len(runs)},
            "runs": runs}


def result(run) -> dict:
    """What the comparison reads of one of the program's replays, in the
    reference's shape.  The plane's state is dropped here, so that the
    window's heap, which every full garbage collection traces, does not
    grow by a whole deployment with each replay."""
    return {"decisions": run.decisions, "holders": run.holders,
            "counters": run.report.counters(),
            "bill": run.report.components(),
            "refreshes": getattr(run.policy.ctl, "n_refreshes", None)}


def compare(ref: dict, runs) -> list:
    """The numbers a window is judged on: each replay (:func:`result`, or
    a reference replay in the program's place) against the reference."""
    dec = hold = cnt = 0
    gap = 0.0
    for r in runs:
        a, b = r["decisions"], ref["decisions"]
        dec += sum(1 for x, y in zip(a, b) if x != y) + abs(len(a) - len(b))
        keys = set(r["holders"]) | set(ref["holders"])
        hold += sum(1 for k in keys
                    if r["holders"].get(k) != ref["holders"].get(k))
        counters = r["counters"]
        cnt += sum(1 for k, v in ref["counters"].items() if counters.get(k) != v)
        bill = r["bill"]
        for k, v in ref["bill"].items():
            m = max(abs(v), abs(bill[k]))
            gap = max(gap, abs(v - bill[k]) / m if m else 0.0)
    return [Check("decisions_differing", dec, 0),
            Check("holders_differing", hold, 0),
            Check("counters_differing", cnt, 0),
            Check("bill_rel_gap", gap, BILL_REL_GAP_LIMIT)]


def check(ctx, st: dict, win: dict) -> list:
    trace = st["trace"]
    ref = skystore_fb.replay(st["cfg"], trace.events, trace.regions,
                             trace.buckets)
    return compare(ref, win["runs"])
