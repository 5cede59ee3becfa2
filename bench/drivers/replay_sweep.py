"""Replay traffic on the ``tick`` refresh schedule: no read refreshes a TTL,
and every maintenance tick re-solves every warm (bucket, region) pair in one
sweep.

The window is ``replay.py``'s: one seeded trace, replayed whole, back to
back, until ``seconds`` have passed; ``replay_events_per_s`` is every event
of every replay over the wall time from the first start to the last end.
Each replay is compared with the plain reference of this schedule
(``bench/reference/skystore_fb_sweep.py``) on ``replay.py``'s numbers and
on the pairs its sweeps solved and every edge TTL they left.

Set-up compiles (or loads from the cache) the sweep program of every padded
size the trace can reach.  Traced runs time each maintenance tick (the
paused-bytes census and the sweep) as host span ``bench.tick``.  The window
reports the counters ``sweeps`` and ``sweep_pairs``, and no
``ttl_refreshes``: no read refreshes here.
"""

from __future__ import annotations

import time

import numpy as np

from bench import harness, stats, system
from bench.harness import Check
from bench.reference import skystore_fb_sweep

replay = harness.load_module(harness.BENCH / "drivers" / "replay.py")

#: Gap, in seconds, of the synthetic samples that warm a sweep program.
WARM_GAP_S = 3600.0


def setup(ctx) -> dict:
    cfg = ctx.cell.config
    cost = system.cost_model(cfg)
    trace = ctx.generator(cost.region_names(), ctx.seed,
                          **ctx.cell.traffic["params"])
    warm(ctx, cfg, cost, trace)
    return {"cfg": cfg, "cost": cost, "trace": trace}


def warm(ctx, cfg: dict, cost, trace) -> None:
    """Compile (or load from the cache) every sweep program the replays
    can run: one sweep of each padded size, up to the (bucket, region)
    pairs the trace reads from, through the controller the policy builds."""
    from repro.core.traces import OP_GET
    from repro.core.ttl_policy import AdaptiveTTLController
    from repro.kernels.ops import sweep_programs

    pp = cfg["policy_params"]

    def controller():
        return AdaptiveTTLController(
            cost, refresh_period=pp["refresh_period"],
            warmup_min_samples=pp["warmup_min_samples"],
            u_perf_val_per_gb=pp["u_perf_val_per_gb"], engine=pp["engine"],
            refresh_schedule=pp["refresh_schedule"])

    if controller().resolve_engine() not in ("kernel", "jax"):
        return                      # no device program to compile
    ev = trace.events[trace.events["op"] == OP_GET]
    n_pairs = np.unique(ev["bucket"].astype(np.int64) * len(trace.regions)
                        + ev["region"]).shape[0]
    names = cost.region_names()
    gaps = np.full(pp["warmup_min_samples"], WARM_GAP_S)
    sizes = sweep_programs(n_pairs, len(names) - 1)
    compiled = 0
    for size in sizes:
        ctl = controller()
        for i in range(size):
            ctl.record_gaps(f"warm-{i}", names[i % len(names)], gaps, gaps)
        ctl.sweep(cfg["scan_interval_s"])
        if ctl.n_sweep_pairs != size:
            raise SystemExit(f"bench: the warm-up sweep solved "
                             f"{ctl.n_sweep_pairs} pairs, not {size}")
        compiled += ctl.n_scan_compiles
    ctx.log(f"sweep programs of {sizes} pair rows ({n_pairs} pairs read "
            f"from), {compiled} compiled")


def result(run) -> dict:
    """:func:`replay.result` with the sweep's counts in place of the
    refreshes, and every (bucket, source, region) TTL the last sweeps
    left."""
    out = replay.result(run)
    del out["refreshes"]
    ctl = run.policy.ctl
    out.update(sweeps=ctl.n_sweeps, sweep_pairs=ctl.n_sweep_pairs,
               scan_compiles=ctl.n_scan_compiles,
               edge_ttls={k: e.ttl_seconds for k, e in ctl.edge_ttls.items()})
    return out


def window(ctx, st: dict) -> dict:
    if ctx.trace:
        from repro.core.policies import SkyStorePolicy
        ctx.spans.wrap(SkyStorePolicy, "periodic", "bench.tick")
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
    counters = {"sweeps": sum(r["sweeps"] for r in runs),
                "sweep_pairs": sum(r["sweep_pairs"] for r in runs),
                "scan_compiles": sum(r["scan_compiles"] for r in runs),
                "replays": len(runs)}
    ctx.log(f"replays {len(runs)} of {len(trace.events)} events, seconds "
            f"{seconds!r}; {counters}")
    failed = sum(1 for r in runs for d in r["decisions"] if d[-1] == "error")
    return {"t0": t0, "t1": t1, "attempted": n, "failed": failed,
            "e2e": {"replay_events_per_s": stats.rate(n, t1 - t0)},
            "counters": counters, "runs": runs}


def compare(ref: dict, runs) -> list:
    """:func:`replay.compare`, then against the reference's: the pairs each
    replay's sweeps solved, and each replay's edge TTLs, exactly."""
    pairs = sum(abs(r["sweep_pairs"] - ref["sweep_pairs"]) for r in runs)
    want = ref["edge_ttls"]
    edges = sum(1 for r in runs for k in set(want) | set(r["edge_ttls"])
                if want.get(k) != r["edge_ttls"].get(k))
    return replay.compare(ref, runs) + [
        Check("sweep_pairs_differing", pairs, 0),
        Check("edge_ttls_differing", edges, 0)]


def check(ctx, st: dict, win: dict) -> list:
    trace = st["trace"]
    ref = skystore_fb_sweep.replay(st["cfg"], trace.events, trace.regions,
                                   trace.buckets)
    return compare(ref, win["runs"])
