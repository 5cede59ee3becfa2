"""The system under test, built from a configuration file.

The only module of the benchmark, with the drivers, that calls into the
program (``repro``): it turns a configuration into the program's cost model
and policy arguments, and warms the TTL engine through a short replay.
"""

from __future__ import annotations

import numpy as np

DAY = 24 * 3600.0


def cost_model(cfg: dict):
    """The program's CostModel for the configuration's price catalog."""
    from repro.core.costmodel import CostModel, Region

    regions = [Region(r["name"], r["storage_gb_month"],
                      put_price=r["put_request"], get_price=r["get_request"])
               for r in cfg["regions"]]
    egress = {(a, b): p for a, row in cfg["egress_gb"].items()
              for b, p in row.items()}
    return CostModel(regions, egress)


def plane_kwargs(cfg: dict) -> dict:
    """Keyword arguments of ``repro.core.replay.run_*_plane`` and of the
    policy, as the configuration states them."""
    return dict(mode=cfg["mode"], scan_interval=cfg["scan_interval_s"],
                **cfg["policy_params"])


def run_plane(cfg: dict, trace, cost):
    """One whole replay of ``trace`` through the configuration's plane."""
    from repro.core.replay import run_live_plane, run_sim_plane

    run = run_sim_plane if cfg["plane"] == "sim" else run_live_plane
    return run(trace, cost, cfg["policy"], **plane_kwargs(cfg))


def warm_trace(regions, warmup_samples: int):
    """A 2-region replay that ends in one solved TTL refresh: a PUT, then
    ``warmup_samples + 1`` reads from a second region a minute apart, then
    one read a day later, past the refresh period.  Replayed through a
    9-region catalog it solves the 8 incoming edges of one region, the
    device programs every refresh of these cells runs."""
    from repro.core.traces import EVENT_DTYPE, OP_GET, OP_PUT, Trace

    n = warmup_samples + 1
    ev = np.zeros(n + 2, dtype=EVENT_DTYPE)
    ev["t"] = np.concatenate([[0.0], 10.0 + 60.0 * np.arange(n),
                              [10.0 + 60.0 * n + DAY]])
    ev["op"] = [OP_PUT] + [OP_GET] * (n + 1)
    ev["size"] = 1024
    ev["region"] = [0] + [1] * (n + 1)
    return Trace("warm", ev, tuple(regions), ("warm",))


def warm(ctx, cfg: dict, cost) -> None:
    """Compile (or load from the cache) every device program of a TTL
    refresh, through the live or simulated plane the cell runs."""
    trace = warm_trace(cost.region_names(),
                       cfg["policy_params"]["warmup_min_samples"])
    run = run_plane(cfg, trace, cost)
    if getattr(run.policy.ctl, "n_refreshes", 1) < 1:
        ctx.log("WARNING: the warm-up replay solved no TTL refresh")
