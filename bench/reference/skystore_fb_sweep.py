"""Plain reference of the FB SkyStore deployment whose TTLs are re-solved
at the daily tick (arXiv 2502.20818 §6.7.3: the metadata server recomputes
every (bucket, edge) TTL periodically).

It is :mod:`bench.reference.skystore_fb` with one change of schedule, and
imports nothing of the program under test:

* a read never refreshes a TTL: a replica's TTL comes from the edge TTLs
  the last sweep left (T_even on an edge never solved);
* at every daily tick, after the paused-bytes census, every (bucket, region)
  pair with at least ``warmup_min_samples`` gap samples in its merged
  collection windows has every incoming edge re-solved as the argmin of
  ExpectedCost over the cell edges, and its window rotates as a refresh's
  would.  Pairs under warm-up are left as they are.

:func:`replay` returns what :func:`skystore_fb.replay` returns, with
``sweeps`` (ticks that solved at least one pair) and ``sweep_pairs`` (pairs
solved) in place of ``refreshes``.  ``skip_pair`` leaves the first warm pair
of every sweep unsolved: like a ``precision`` below the configuration's, a
fault that must fail the comparison.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from bench.reference.skystore_fb import (
    DELETE, GB, GET, HEAD, LIST, PUT, SECONDS_PER_MONTH, Controller,
    Reference, Window, cost_surface,
)


class SweepController(Controller):
    """Per-(bucket, region) statistics, with TTLs solved only by
    :meth:`sweep`."""

    def __init__(self, cat, cfg: dict, precision: str,
                 skip_pair: bool = False) -> None:
        super().__init__(cat, cfg, precision)
        self.skip_pair = skip_pair
        self.n_sweeps = 0
        self.n_sweep_pairs = 0

    def maybe_refresh(self, bucket: str, dst: str, now: float) -> None:
        """Reads never refresh on this schedule."""

    def sweep(self, now: float) -> None:
        warm = [(key, st) for key, st in self.stats.items()
                if st.cur.n + (st.prev.n if st.prev is not None else 0)
                >= self.warmup]
        if self.skip_pair:
            warm = warm[1:]
        for (bucket, dst), st in warm:
            cur, prev = st.cur, st.prev
            if prev is None:
                hist, time_w, first = cur.hist, cur.time_w, cur.first_remote
            else:
                hist, time_w = cur.hist + prev.hist, cur.time_w + prev.time_w
                first = cur.first_remote + prev.first_remote
            srcs = [r for r in self.cat.names if r != dst]
            s = np.full(len(srcs),
                        self.cat.storage[dst] / GB / SECONDS_PER_MONTH)
            n_price = np.asarray([self.cat.egress[(r, dst)] / GB
                                  for r in srcs])
            cost = cost_surface(self.edges, hist, time_w, cur.last, first, s,
                                n_price, self.dtype)
            ttls = np.concatenate([[0.0], self.edges])
            for src, k in zip(srcs, np.argmin(cost, axis=1)):
                self.ttl[(bucket, src, dst)] = float(ttls[k])
            self.last_refresh[(bucket, dst)] = now
            t_even_max = max(self.cat.t_even_seconds(r, dst) for r in srcs)
            if now - st.window_start > self.rotate_multiple * t_even_max:
                st.prev, st.cur = st.cur, Window(self.edges.shape[0])
                st.window_start = now
        if warm:
            self.n_sweeps += 1
            self.n_sweep_pairs += len(warm)


class SweepReference(Reference):
    """One replay of one trace through the deployment on the tick
    schedule."""

    def __init__(self, cfg: dict, horizon: float, precision: str = "float64",
                 skip_pair: bool = False):
        super().__init__(cfg, horizon, precision)
        self.ctl = SweepController(self.cat, cfg, precision, skip_pair)

    def tick(self, now: float) -> None:
        """The daily census of paused bytes, then the sweep."""
        super().tick(now)
        self.ctl.sweep(now)


def replay(cfg: dict, events: np.ndarray, regions: Sequence[str],
           buckets: Sequence[str], precision: str = "float64",
           skip_pair: bool = False) -> dict:
    """Replay the event table (columns t, op, obj, size, region, bucket)
    through the reference; returns decisions, holders, counters, bill,
    the sweep counts and every edge's TTL as the last sweep left it."""
    t_col, op_col = events["t"].tolist(), events["op"].tolist()
    obj_col, size_col = events["obj"].tolist(), events["size"].tolist()
    reg_col, bkt_col = events["region"].tolist(), events["bucket"].tolist()
    horizon = t_col[-1] if t_col else 0.0
    ref = SweepReference(cfg, horizon, precision, skip_pair)
    day = cfg["scan_interval_s"]
    next_tick = day
    for i in range(len(t_col)):
        now = t_col[i]
        while next_tick <= now:
            ref._expire_due(next_tick)
            ref.tick(next_tick)
            next_tick += day
        ref._expire_due(now)
        op, region = op_col[i], regions[reg_col[i]]
        bucket = buckets[bkt_col[i]]
        if op == GET:
            ref.get(now, obj_col[i], bucket, region)
        elif op == PUT:
            ref.put(now, obj_col[i], bucket, region, float(size_col[i]))
        elif op == HEAD:
            ref.head(obj_col[i], region)
        elif op == LIST:
            ref.list(region)
        elif op == DELETE:
            ref.delete(now, obj_col[i], region)
    ref.finish()
    bill = dict(ref.bill)
    bill["total"] = (bill["storage"] + bill["storage_base"] + bill["network"]
                     + bill["ops"])
    holders = {oid: tuple(sorted(o.replicas))
               for oid, o in ref.objects.items() if o.replicas}
    return {"decisions": ref.decisions, "holders": holders,
            "counters": ref.counters, "bill": bill,
            "sweeps": ref.ctl.n_sweeps, "sweep_pairs": ref.ctl.n_sweep_pairs,
            "edge_ttls": dict(ref.ctl.ttl)}
