"""Plain reference of a SkyStore deployment in FB mode (arXiv 2502.20818 §3).

Written from the paper and the configuration file, with nothing taken from
the program under test: it reads a trace's event columns and a configuration
dict, and replays them with plain Python and numpy in float64.

Semantics (what the program's simulator and live store both claim):

* write-local PUTs; the first PUT of a key fixes its base region, whose
  replica is pinned; a PUT from another region is copied to the base at
  once, and the writer's copy becomes a cache replica with a policy TTL;
  an overwrite drops every replica of the old version (last writer wins);
* a GET is a hit if its region holds a replica, else it is served by the
  holder with the cheapest egress into it (ties by region name), pays that
  egress, and stores a local copy with the policy TTL; a hit resets the
  replica's TTL;
* replicas expire at ``last access + TTL``; every expiry due at or before a
  request is applied before it, in (expire, object, region) order, and the
  storage of a replica is paid from its creation to its drop;
* the TTL of a replica is the least per-edge TTL over the holders whose own
  copy outlives it (§3.3.1); each (bucket, region) keeps an 800-cell
  histogram of re-read gaps and of paused bytes (§3.2.3), and once a day
  per (bucket, region), once 32 samples are in, every incoming edge's TTL
  becomes the argmin of ExpectedCost over the cell edges (§3.2.2);
* once a day the paused-bytes census of every (bucket, region) is rebuilt
  from the last GET of each object there.

:func:`replay` returns what a run is compared on: the per-GET decisions,
the holders at the horizon, the counters, the bill, and every edge's TTL
as the last refresh left it.  ``precision``
selects the dtype the ExpectedCost surface is computed in; ``"bfloat16"``
is the benchmark's control, the next precision below the kernel's float32.
"""

from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

INF = float("inf")
GB = 1024.0 ** 3
SECONDS_PER_MONTH = 30.0 * 24 * 3600.0
#: Trace op codes of the event table (PUT, GET, DELETE, HEAD, LIST).
PUT, GET, DELETE, HEAD, LIST = 0, 1, 2, 3, 4


class Catalog:
    """Prices of the configuration: $/GB/month, $/GB egress, $/request."""

    def __init__(self, cfg: dict) -> None:
        self.names = [r["name"] for r in cfg["regions"]]
        self.storage = {r["name"]: r["storage_gb_month"] for r in cfg["regions"]}
        self.put = {r["name"]: r["put_request"] for r in cfg["regions"]}
        self.get = {r["name"]: r["get_request"] for r in cfg["regions"]}
        self.egress = {(a, b): (0.0 if a == b else cfg["egress_gb"][a][b])
                       for a in self.names for b in self.names}

    def storage_cost(self, region: str, size: float, seconds: float) -> float:
        return (self.storage[region] * (size / GB)
                * (max(seconds, 0.0) / SECONDS_PER_MONTH))

    def transfer_cost(self, src: str, dst: str, size: float) -> float:
        return self.egress[(src, dst)] * (size / GB)

    def t_even_seconds(self, src: str, dst: str) -> float:
        s = self.storage[dst]
        return (self.egress[(src, dst)] / s if s > 0 else INF) * SECONDS_PER_MONTH


def cell_edges(hcfg: dict) -> np.ndarray:
    """Upper cell boundaries: one-second cells, then log cells of ratio
    ``log_base`` from ``log_start_s``."""
    lin = np.arange(1, hcfg["linear_cells"] + 1, dtype=np.float64)
    log = hcfg["log_start_s"] * hcfg["log_base"] ** np.arange(
        1, hcfg["log_cells"] + 1, dtype=np.float64)
    return np.concatenate([lin, log])


class Window:
    """One collection window of a (bucket, region) pair."""

    def __init__(self, n_cells: int) -> None:
        self.hist = np.zeros(n_cells)       # re-read bytes per gap cell
        self.time_w = np.zeros(n_cells)     # gap * bytes per cell
        self.last = np.zeros(n_cells)       # paused bytes by pause age
        self.first_remote = 0.0             # bytes whose first GET was remote
        self.n = 0                          # gap samples


class Stats:
    def __init__(self, n_cells: int) -> None:
        self.cur = Window(n_cells)
        self.prev: Optional[Window] = None
        self.window_start = 0.0


def _round(x, dtype):
    return np.asarray(x, dtype=np.float64).astype(dtype)


def cost_surface(edges, hist, time_w, last, first, s, n, dtype=np.float64):
    """ExpectedCost of every candidate TTL ``[0, edges...]`` for each row
    of prices ``s`` ($/byte-second) and ``n`` ($/byte), one histogram
    shared by all rows.  Every input and every step is held in ``dtype``."""
    e = _round(edges, dtype)
    hist, time_w, last = (_round(x, dtype) for x in (hist, time_w, last))
    s = _round(s, dtype)[:, None]
    n = _round(n, dtype)[:, None]
    first = _round(first, dtype)
    zero = np.zeros(1, dtype)
    lower = np.concatenate([zero, e[:-1]])
    mid = (lower + e) * _round(0.5, dtype)
    with np.errstate(invalid="ignore", divide="ignore"):
        t_hat = np.where(hist > 0, time_w / np.maximum(hist, _round(1e-30, dtype)),
                         mid).astype(dtype)
    hit = np.concatenate([zero, np.cumsum(hist * t_hat, dtype=dtype)])
    hist_c = np.concatenate([zero, np.cumsum(hist, dtype=dtype)])
    last_c = np.concatenate([zero, np.cumsum(last, dtype=dtype)])
    age = np.concatenate([zero, np.cumsum(last * mid, dtype=dtype)])
    ttls = np.concatenate([zero, e])[None, :]
    miss = (hist_c[-1] - hist_c)[None, :]
    tail = (last_c[-1] - last_c)[None, :]
    cost = (first * n + hit[None, :] * s + miss * (n + ttls * s)
            + tail * ttls * s)
    return (cost + age[None, :] * s).astype(dtype)


class Controller:
    """Per-(bucket, region) statistics and per-edge TTLs."""

    def __init__(self, cat: Catalog, cfg: dict, precision: str) -> None:
        ttl = cfg["ttl"]
        self.cat = cat
        self.edges = cell_edges(ttl["histogram"])
        self.period = ttl["refresh_period_s"]
        self.warmup = ttl["warmup_min_samples"]
        self.rotate_multiple = ttl["rotate_multiple_of_t_even"]
        self.dtype = _dtype(precision)
        self.stats: Dict[Tuple[str, str], Stats] = {}
        self.ttl: Dict[Tuple[str, str, str], float] = {}
        self.last_refresh: Dict[Tuple[str, str], float] = {}
        self.n_refreshes = 0

    def stats_for(self, bucket: str, region: str) -> Stats:
        st = self.stats.get((bucket, region))
        if st is None:
            st = self.stats[(bucket, region)] = Stats(self.edges.shape[0])
        return st

    def cell(self, t: float) -> int:
        return min(int(np.searchsorted(self.edges, t, side="left")),
                   self.edges.shape[0] - 1)

    def record_gap(self, bucket, region, gap, size) -> None:
        w = self.stats_for(bucket, region).cur
        c = self.cell(gap)
        w.hist[c] += size
        w.time_w[c] += size * gap
        w.n += 1

    def record_first_read(self, bucket, region, size, remote) -> None:
        w = self.stats_for(bucket, region).cur
        if remote:
            w.first_remote += size

    def census(self, bucket, region, ages, sizes) -> None:
        w = self.stats_for(bucket, region).cur
        w.last[:] = 0.0
        for a, z in zip(ages, sizes):
            w.last[self.cell(a)] += z

    def maybe_refresh(self, bucket: str, dst: str, now: float) -> None:
        key = (bucket, dst)
        if now - self.last_refresh.get(key, -INF) < self.period:
            return
        self.last_refresh[key] = now
        st = self.stats_for(bucket, dst)
        cur, prev = st.cur, st.prev
        if prev is None:
            hist, time_w, first, n = cur.hist, cur.time_w, cur.first_remote, cur.n
        else:
            hist, time_w = cur.hist + prev.hist, cur.time_w + prev.time_w
            first, n = cur.first_remote + prev.first_remote, cur.n + prev.n
        if n < self.warmup:
            return
        srcs = [r for r in self.cat.names if r != dst]
        s = np.full(len(srcs), self.cat.storage[dst] / GB / SECONDS_PER_MONTH)
        n_price = np.asarray([self.cat.egress[(r, dst)] / GB for r in srcs])
        cost = cost_surface(self.edges, hist, time_w, cur.last, first, s,
                            n_price, self.dtype)
        ttls = np.concatenate([[0.0], self.edges])
        for src, k in zip(srcs, np.argmin(cost, axis=1)):
            self.ttl[(bucket, src, dst)] = float(ttls[k])
        self.n_refreshes += 1
        t_even_max = max(self.cat.t_even_seconds(r, dst) for r in srcs)
        if now - st.window_start > self.rotate_multiple * t_even_max:
            st.prev, st.cur = st.cur, Window(self.edges.shape[0])
            st.window_start = now

    def object_ttl(self, bucket: str, region: str, now: float,
                   holders: Dict[str, float]) -> float:
        """The replica TTL for a copy at ``region`` (§3.3.1): the least edge
        TTL over holders whose copy outlives it, else over pinned holders,
        else over all holders."""
        self.maybe_refresh(bucket, region, now)
        edge = {}
        for src in holders:
            if src != region:
                edge[src] = self.ttl.get((bucket, src, region),
                                         self.cat.t_even_seconds(src, region))
        if not edge:
            return INF
        safe = [t for s, t in edge.items() if holders[s] >= now + t]
        pinned = [t for s, t in edge.items() if holders[s] == INF]
        return float(min(safe or pinned or list(edge.values())))


def _dtype(precision: str):
    if precision == "float64":
        return np.float64
    if precision == "float32":
        return np.float32
    if precision == "bfloat16":
        import ml_dtypes
        return ml_dtypes.bfloat16
    raise ValueError(f"unknown precision {precision!r}")


class Replica:
    __slots__ = ("start", "ttl", "expire", "pinned")

    def __init__(self, start, ttl, pinned):
        self.start, self.ttl, self.pinned = start, ttl, pinned
        self.expire = INF if pinned else start + ttl


class Obj:
    __slots__ = ("size", "bucket", "base", "replicas")

    def __init__(self, size, bucket):
        self.size, self.bucket, self.base = size, bucket, None
        self.replicas: Dict[str, Replica] = {}


class Reference:
    """One replay of one trace through the reference deployment."""

    def __init__(self, cfg: dict, horizon: float, precision: str = "float64"):
        if cfg["mode"] != "FB" or cfg["policy"] != "skystore":
            raise ValueError("the reference models the FB skystore deployment")
        self.cat = Catalog(cfg)
        self.ctl = Controller(self.cat, cfg, precision)
        self.horizon = horizon
        self.objects: Dict[int, Obj] = {}
        self.heap: List[Tuple[float, int, str, int]] = []
        self.seq = 0
        self.last_get: Dict[Tuple[int, str], float] = {}
        self.open_last: Dict[Tuple[str, str], Dict[int, Tuple[float, float]]] = {}
        self.decisions: List[Tuple] = []
        self.bill = {"storage": 0.0, "storage_base": 0.0, "network": 0.0,
                     "ops": 0.0}
        self.counters = dict.fromkeys(
            ("n_get", "n_put", "n_head", "n_list", "n_hit", "n_miss",
             "n_evictions", "n_replications"), 0)

    # -- replicas -------------------------------------------------------------
    def _charge(self, obj: Obj, region: str, rep: Replica, end: float) -> None:
        end = min(end, self.horizon) if self.horizon else end
        c = self.cat.storage_cost(region, obj.size, end - rep.start)
        self.bill["storage_base" if rep.pinned else "storage"] += c

    def _set(self, oid: int, obj: Obj, region: str, now: float, ttl: float,
             pinned: bool = False) -> None:
        rep = obj.replicas.get(region)
        if rep is None:
            rep = obj.replicas[region] = Replica(now, ttl, pinned)
        else:
            rep.ttl = ttl
            rep.pinned = rep.pinned or pinned
            rep.expire = INF if rep.pinned else now + ttl
        if rep.expire != INF:
            self.seq += 1
            heapq.heappush(self.heap, (rep.expire, oid, region, self.seq))

    def _drop(self, oid: int, obj: Obj, region: str, now: float,
              evicted: bool = False) -> None:
        rep = obj.replicas.pop(region)
        self._charge(obj, region, rep, now)
        if evicted:
            self.counters["n_evictions"] += 1

    def _expire_due(self, now: float) -> None:
        heap = self.heap
        while heap and heap[0][0] <= now:
            expire, oid, region, _seq = heapq.heappop(heap)
            obj = self.objects.get(oid)
            rep = obj.replicas.get(region) if obj is not None else None
            if rep is None or rep.pinned or rep.expire != expire:
                continue        # superseded schedule
            self._drop(oid, obj, region, expire, evicted=True)

    @staticmethod
    def _holders(obj: Obj) -> Dict[str, float]:
        return {r: rep.expire for r, rep in obj.replicas.items()}

    # -- requests -------------------------------------------------------------
    def put(self, now, oid, bucket, region, size) -> None:
        self.counters["n_put"] += 1
        self.bill["ops"] += self.cat.put[region]
        obj = self.objects.get(oid)
        if obj is None:
            obj = self.objects[oid] = Obj(size, bucket)
        else:
            for r in list(obj.replicas):
                self._drop(oid, obj, r, now)
        obj.size = size
        if obj.base is None:
            obj.base = region
        base = obj.base
        self._set(oid, obj, region, now, INF, pinned=(region == base))
        if region != base:
            self.bill["network"] += self.cat.transfer_cost(region, base, size)
            self.bill["ops"] += self.cat.put[base]
            self.counters["n_replications"] += 1
            self._set(oid, obj, base, now, INF, pinned=True)
            ttl = self.ctl.object_ttl(bucket, region, now, self._holders(obj))
            if ttl <= 0:
                self._drop(oid, obj, region, now)
            else:
                self._set(oid, obj, region, now, ttl)

    def get(self, now, oid, bucket, region) -> None:
        obj = self.objects.get(oid)
        if obj is None or not obj.replicas:
            return
        size = obj.size
        holders = self._holders(obj)
        alive = {r: e for r, e in holders.items() if e > now} or holders
        hit = region in alive
        src = region if hit else min(
            alive, key=lambda h: (self.cat.egress[(h, region)], h))
        self.counters["n_get"] += 1
        self.bill["ops"] += self.cat.get[region]
        prev = self.last_get.get((oid, region))
        if prev is not None:
            self.ctl.record_gap(bucket, region, now - prev, size)
        else:
            self.ctl.record_first_read(bucket, region, size, remote=not hit)
        self.counters["n_hit" if hit else "n_miss"] += 1
        action = "skip"
        if not hit:
            self.bill["network"] += self.cat.transfer_cost(src, region, size)
            self.counters["n_replications"] += 1
            ttl = self.ctl.object_ttl(bucket, region, now, holders)
            if ttl > 0:
                self._set(oid, obj, region, now, ttl)
                action = "store"
        elif not obj.replicas[region].pinned:
            ttl = self.ctl.object_ttl(bucket, region, now, holders)
            if ttl <= 0:
                self._drop(oid, obj, region, now, evicted=True)
                action = "evict"
            else:
                self._set(oid, obj, region, now, ttl)
                action = "keep"
        else:
            action = "keep"
        self.decisions.append((now, oid, region, src, hit, action))
        self.last_get[(oid, region)] = now
        self.open_last.setdefault((bucket, region), {})[oid] = (now, size)

    def head(self, oid, region) -> None:
        if oid in self.objects:
            self.counters["n_head"] += 1
            self.bill["ops"] += self.cat.get[region]

    def list(self, region) -> None:
        self.counters["n_list"] += 1
        self.bill["ops"] += self.cat.put[region]

    def delete(self, now, oid, region) -> None:
        obj = self.objects.pop(oid, None)
        if obj is None:
            return
        self.bill["ops"] += self.cat.put[region]
        for r in list(obj.replicas):
            self._drop(oid, obj, r, now)

    def tick(self, now: float) -> None:
        """The daily census of paused bytes per (bucket, region)."""
        for (bucket, region), entries in self.open_last.items():
            if entries:
                vals = list(entries.values())
                self.ctl.census(bucket, region, [now - t for t, _ in vals],
                                [z for _, z in vals])

    def finish(self) -> None:
        self._expire_due(self.horizon)
        for obj in self.objects.values():
            for region, rep in obj.replicas.items():
                self._charge(obj, region, rep, min(rep.expire, self.horizon))


def replay(cfg: dict, events: np.ndarray, regions: Sequence[str],
           buckets: Sequence[str], precision: str = "float64") -> dict:
    """Replay the event table (columns t, op, obj, size, region, bucket)
    through the reference; returns decisions, holders, counters and bill."""
    t_col, op_col = events["t"].tolist(), events["op"].tolist()
    obj_col, size_col = events["obj"].tolist(), events["size"].tolist()
    reg_col, bkt_col = events["region"].tolist(), events["bucket"].tolist()
    horizon = t_col[-1] if t_col else 0.0
    ref = Reference(cfg, horizon, precision)
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
            "refreshes": ref.ctl.n_refreshes, "edge_ttls": dict(ref.ctl.ttl)}
