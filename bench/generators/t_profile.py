"""T-profile traces (SkyStore paper, Table 2) spread over regions by the
§6.1.3 workload types.

A copy of the profile generator and the region assignment in
``repro.core.traces``, kept with the benchmark so that a change to the
program's generator cannot move the yardstick.  The arithmetic is unchanged;
only the split of the seed is the benchmark's own:

* ``structure_seed`` (fixed in the traffic file) draws the objects, their
  sizes, GET counts and arrival times -- the amount of work;
* ``seed`` (the run's ``--seed``) draws the region assignment.

So every seed replays the same number of events with the same sizes and
arrivals, read from and written to other regions.
"""

from __future__ import annotations

import zlib
from typing import Dict, Optional, Sequence

import numpy as np

from repro.core.traces import EVENT_DTYPE, OP_GET, OP_PUT, Trace

DAY = 24 * 3600.0
MONTH = 30 * DAY
KB, MB, GB = 1024, 1024 ** 2, 1024 ** 3

#: (size-class weights [tiny, small, medium, large], read-frequency weights
#: [one-hit, cold, warm, hot, superhot], put fraction, burstiness, gap scale
#: and spread, active window, months, objects) per profile.
PROFILES: Dict[str, Dict] = {
    "T15": dict(sizes=[0.0, 0.80, 0.20, 0.0], freq=[0.48, 0.52, 0.0, 0.0, 0.0],
                put_frac=0.43, burst_p=0.05, gap_scale=0.6 * DAY,
                gap_sigma=1.2, active=(0.0, 0.60), months=5.0,
                n_objects=1400),
    "T29": dict(sizes=[0.44, 0.56, 0.0, 0.0], freq=[0.02, 0.98, 0.0, 0.0, 0.0],
                put_frac=0.30, burst_p=0.05, gap_scale=20.0 * DAY,
                gap_sigma=1.4, active=(0.0, 1.0), months=5.0,
                n_objects=2600),
    "T65": dict(sizes=[0.31, 0.34, 0.3497, 0.0003],
                freq=[0.02, 0.09, 0.22, 0.669, 0.001], put_frac=0.01,
                burst_p=0.45, gap_scale=1.3 * DAY, gap_sigma=1.1,
                active=(0.0, 1.0), months=5.0, n_objects=260),
    "T78": dict(sizes=[0.01, 0.98, 0.01, 0.0],
                freq=[0.10, 0.30, 0.51, 0.088, 0.002], put_frac=0.10,
                burst_p=0.30, gap_scale=2.6 * DAY, gap_sigma=1.2,
                active=(0.55, 1.0), months=5.0, n_objects=700),
    "T79": dict(sizes=[0.0, 0.3965, 0.60, 0.0035],
                freq=[0.17, 0.55, 0.22, 0.06, 0.0], put_frac=0.11,
                burst_p=0.20, gap_scale=8.3 * DAY, gap_sigma=1.3,
                active=(0.0, 1.0), months=5.0, n_objects=420),
}

_SIZE_RANGES = [(128, 1 * KB), (1 * KB, 1 * MB), (1 * MB, 1 * GB),
                (1 * GB, 4 * GB)]
_FREQ_RANGES = [(1, 1), (2, 10), (10, 100), (100, 1000), (1000, 3000)]


def _sample_sizes(rng, weights, n):
    cls = rng.choice(4, size=n, p=np.asarray(weights) / np.sum(weights))
    lo = np.asarray([_SIZE_RANGES[c][0] for c in cls], dtype=np.float64)
    hi = np.asarray([_SIZE_RANGES[c][1] for c in cls], dtype=np.float64)
    u = rng.random(n)
    return np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo))).astype(np.int64)


def _sample_get_counts(rng, weights, n):
    cls = rng.choice(5, size=n, p=np.asarray(weights) / np.sum(weights))
    lo = np.asarray([_FREQ_RANGES[c][0] for c in cls], dtype=np.float64)
    hi = np.asarray([_FREQ_RANGES[c][1] for c in cls], dtype=np.float64)
    u = rng.random(n)
    return np.maximum(
        np.exp(np.log(lo) + u * (np.log(np.maximum(hi, lo + 1e-9))
                                 - np.log(lo))), 1.0).astype(np.int64)


def _object_get_times(rng, put_t, n_gets, p, horizon):
    """Lognormal gaps with occasional bursts of 2-8 GETs within 10 minutes."""
    times = []
    t = put_t
    lo, hi = p["active"]
    t0, t1 = lo * horizon, hi * horizon
    remaining = n_gets
    while remaining > 0:
        t = t + rng.lognormal(np.log(p["gap_scale"]), p["gap_sigma"])
        if t > t1:
            break
        if t < t0:
            t = t0 + rng.random() * min(p["gap_scale"], t1 - t0)
        if rng.random() < p["burst_p"] and remaining > 1:
            k = int(min(rng.integers(2, 9), remaining))
            burst = np.sort(t + rng.random(k) * 600.0)
            times.extend(burst.tolist())
            t = float(burst[-1])
            remaining -= k
        else:
            times.append(t)
            remaining -= 1
    return np.asarray(times, dtype=np.float64)


def profile_trace(name: str, seed: int, n_objects: Optional[int] = None,
                  months: Optional[float] = None, n_buckets: int = 4) -> Trace:
    """Single-region logical trace of profile ``name``."""
    p = dict(PROFILES[name])
    n_obj = n_objects or p["n_objects"]
    horizon = (months or p["months"]) * MONTH
    rng = np.random.default_rng(seed ^ (zlib.crc32(name.encode()) % (2**31)))
    sizes = _sample_sizes(rng, p["sizes"], n_obj)
    counts = _sample_get_counts(rng, p["freq"], n_obj)
    put_times = rng.random(n_obj) ** 1.5 * horizon * 0.55
    rows = []
    for oid in range(n_obj):
        rows.append((put_times[oid], OP_PUT, oid, sizes[oid]))
        for t in _object_get_times(rng, put_times[oid], int(counts[oid]), p,
                                   horizon):
            rows.append((t, OP_GET, oid, sizes[oid]))
        if p["put_frac"] > 0.25 and rng.random() < 0.5:
            t_over = put_times[oid] + rng.random() * (horizon - put_times[oid])
            rows.append((t_over, OP_PUT, oid, sizes[oid]))
    rows.sort(key=lambda r: r[0])
    ev = np.zeros(len(rows), dtype=EVENT_DTYPE)
    ev["t"] = [r[0] for r in rows]
    ev["op"] = [r[1] for r in rows]
    ev["obj"] = [r[2] for r in rows]
    ev["size"] = [r[3] for r in rows]
    ev["bucket"] = ev["obj"] % n_buckets
    return Trace(name, ev, ("local",),
                 tuple(f"bucket-{i}" for i in range(n_buckets)))


def assign_regions(trace: Trace, regions: Sequence[str], kind: str,
                   seed: int) -> Trace:
    """Workload types A-E of §6.1.3 over ``regions``."""
    rng = np.random.default_rng(seed * 7919 + 13)
    ev = trace.events.copy()
    n_r = len(regions)
    objs = ev["obj"]
    n_obj = int(objs.max()) + 1 if len(objs) else 0
    kind = kind.upper()
    if kind == "A":          # uniform
        ev["region"] = rng.integers(0, n_r, size=len(ev))
    elif kind == "B":        # dedicated PUT and GET region per object
        put_r = rng.integers(0, n_r, size=n_obj)
        get_r = (put_r + 1 + rng.integers(0, n_r - 1, size=n_obj)) % n_r
        ev["region"] = np.where(ev["op"] != OP_GET, put_r[objs], get_r[objs])
    elif kind == "C":        # PUT anywhere, GET from one central region
        central = int(rng.integers(0, n_r))
        ev["region"] = np.where(ev["op"] != OP_GET,
                                rng.integers(0, n_r, size=len(ev)), central)
    elif kind == "D":        # dedicated PUT region, GETs elsewhere
        put_r = rng.integers(0, n_r, size=n_obj)
        shift = 1 + rng.integers(0, n_r - 1, size=len(ev))
        ev["region"] = np.where(ev["op"] != OP_GET, put_r[objs],
                                (put_r[objs] + shift) % n_r)
    elif kind == "E":        # per-object blend of A-D
        per_obj_kind = rng.integers(0, 4, size=n_obj)
        sub = [assign_regions(trace, regions, letter, seed + k).events["region"]
               for k, letter in enumerate("ABCD")]
        ev["region"] = np.select([per_obj_kind[objs] == k for k in range(4)],
                                 sub)
    else:
        raise KeyError(f"unknown region mix {kind!r}")
    return Trace(f"{trace.name}/{kind}", ev, tuple(regions), trace.buckets)


def make(regions: Sequence[str], seed: int, profile: str, structure_seed: int,
         region_mix: str, n_buckets: int, months: Optional[float] = None,
         n_objects: Optional[int] = None) -> Trace:
    """The traffic of one run: a fixed profile trace, regions from ``seed``."""
    base = profile_trace(profile, structure_seed, n_objects=n_objects,
                         months=months, n_buckets=n_buckets)
    return assign_regions(base, regions, region_mix, seed)
