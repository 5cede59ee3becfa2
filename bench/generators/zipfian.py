"""Zipf-skewed key-value traffic with per-object reader affinity.

A copy of ``repro.core.workloads.zipfian`` (and the helpers it uses), kept
with the benchmark so that a change to the program's generator cannot move
the yardstick.  With YCSB parameters (zipfian constant 0.99, 1 KB records,
a read/update mix, no HEADs or deletes) it gives the key-value cells their
traffic.  Every seed gives the same number of objects and requests, of the
same sizes; the seed draws which keys, regions and times.

Two parameters are the benchmark's own; left out, the trace is the
program's.  ``requests_per_bucket_day`` sets the virtual clock: the requests
fill 80% of ``duration`` (after the records' first PUTs), so the duration
follows from the number of requests, the buckets and this rate.
``phase_stagger_days`` starts the requests of bucket ``b`` from region ``r``
``(b * n_regions + r) / (n_buckets * n_regions)`` of that many days after
those of (bucket 0, region 0).  Each (bucket, region) pair's daily TTL
refresh is phased by its first read there, so in a long-running deployment
the refreshes fall at their own hours; with the stagger they come one at a
time, evenly over the day, instead of all together at its start.
"""

from __future__ import annotations

import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.traces import (
    EVENT_DTYPE, OP_DELETE, OP_GET, OP_HEAD, OP_LIST, OP_PUT, Trace,
)

DAY = 24 * 3600.0
KB = 1024


def _rng(name: str, seed: int) -> np.random.Generator:
    return np.random.default_rng(seed ^ (zlib.crc32(name.encode()) % (2**31)))


def _sizes(rng, n, size_range):
    lo, hi = size_range
    u = rng.random(n)
    return np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo))).astype(np.int64)


def _zipf_weights(n: int, alpha: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** alpha
    return w / w.sum()


def _finalize(name: str, rows: List[Tuple], regions: Sequence[str],
              n_buckets: int) -> Trace:
    """Sort, make timestamps strictly increasing, pack into a Trace.  Rows
    are (t, op, obj, size, region); LIST rows carry the bucket in ``obj``."""
    rows.sort(key=lambda r: r[0])
    ev = np.zeros(len(rows), dtype=EVENT_DTYPE)
    t_prev = -1.0
    for i, (t, op, obj, size, region) in enumerate(rows):
        t = t if t > t_prev else t_prev + 1e-3
        t_prev = t
        bucket = obj % n_buckets if op != OP_LIST else obj
        ev[i] = (t, op, obj if op != OP_LIST else 0, size, region, bucket)
    return Trace(name, ev, tuple(regions),
                 tuple(f"bucket-{i}" for i in range(n_buckets)))


def _append_deletes(rng, rows, delete_frac, n_objects):
    """Terminal deletes, each strictly after its object's last access."""
    if delete_frac <= 0 or not rows:
        return
    last: Dict[int, Tuple[float, int]] = {}
    for (t, op, obj, _s, region) in rows:
        if op != OP_LIST and (obj not in last or t >= last[obj][0]):
            last[obj] = (t, region)
    victims = rng.choice(n_objects, size=max(1, int(delete_frac * n_objects)),
                         replace=False)
    for obj in victims:
        if int(obj) in last:
            t, region = last[int(obj)]
            rows.append((t + 60.0 + rng.random() * 3600.0, OP_DELETE,
                         int(obj), 0, region))


def make(regions: Sequence[str], seed: int, n_objects: int = 150,
         n_requests: int = 2000, alpha: float = 1.1, put_frac: float = 0.06,
         head_frac: float = 0.05, delete_frac: float = 0.05,
         affinity: float = 0.7, duration: float = 10 * DAY,
         size_range: Tuple[int, int] = (4 * KB, 64 * KB),
         n_buckets: int = 2,
         requests_per_bucket_day: Optional[float] = None,
         phase_stagger_days: float = 0.0) -> Trace:
    """Zipf-skewed popularity, per-object home (writer) and reader regions;
    ``duration`` in seconds.  PUTs land at the object's home region."""
    if requests_per_bucket_day is not None:
        duration = n_requests / (requests_per_bucket_day * n_buckets) * DAY / 0.8
    rng = _rng("zipfian", seed)
    n_r = len(regions)
    sizes = _sizes(rng, n_objects, tuple(size_range))
    home = rng.integers(0, n_r, size=n_objects)
    reader = (home + 1 + rng.integers(0, max(n_r - 1, 1), size=n_objects)) % n_r
    pop = _zipf_weights(n_objects, alpha)
    rank = rng.permutation(n_objects)
    rows: List[Tuple] = []
    put_t = rng.random(n_objects) * 0.2 * duration
    for o in range(n_objects):
        rows.append((put_t[o], OP_PUT, o, int(sizes[o]), int(home[o])))
    req_t = np.sort(0.2 * duration + rng.random(n_requests) * 0.8 * duration)
    objs = rank[rng.choice(n_objects, size=n_requests, p=pop)]
    u = rng.random(n_requests)
    pairs = np.arange(n_buckets * n_r).reshape(n_buckets, n_r)
    phase = pairs / (n_buckets * n_r) * phase_stagger_days * DAY
    for i in range(n_requests):
        o = int(objs[i])
        if u[i] < put_frac:
            op, r = OP_PUT, int(home[o])
        else:
            r = (int(reader[o]) if rng.random() < affinity
                 else int(rng.integers(0, n_r)))
            op = OP_HEAD if u[i] < put_frac + head_frac else OP_GET
        rows.append((req_t[i] + phase[o % n_buckets, r], op, o,
                     int(sizes[o]), r))
    for d in range(1, int(duration / DAY)):
        rows.append((d * DAY + 17.0, OP_LIST, int(rng.integers(0, n_buckets)),
                     0, int(rng.integers(0, n_r))))
    _append_deletes(rng, rows, delete_frac, n_objects)
    return _finalize("ycsb/zipfian", rows, regions, n_buckets)
