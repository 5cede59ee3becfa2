"""YCSB core workload B run by many tenants, each in a bucket of its own.

Each of ``n_buckets`` tenants runs YCSB's workload B on its own keyspace:
``records_per_bucket`` records of ``record_bytes``, read or updated under a
zipfian request distribution (constant ``zipfian_constant``) over the
tenant's records.  Each bucket lives in ``regions_per_bucket`` of the
regions, drawn from the seed: the first is its base, which takes the
records' first PUTs and every update; reads are uniform over the bucket's
regions.

The load is uniform over buckets: every bucket gets exactly
``n_requests / n_buckets`` requests, and exactly ``update_frac`` of all
requests are updates.  As in ``bench/generators/zipfian.py``, the first
PUTs fill the first 20% of the run and the requests the other 80%, at
``requests_per_bucket_day``; the duration follows from them.  Every seed
gives the same number of events of each kind, per bucket, of the same
sizes; the seed draws the regions, the records and the times.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from repro.core.traces import EVENT_DTYPE, OP_GET, OP_PUT, Trace

DAY = 24 * 3600.0


def _zipf_weights(n: int, constant: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** constant
    return w / w.sum()


def make(regions: Sequence[str], seed: int, n_buckets: int = 1000,
         records_per_bucket: int = 10, record_bytes: int = 1024,
         n_requests: int = 250_000, update_frac: float = 0.05,
         zipfian_constant: float = 0.99, regions_per_bucket: int = 3,
         requests_per_bucket_day: float = 10.0) -> Trace:
    if n_requests % n_buckets:
        raise ValueError("n_requests must be a multiple of n_buckets")
    rng = np.random.default_rng(seed)
    n_r = len(regions)
    per_bucket = n_requests // n_buckets
    duration = per_bucket / requests_per_bucket_day * DAY / 0.8
    # The bucket's regions, base first.
    home = np.argsort(rng.random((n_buckets, n_r)),
                      axis=1)[:, :regions_per_bucket]
    n_obj = n_buckets * records_per_bucket
    obj_bucket = np.arange(n_obj) // records_per_bucket
    put_t = rng.random(n_obj) * 0.2 * duration

    bucket = rng.permutation(np.repeat(np.arange(n_buckets), per_bucket))
    req_t = np.sort(0.2 * duration + rng.random(n_requests) * 0.8 * duration)
    update = np.zeros(n_requests, dtype=bool)
    update[rng.permutation(n_requests)[:round(update_frac * n_requests)]] = True
    rank = rng.choice(records_per_bucket, size=n_requests,
                      p=_zipf_weights(records_per_bucket, zipfian_constant))
    record_of_rank = np.argsort(rng.random((n_buckets, records_per_bucket)),
                                axis=1)
    obj = bucket * records_per_bucket + record_of_rank[bucket, rank]
    reader = rng.integers(0, regions_per_bucket, size=n_requests)
    region = home[bucket, np.where(update, 0, reader)]

    t = np.concatenate([put_t, req_t])
    order = np.argsort(t, kind="stable")
    ev = np.zeros(t.shape[0], dtype=EVENT_DTYPE)
    ev["op"] = np.concatenate([np.full(n_obj, OP_PUT),
                               np.where(update, OP_PUT, OP_GET)])[order]
    ev["obj"] = np.concatenate([np.arange(n_obj), obj])[order]
    ev["size"] = record_bytes
    ev["region"] = np.concatenate([home[obj_bucket, 0], region])[order]
    ev["bucket"] = np.concatenate([obj_bucket, bucket])[order]
    # Strictly increasing times: an equal time moves 1 ms after the last.
    times, prev = t[order].tolist(), -1.0
    for i, x in enumerate(times):
        prev = times[i] = x if x > prev else prev + 1e-3
    ev["t"] = times
    return Trace("ycsb_b_tenants", ev, tuple(regions),
                 tuple(f"tenant-{i:04d}" for i in range(n_buckets)))
