"""The benchmark's copies of the trace generators: deterministic in the seed
(pinned by a digest of one small trace each), the same work for every seed,
and -- today -- the same traces as the program's own generators."""

import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from repro.core.traces import OP_LIST  # noqa: E402

REGIONS = tuple(f"r{i}" for i in range(9))
T_PROFILE = dict(profile="T65", structure_seed=65, region_mix="E",
                 n_buckets=1, n_objects=40, months=2.0)
ZIPFIAN = dict(n_objects=300, n_requests=3000, alpha=0.99, put_frac=0.05,
               head_frac=0.0, delete_frac=0.0, affinity=0.7,
               duration=172800.0, size_range=[1024, 1024], n_buckets=16)


def make(name, seed, **params):
    return harness.generator({"generator": name})(REGIONS, seed, **params)


def digest(trace) -> str:
    return hashlib.sha256(trace.events.tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("name,params,want", [
    ("t_profile", T_PROFILE, "ad92983fd501c18f"),
    ("zipfian", ZIPFIAN, "a02e4cc2ef65e05d"),
])
def test_digest_pins_the_trace(name, params, want):
    a, b = make(name, 7, **params), make(name, 7, **params)
    assert digest(a) == digest(b) == want


@pytest.mark.parametrize("name,params", [("t_profile", T_PROFILE),
                                         ("zipfian", ZIPFIAN)])
def test_every_seed_gets_the_same_work(name, params):
    a, b = make(name, 1, **params), make(name, 2**31 + 11, **params)
    assert len(a.events) == len(b.events)
    assert digest(a) != digest(b)
    if name == "t_profile":
        # Only the regions move: times, objects and sizes are fixed.
        for col in ("t", "op", "obj", "size"):
            assert (a.events[col] == b.events[col]).all()
    else:
        assert sorted(a.events["size"]) == sorted(b.events["size"])


def test_t_profile_matches_the_programs_generator():
    from repro.core.traces import assign_workload, generate_trace

    seed = 5
    mine = make("t_profile", seed, **dict(T_PROFILE, structure_seed=seed))
    base = generate_trace("T65", seed=seed, n_objects=40, months=2.0,
                          n_buckets=1)
    theirs = assign_workload(base, REGIONS, "E", seed=seed)
    assert np.array_equal(mine.events, theirs.events)


def test_zipfian_matches_the_programs_generator():
    from repro.core.workloads import zipfian

    mine = make("zipfian", 3, **ZIPFIAN)
    p = dict(ZIPFIAN, size_range=tuple(ZIPFIAN["size_range"]))
    theirs = zipfian(REGIONS, seed=3, **p)
    assert np.array_equal(mine.events, theirs.events)
    assert mine.buckets == theirs.buckets


def test_zipfian_clock_and_stagger():
    """``requests_per_bucket_day`` sets the duration; ``phase_stagger_days``
    moves the requests of each (bucket, region) pair later by the pair's
    share of the stagger and changes nothing else."""
    day = 86400.0
    base = dict(ZIPFIAN)
    del base["duration"]
    rate = 3000 / (16 * 1.6)            # the pinned trace's 1.6 request days
    a = make("zipfian", 7, **dict(base, requests_per_bucket_day=rate))
    pinned = make("zipfian", 7, **ZIPFIAN)
    for col in ("op", "obj", "size", "region", "bucket"):
        assert (a.events[col] == pinned.events[col]).all()
    assert np.allclose(a.events["t"], pinned.events["t"], rtol=1e-12, atol=0)
    b = make("zipfian", 7, **dict(base, requests_per_bucket_day=rate,
                                  phase_stagger_days=1.0))
    assert len(b.events) == len(a.events)
    load = 300                          # the records' first PUTs come first
    ra, rb = a.events[load:], b.events[load:]
    rows = lambda ev: sorted(x for x in zip(
        ev["obj"].tolist(), ev["region"].tolist(), ev["op"].tolist(),
        ev["t"].tolist()) if x[2] != OP_LIST)
    shift = lambda o, r: ((o % 16) * 9 + r) / (16 * 9) * day
    want = sorted((o, r, op, t + shift(o, r)) for o, r, op, t in rows(ra))
    got = rows(rb)
    assert len(got) == len(want)
    # Equal times are nudged 1 ms apart when the trace is packed.
    assert all(x[:3] == y[:3] and abs(x[3] - y[3]) < 0.01
               for x, y in zip(want, got))
    start = ra["t"].min()               # requests fill the last 80%
    for bk, r in ((0, 0), (3, 4), (15, 8)):
        pair = rb["t"][(rb["bucket"] == bk) & (rb["region"] == r)
                       & (rb["op"] != OP_LIST)]
        assert pair.min() >= start + shift(bk, r) - 0.01
