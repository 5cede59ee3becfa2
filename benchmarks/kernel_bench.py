"""Kernel micro-benchmarks: us/call for the policy-plane hot spot (the
argmin-over-TTLs scan) at production scale, Pallas kernel (compiled on a
TPU, interpreted elsewhere) vs the jnp oracle, plus the CPU-side simulator
throughput."""

from __future__ import annotations

import time

import numpy as np

from repro.core import assign_two_region, generate_trace, paper_2region_catalog, run_policy
from repro.core.histogram import cell_edges
from repro.kernels import ttl_scan


def ttl_problem(e_dim: int, seed=0):
    """A seeded ``e_dim`` x 800-cell TTL problem: the arguments of
    :func:`repro.kernels.ttl_scan` (float32 histograms, float64 cells)."""
    rng = np.random.default_rng(seed)
    c = 800
    edges = cell_edges()
    hist = (rng.gamma(0.3, 1e9, (e_dim, c)) * (rng.random((e_dim, c)) < 0.1)
            ).astype(np.float32)
    time_w = hist * (edges[None] * rng.random((e_dim, c))).astype(np.float32)
    last = (rng.gamma(0.3, 1e9, (e_dim, c)) * (rng.random((e_dim, c)) < 0.05)
            ).astype(np.float32)
    s = rng.uniform(5e-18, 5e-17, e_dim).astype(np.float32)
    n = rng.uniform(1e-11, 1e-10, e_dim).astype(np.float32)
    first = rng.gamma(1.0, 1e9, e_dim).astype(np.float32)
    return hist, time_w, last, edges, s, n, first


def ttl_scan_bench(e_dim: int = 1024, iters: int = 3):
    """The §6.7.3 scale: ~1000 bucket-edges refreshed per cycle.

    ``compiled`` reports whether the Pallas leg ran as a real compiled TPU
    kernel or under the Mosaic interpreter (CPU CI); when it did not
    compile, ``skip_reason`` says why, so the BENCH artifact can never pass
    an interpret-mode timing off as a hardware measurement."""
    import jax

    prob = ttl_problem(e_dim)
    backend = jax.default_backend()
    compiled = backend == "tpu"
    out = {}
    for use_kernel, label in ((False, "jnp_oracle"), (True, "pallas")):
        kw = dict(use_kernel=use_kernel, interpret=not compiled)
        ttl_scan(*prob, **kw)      # warm/compile
        t0 = time.perf_counter()
        for _ in range(iters):
            r = ttl_scan(*prob, **kw)
            r[0].block_until_ready()
        out[label] = (time.perf_counter() - t0) / iters * 1e6
    out["edges_per_refresh"] = e_dim
    out["compiled"] = compiled
    out["skip_reason"] = (
        "" if compiled else
        f"no TPU attached (jax.default_backend()={backend!r}); Pallas leg "
        f"timed in interpret mode, not a hardware kernel measurement")
    return out


def simulator_bench():
    """Events/second of the cost simulator (the paper's evaluation engine)."""
    cat = paper_2region_catalog()
    tr = assign_two_region(generate_trace("T65", seed=0, n_objects=120),
                           "aws:us-east-1", "aws:us-west-1")
    t0 = time.perf_counter()
    run_policy(tr, cat, "skystore", mode="FB")
    dt = time.perf_counter() - t0
    return {"events": len(tr.events), "events_per_s": len(tr.events) / dt,
            "us_per_event": dt / len(tr.events) * 1e6}
