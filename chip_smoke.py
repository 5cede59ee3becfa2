"""Drive SkyStore's main path once on one TPU chip and check what comes out.

    python chip_smoke.py

One process runs every phase, in order; the first failure exits non-zero.

  device  jax.devices() must be a TPU.  Anything else exits at once, naming
          what was found: nothing here runs on a CPU in its place.
  cache   JAX's persistent compilation cache (repro.compile_cache).
  kernel  the Pallas TTL kernel, compiled, on two seeded problems -- the
          §6.7.3 refresh batch (E=1024) and one 9-region refresh (E=8), both
          at C=800 cells -- against the jnp oracle on the same chip.
  replay  zipfian@xlarge (1.1M events, 100k objects, 16 buckets, 90 days)
          over 9 regions through both planes with engine "auto": zero
          divergence, both TTL controllers on the kernel.  Then the sim plane
          again on the float64 numpy engine: the same decisions and bill.
  served  a policy-driven VirtualStore behind two S3Proxy endpoints on
          localhost under a virtual clock; one sequential client PUTs 2,000
          seeded objects through one region and reads each back through the
          other region and its own, twice.  Every body round-trips byte-equal
          and the kernel serves at least one TTL refresh.

Throughputs are printed for information, labelled with the device; they are
not benchmark metrics.  The last line printed is the verdict:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import http.client
import json
import sys
import time
from pathlib import Path

SEED = 7
#: The kernel's cell count: repro.core.histogram.cell_edges().
CELLS = 800
#: Edge problems per kernel check: the §6.7.3 refresh batch, and one refresh
#: of a 9-region store (8 incoming edges).
KERNEL_EDGES = (1024, 8)
#: Matches tests/test_kernels.py (float32 kernel against the float32 oracle).
KERNEL_RTOL, KERNEL_ATOL = 2e-5, 1e-4
REGIONS = 9
#: Served phase: objects, their size range, and virtual seconds per request.
#: At 120 s a request, the second pass of reads through the far region spans
#: more than one 24 h refresh period after the histogram has warmed up.
SERVED_OBJECTS = 2000
SERVED_SIZES = (4 * 1024, 64 * 1024)
SERVED_STEP = 120.0


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def device_phase():
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found platform {d.platform!r} "
            f"({d.device_kind}, {len(devs)} device(s))")
    print(f"device: {d.platform} {d.device_kind} x{len(devs)}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def kernel_phase(kind: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.kernel_bench import ttl_problem
    from repro.core.ttl_policy import batched_cost_curves
    from repro.kernels import ops, ref
    from repro.kernels.ttl_scan import ttl_cost_surface

    oracle = jax.jit(ref.ttl_cost_ref)
    for e_dim in KERNEL_EDGES:
        prob = ttl_problem(e_dim, seed=SEED + e_dim)
        args = [jnp.asarray(x, jnp.float32) for x in prob]
        lowered = ttl_cost_surface.lower(*args)
        check("tpu_custom_call" in lowered.as_text(),
              f"E={e_dim}: no tpu_custom_call in the lowered kernel")
        surface = np.asarray(lowered.compile()(*args))
        expect = np.asarray(oracle(*args))
        check(surface.shape == (e_dim, CELLS) and np.isfinite(surface).all(),
              f"E={e_dim}: surface {surface.shape} not finite [E, C]")
        np.testing.assert_allclose(surface, expect, rtol=KERNEL_RTOL,
                                   atol=KERNEL_ATOL)
        rel = float(np.max(np.abs(surface - expect) / np.abs(expect)))

        _, _, full = ops.ttl_scan(*prob)
        full = np.asarray(full)
        grid, cost64 = batched_cost_curves(*prob[:4], prob[6], prob[4],
                                           prob[5])
        want = np.argmin(cost64, axis=1)
        plain = int(np.sum(np.argmin(full, axis=1) == want))
        # The refresh loop's decision: a row per edge, decided on the chip.
        ttl, _ = ops.ttl_sweep(np.stack(prob[:3], axis=1), prob[6], prob[4],
                               prob[5][:, None], prob[3])
        check(np.array_equal(ttl[:, 0], grid[want]),
              f"E={e_dim}: device decision != float64 argmin")
        print(f"kernel: E={e_dim} C={CELLS} compiled for {kind} "
              f"(tpu_custom_call); matches the jnp oracle on the chip, max "
              f"rel diff {rel!r}; plain float32 argmin == float64 argmin on "
              f"{plain}/{e_dim} rows, device decision on {e_dim}/{e_dim}",
              flush=True)


def replay_phase(kind: str) -> None:
    from repro.core.costmodel import pick_regions
    from repro.core.replay import replay_differential, run_sim_plane
    from repro.core.workloads import make_workload

    cat = pick_regions(REGIONS)
    tr = make_workload("zipfian", cat.region_names(), seed=SEED,
                       tier="xlarge")
    stats = tr.stats()
    print(f"replay: zipfian@xlarge, {len(tr.events)} events, "
          f"{stats['objects']} objects, {len(tr.buckets)} buckets, "
          f"{REGIONS} regions", flush=True)

    diff = replay_differential(tr, cat, "skystore", workload="zipfian@xlarge")
    print(f"replay: {diff.summary_line()}", flush=True)
    check(diff.ok(), "the planes diverged")
    for name, run in diff.planes.items():
        ctl = run.policy.ctl
        engine, n = ctl.resolve_engine(), ctl.n_refreshes
        print(f"replay: {name} plane engine={engine} refreshes={n} "
              f"{len(tr.events) / run.seconds!r} events/s on {kind}",
              flush=True)
        check(engine == "kernel", f"{name} plane resolved engine {engine!r}")
        check(n > 0, f"{name} plane served no TTL refresh")

    sim = diff.planes["sim"]
    ref = run_sim_plane(tr, cat, "skystore", engine="numpy")
    print(f"replay: sim plane engine=numpy refreshes="
          f"{ref.policy.ctl.n_refreshes} "
          f"{len(tr.events) / ref.seconds!r} events/s on {kind}", flush=True)
    n_diff = sum(a != b for a, b in zip(sim.decisions, ref.decisions))
    n_diff += abs(len(sim.decisions) - len(ref.decisions))
    print(f"replay: kernel vs numpy: {len(sim.decisions)} decisions, "
          f"{n_diff} differ", flush=True)
    check(n_diff == 0, f"kernel and numpy decisions differ at {n_diff}")
    check(sim.report.components() == ref.report.components(),
          "kernel and numpy bills differ")
    check(sim.report.counters() == ref.report.counters(),
          "kernel and numpy counters differ")


class VirtualClock:
    """The store's clock, advanced by the client between requests."""

    def __init__(self) -> None:
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def served_phase(kind: str) -> None:
    import numpy as np

    from repro.core import VirtualStore, make_backends, pick_regions
    from repro.core.policies import make_policy
    from repro.core.s3_proxy import S3Proxy

    cat = pick_regions(REGIONS)
    names = cat.region_names()
    clock = VirtualClock()
    policy = make_policy("skystore", cat)
    store = VirtualStore(cat, make_backends(list(names), "memory"),
                         policy=policy, clock=clock)
    near, far = names[0], names[REGIONS - 1]
    proxies = [S3Proxy(store, r).start() for r in (near, far)]
    conns = [http.client.HTTPConnection(*p.httpd.server_address[:2],
                                        timeout=30) for p in proxies]

    def call(conn, method, path, body=None):
        conn.request(method, path, body=body)
        resp = conn.getresponse()
        data = resp.read()
        check(resp.status == 200, f"{method} {path}: HTTP {resp.status} "
                                  f"{data[:200]!r}")
        clock.t += SERVED_STEP
        return data

    rng = np.random.default_rng(SEED)
    sizes = rng.integers(SERVED_SIZES[0], SERVED_SIZES[1] + 1, SERVED_OBJECTS)
    bodies = [rng.bytes(int(n)) for n in sizes]
    n_req = 0
    t0 = time.perf_counter()
    try:
        call(conns[0], "PUT", "/smoke")
        for i, body in enumerate(bodies):
            call(conns[0], "PUT", f"/smoke/obj-{i:05d}", body)
        n_req += 1 + len(bodies)
        for _round in range(2):
            for conn in (conns[1], conns[0]):
                for i, body in enumerate(bodies):
                    got = call(conn, "GET", f"/smoke/obj-{i:05d}")
                    check(got == body, f"obj-{i:05d}: body differs "
                                       f"({len(got)} != {len(body)} bytes)")
                n_req += len(bodies)
    finally:
        for conn in conns:
            conn.close()
        for p in proxies:
            p.stop()
    dt = time.perf_counter() - t0
    ctl = policy.ctl
    engine, n = ctl.resolve_engine(), ctl.n_refreshes
    print(f"served: {n_req} requests via {near} and {far}, all bodies "
          f"byte-equal, engine={engine} refreshes={n}, "
          f"{n_req / dt!r} requests/s on {kind}", flush=True)
    check(engine == "kernel", f"served store resolved engine {engine!r}")
    check(n > 0, "no TTL refresh fell due on the served store")


def main() -> None:
    device = device_phase()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import setup_compile_cache

    print(f"cache: {setup_compile_cache()}", flush=True)
    for name, phase in (("kernel", kernel_phase),
                        ("replay", replay_phase),
                        ("served", served_phase)):
        t0 = time.perf_counter()
        phase(device["kind"])
        print(f"{name}: phase took {time.perf_counter() - t0!r} s", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
