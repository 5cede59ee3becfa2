"""Readings that the limits of ``correct`` are set from (PERF.md, section 2).

    python bench/readings.py --workload sim9_t65_mixE --seeds 1 2 3 \\
        --control-seeds 1 2 3

For each seed of a replay cell, one replay through the program (as the
window runs it) and one through the plain reference, compared by the
cell's numbers: the program's readings.  For each control seed, the
reference computed in the precision below the configuration's (the TTL
surface in bfloat16 for the kernel's float32) put in the program's place
and compared the same way: the control's readings, which must fail.  For
a served cell, the window runs at the cell's rate and there are two
controls: the reference in that lower precision in the store's place for
the TTL numbers, and a store that breaks read-your-writes (it answers every
GET with the first body written to the key) for the answers.  One process,
one chip; prints one JSON line per reading.  Not part of a benchmark run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, runner, system  # noqa: E402
from bench.reference import skystore_fb  # noqa: E402

#: The precision of the control, one below the configuration's surface.
CONTROL_PRECISION = {"float32": "bfloat16", "float64": "float32"}


def replay_readings(cell, seed: int, control: bool) -> dict:
    drv = harness.driver(cell.traffic)
    cfg = cell.config
    cost = system.cost_model(cfg)
    trace = harness.generator(cell.traffic)(cost.region_names(), seed,
                                            **cell.traffic["params"])
    ref = skystore_fb.replay(cfg, trace.events, trace.regions, trace.buckets)
    if control:
        low = CONTROL_PRECISION[cfg["ttl"]["surface_precision"]]
        runs = [skystore_fb.replay(cfg, trace.events, trace.regions,
                                   trace.buckets, precision=low)]
    else:
        runs = [drv.result(system.run_plane(cfg, trace, cost))]
    checks = drv.compare(ref, runs)
    return {c.name: c.value for c in checks}


def stale_answers(rows, answers, acked: dict) -> list:
    """The served control: the answers of a store that never applies an
    update -- every GET returns the first body written to its key."""
    first = dict(acked)
    out = []
    for (t, op, bucket, key, region, body), (status, data, etag) in zip(
            rows, answers):
        if op == "PUT":
            first.setdefault((bucket, key), body)
            out.append((status, data, etag))
        else:
            want = first.get((bucket, key), b"")
            out.append((200, want, f'"{hashlib.md5(want).hexdigest()}"'))
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=5.0,
                    help="window of a served cell's readings")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    device = harness.require_devices(cell.chips)
    harness.setup_compile_cache()
    served = cell.traffic["driver"] == "served"
    for control, seeds in ((False, args.seeds), (True, args.control_seeds)):
        for seed in seeds:
            t0 = time.perf_counter()
            if served:
                drv = harness.driver(cell.traffic)
                ctx = runner.Context(cell, seed, args.seconds, False,
                                     harness.Spans(),
                                     harness.generator(cell.traffic))
                st = drv.setup(ctx)
                win = drv.window(ctx, st)
                acked = st["acked"]
                answers = (stale_answers(st["rows"], win["answers"], acked)
                           if control else win["answers"])
                got = (drv.reference_ttls(st, CONTROL_PRECISION[
                           cell.config["ttl"]["surface_precision"]])
                       if control else drv.program_ttls(st))
                checks = (drv.compare(st["rows"], answers, acked)
                          + drv.compare_ttls(drv.reference_ttls(st), got))
                vals = {c.name: c.value for c in checks}
            else:
                vals = replay_readings(cell, seed, control)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "control": control, "device": device["kind"],
                              "seconds": time.perf_counter() - t0, **vals}),
                  flush=True)


if __name__ == "__main__":
    main()
