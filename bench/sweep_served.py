"""Sweep the offered rate of a served cell to find its knee.

    python bench/sweep_served.py --workload store9_ycsb_b_served \\
        --rates 800 1200 1600 --seconds 8 --seed 1

Runs the cell's window once per rate, each on a fresh store, in one process
on one TPU chip, and prints per rate: GET p50 and p99, the generator's send
lag p99, and the completed requests per second.  The knee is the highest
rate at which the send lag stays flat (no growing backlog); the cell's
``rate_per_s`` is set at about four fifths of it.  Not part of a benchmark
run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, runner  # noqa: E402
from bench.stats import percentile  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="store9_ycsb_b_served")
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    device = harness.require_devices(cell.chips)
    harness.setup_compile_cache()
    for rate in args.rates:
        cell.traffic["rate_per_s"] = rate
        out = runner.run_cell(cell, args.seed, args.seconds, False, device,
                              time.perf_counter())
        lat = out["samples"]
        print(json.dumps({
            "rate_per_s": rate, "correct": out["correct"],
            "get_p50_ms": 1e3 * percentile(lat["get_latency_s"], 50),
            "get_p99_ms": 1e3 * percentile(lat["get_latency_s"], 99),
            "get_p999_ms": 1e3 * percentile(lat["get_latency_s"], 99.9),
            "send_lag_p99_ms": 1e3 * percentile(lat["send_lag_s"], 99),
            "send_lag_last_ms": 1e3 * lat["send_lag_s"][-1],
            "completed_per_s": out["attempted"] / out["window_s"]}), flush=True)


if __name__ == "__main__":
    main()
