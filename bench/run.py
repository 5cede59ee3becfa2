"""Run one cell of the benchmark once and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name through
``BENCHMARK.json`` (see ``bench/harness.py``).  The run

1. checks that JAX sees a TPU with the chips the cell asks for, and exits
   non-zero, naming the platform it found, when it does not;
2. sets up: generates the traffic from ``--seed``, builds the deployment
   and warms every device program through a short replay (``setup_s``, from
   the start of this script, compilation or cache loads included);
3. measures one window of ``--seconds`` (``--trace 1``: under the profiler,
   with host spans around the program's TTL selection);
4. reads the device's peak memory, then compares what the window produced
   with the plain reference, and prints each compared number beside its
   limit as the last lines of stderr;
5. prints one JSON line: ``correct``, ``attempted``, ``failed``,
   ``metrics`` (end-to-end with ``--trace 0``, per-layer with
   ``--trace 1``), ``device``, ``breakdown`` (traced runs) and ``checks``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, runner  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    device = harness.require_devices(cell.chips)
    harness.setup_compile_cache()
    out = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
                   T_START)
    harness.emit(out["correct"], out["attempted"], out["failed"],
                 out["metrics"], out["device"], out["checks"],
                 out.get("breakdown"))


if __name__ == "__main__":
    main()
