"""The plain reference agrees with the program, decision for decision, on
small traces through both planes (float64 numpy engine on this CPU host),
over every request kind the generators emit."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, system  # noqa: E402
from bench.reference import skystore_fb  # noqa: E402

CFG = harness.load_json(ROOT / "bench/configs/sim9_fb_skystore.json")
REPLAY = harness.load_module(ROOT / "bench/drivers/replay.py")


def trace_of(kind, seed):
    regions = system.cost_model(CFG).region_names()
    if kind == "t65":
        return harness.generator({"generator": "t_profile"})(
            regions, seed, profile="T65", structure_seed=seed, region_mix="E",
            n_buckets=2, n_objects=40, months=2.0)
    if kind == "zipfian":      # HEADs, deletes, LISTs, overwrites
        return harness.generator({"generator": "zipfian"})(
            regions, seed, n_objects=200, n_requests=3000, n_buckets=3)
    from repro.core.workloads import write_heavy   # cross-region overwrites
    return write_heavy(regions, seed=seed)


# T65 objects reach 1 GB, which the live plane would materialize: the T65
# traces go through the simulator only, as in the benchmark's cells.
@pytest.mark.parametrize("plane,kind,seed", [
    ("sim", "t65", 1), ("sim", "t65", 2**31 + 3), ("sim", "zipfian", 4),
    ("live", "zipfian", 4), ("sim", "write_heavy", 5),
    ("live", "write_heavy", 5)])
def test_reference_matches_the_program(plane, kind, seed):
    cfg = dict(CFG, plane=plane)
    trace = trace_of(kind, seed)
    run = system.run_plane(cfg, trace, system.cost_model(cfg))
    ref = skystore_fb.replay(cfg, trace.events, trace.regions, trace.buckets)
    assert ref["refreshes"] > 0
    assert len(ref["decisions"]) > 100
    for c in REPLAY.compare(ref, [REPLAY.result(run)]):
        assert c.ok, (c.name, c.value)
