"""BENCHMARK.json and the files it names: every cell, configuration, traffic
mix and per-layer metric is found by name, and each configuration is the
deployment the program runs."""

import json
import math
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1] == "bench/run.py"


def test_names_units_and_lines():
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in SPEC["workloads"]]:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for entry in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


def test_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_run_seconds_fit_a_full_check():
    s = SPEC["run_seconds"]
    assert 1 <= s <= 51
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load_by_name(cell):
    c = harness.load_cell(cell, SPEC)
    assert c.chips == 1
    assert callable(harness.generator(c.traffic))
    drv = harness.driver(c.traffic)
    for fn in ("setup", "window", "check"):
        assert callable(getattr(drv, fn))
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.metric_reader(m["name"]))


def test_every_config_is_used_and_has_its_own_file():
    used = {w["config"] for w in SPEC["workloads"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert used == {c["name"] for c in SPEC["configs"]}
    assert len(set(files)) == len(files)
    for f in files:
        assert f.startswith("bench/") and (ROOT / f).is_file()


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_config_is_the_programs_deployment(config):
    from repro.core.costmodel import pick_regions
    from repro.core.histogram import cell_edges
    from repro.core.ttl_policy import AdaptiveTTLController

    from bench import system
    from bench.reference import skystore_fb

    cfg = json.loads((ROOT / f"bench/configs/{config}.json").read_text())
    want = pick_regions(9)
    got = system.cost_model(cfg)
    assert got.region_names() == want.region_names()
    for a in want.region_names():
        assert got.storage_price(a) == want.storage_price(a)
        assert got.op_cost(a, "PUT") == want.op_cost(a, "PUT")
        assert got.op_cost(a, "GET") == want.op_cost(a, "GET")
        for b in want.region_names():
            assert got.egress_price(a, b) == want.egress_price(a, b)
    edges = skystore_fb.cell_edges(cfg["ttl"]["histogram"])
    assert edges.shape == cell_edges().shape and (edges == cell_edges()).all()
    ctl = AdaptiveTTLController(got)
    assert ctl.refresh_period == cfg["ttl"]["refresh_period_s"]
    assert ctl.warmup_min_samples == cfg["ttl"]["warmup_min_samples"]
    assert ctl.rotate_multiple == cfg["ttl"]["rotate_multiple_of_t_even"]
    assert cfg["policy_params"]["refresh_period"] == ctl.refresh_period
    assert math.isclose(cfg["scan_interval_s"], 86400.0)
