"""The benchmark's command refuses to run anywhere but on a TPU: it exits
non-zero, names the platform it found, and prints no result.  A traced run
stops, rather than drop its TTL metrics, when the TTL spans cannot be
placed or recorded nothing."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def test_run_exits_nonzero_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sim9_t65_mixE",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def _record(refreshes, ttl_spans, device_ttl_s):
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, runner

    class Device:
        def seconds_inside(self, span):
            return device_ttl_s if span == "bench.ttl" else 0.0

    spans = harness.Spans()
    for a, b in ttl_spans:
        spans.add("bench.ttl", a, b)
    return runner.RunRecord("replay", {}, (0.0, 10.0), spans,
                            {"ttl_refreshes": refreshes}, {}, Device(),
                            "TPU v5 lite")


@pytest.mark.parametrize("refreshes,ttl_spans,device_ttl_s,fails", [
    (3, [(1.0, 1.5)], 0.002, False),
    (0, [], 0.0, False),
    (3, [], 0.0, True),
    (3, [(1.0, 1.5)], 0.0, True),
])
def test_ttl_metrics_never_fall_silent(refreshes, ttl_spans, device_ttl_s,
                                       fails):
    from bench import runner

    rec = _record(refreshes, ttl_spans, device_ttl_s)
    if fails:
        with pytest.raises(SystemExit, match="TTL refreshes in the window"):
            runner._require_ttl_spans(rec)
    else:
        runner._require_ttl_spans(rec)


def test_missing_ttl_entry_point_stops_the_run(monkeypatch):
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from repro.core.ttl_policy import AdaptiveTTLController

    from bench import harness, runner

    monkeypatch.delattr(AdaptiveTTLController, "edge_ttl_table")
    spans = harness.Spans()
    with pytest.raises(SystemExit, match="edge_ttl_table"):
        runner._span_ttl(spans)
    assert not spans._restore
