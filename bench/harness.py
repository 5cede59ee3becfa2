"""The benchmark's shared machinery: finding a cell and its files by name,
host spans, the device check, the compile cache and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found here by the name that
``BENCHMARK.json`` gives it:

    bench/configs/<config>.json       the deployment, as it is run
    bench/traffic/<traffic>.json      driver, generator and parameters
    bench/generators/<generator>.py   ``make(regions, seed, **params)``
    bench/drivers/<driver>.py         ``setup``, ``window``, ``check``
    bench/metrics/<metric>.py         ``read(run)``: a number or None
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

#: The checkout the benchmark runs from (``bench/`` is directly below it).
ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
#: Where a traced run writes its profile; emptied before each traced run.
TRACE_DIR = ROOT / ".bench_trace"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file of the benchmark by its path (metric files carry dots
    in their names, so they are not importable by module name)."""
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts)
    name = name.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str, reported: Optional[set] = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def load_cell(name: str, spec: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` (or of ``spec``), with the
    end-to-end and per-layer metrics it reports."""
    spec = load_json(ROOT / "BENCHMARK.json") if spec is None else spec
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                         f"have {sorted(by_name)}")
    w = by_name[name]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _applies(m, name, reported)]
    return Cell(name, int(w["chips"]), load_json(ROOT / cfg["file"]),
                load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                e2e, per_layer)


def generator(traffic: dict) -> Callable:
    return load_module(BENCH / "generators" / f"{traffic['generator']}.py").make


def driver(traffic: dict):
    return load_module(BENCH / "drivers" / f"{traffic['driver']}.py")


def metric_reader(name: str) -> Callable:
    return load_module(BENCH / "metrics" / f"{name}.py").read


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------

class Spans:
    """Host spans of one run: intervals on the host clock, kept in memory.

    With ``annotate`` on (traced runs) each span is also a
    ``jax.profiler.TraceAnnotation`` of the same name, so the device trace
    says what the host was doing while the device sat idle."""

    def __init__(self, annotate: bool = False) -> None:
        self.annotate = annotate
        self.intervals: Dict[str, List[Tuple[float, float]]] = {}
        self._restore: List[Callable] = []
        if annotate:
            import jax
            self._annotation = jax.profiler.TraceAnnotation

    def add(self, name: str, t0: float, t1: float) -> None:
        self.intervals.setdefault(name, []).append((t0, t1))

    @contextlib.contextmanager
    def span(self, name: str):
        ctx = (self._annotation(name) if self.annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        try:
            with ctx:
                yield
        finally:
            self.add(name, t0, time.perf_counter())

    def wrap(self, owner, attr: str, name: str) -> None:
        """Time every call of ``owner.attr`` (a class's method or an
        object's bound method) as span ``name`` until :meth:`unwrap`."""
        is_class = isinstance(owner, type)
        orig = owner.__dict__[attr] if is_class else getattr(owner, attr)
        span = self.span

        def wrapper(*args, **kwargs):
            with span(name):
                return orig(*args, **kwargs)

        setattr(owner, attr, wrapper)
        if is_class:
            self._restore.append(lambda: setattr(owner, attr, orig))
        else:
            self._restore.append(lambda: delattr(owner, attr))

    def unwrap(self) -> None:
        while self._restore:
            self._restore.pop()()

    def total(self, name: str, window: Tuple[float, float]) -> float:
        """Seconds of span ``name`` inside ``window``, nested calls of the
        same span counted once."""
        lo, hi = window
        total, reach = 0.0, lo
        for a, b in sorted(self.intervals.get(name, [])):
            a, b = max(a, reach), min(b, hi)
            if b > a:
                total += b - a
                reach = b
        return total


# ---------------------------------------------------------------------------
# Device, cache, compiles
# ---------------------------------------------------------------------------

def require_devices(chips: int) -> dict:
    """The accelerator this run measures; exits non-zero, naming what JAX
    found, when that is not a TPU with at least ``chips`` chips."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"bench: needs {chips} TPU chip(s); JAX found platform "
            f"{d.platform!r} ({d.device_kind}, {len(devs)} device(s))")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def setup_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else the fixed ``.jax_cache`` of this checkout.  Every program is
    cached, so a cell's second run finds all of them."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Counts the programs JAX traces for compilation; read it around the
    window to see that nothing compiles there."""

    def __init__(self) -> None:
        self.n = 0
        import jax

        def on_duration(name, _secs, **_kw):
            if name == "/jax/core/compile/jaxpr_trace_duration":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# The result
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit: the run is
    correct when every value is at most its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def emit(correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, checks: List[Check],
         breakdown: Optional[dict] = None) -> None:
    """Print the checks as the last lines of stderr, and the result as the
    last line of stdout with the checks under the last key."""
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r}", file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    print(json.dumps(line), flush=True)
