"""The device trace of a traced run, reduced to what the metrics read.

A traced run records its window with ``jax.profiler``; the benchmark's host
spans (``bench.window``, ``bench.replay``, ``bench.ttl``, ...) are
``TraceAnnotation`` events on the same clock.  From the profile this module
takes:

* the window: the ``bench.window`` host span;
* the device programs: the events of each device plane's ``XLA Modules``
  line (one per program execution), clipped to the window.  Their union is
  the device's busy time; the rest of the window is idle;
* what the host was doing: on every host thread that carries a ``bench.``
  span, the innermost host event at each instant (a ``bench.`` span, or a
  JAX event inside it such as ``DevicePut`` or ``PjitFunction(...)``).
  Where threads differ, a JAX event names the instant before a ``bench.``
  span, and a ``bench.`` span by a fixed order (:data:`PRIORITY`).

Times in the profile are nanoseconds from the start of the trace.
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import re
import shutil
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]


def profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # no per-Python-call events
    opts.enable_hlo_proto = False
    return opts


def start(directory: Path) -> None:
    import jax

    shutil.rmtree(directory, ignore_errors=True)
    jax.profiler.start_trace(str(directory), profiler_options=profiler_options())


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def find_profile(directory: Path) -> Path:
    found = sorted(Path(directory).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def complement(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The gaps of disjoint sorted ``busy`` inside [lo, hi]."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def innermost(events: Sequence[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, int, str]]:
    """Nested events of one thread -> segments ``(start, end, depth, name)``
    naming the innermost event at each instant (depth 1 = outermost)."""
    out: List[Tuple[float, float, int, str]] = []
    stack: List[Tuple[float, str]] = []
    t = None
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm = stack.pop()
            if end > t:
                out.append((t, end, len(stack) + 1, nm))
            t = max(t, end)
        if stack and s > t:
            out.append((t, s, len(stack), stack[-1][1]))
        t = s
        stack.append((e, name))
    while stack:
        end, nm = stack.pop()
        if end > t:
            out.append((t, end, len(stack) + 1, nm))
        t = max(t, end)
    return out


#: Which thread names an instant when several are inside ``bench.`` spans:
#: a JAX event (dispatch, transfer) first, then by this order, then depth.
PRIORITY = {"bench.window": 0, "bench.http": 1, "bench.replay": 2,
            "bench.dispatch": 3, "bench.background": 4, "bench.ttl": 5}


def _rank(seg: Tuple[float, float, int, str]) -> Tuple[int, int, int]:
    name = seg[3]
    return (not name.startswith("bench."), PRIORITY.get(name, 0), seg[2])


def deepest(threads: Sequence[List[Tuple[float, float, int, str]]]
            ) -> List[Tuple[float, float, str]]:
    """Combine per-thread segments: each instant takes the name of the
    highest-ranked (:func:`_rank`) event among the threads."""
    cuts = sorted({x for segs in threads for s in segs for x in s[:2]})
    ptr = [0] * len(threads)
    out: List[Tuple[float, float, str]] = []
    for a, b in zip(cuts, cuts[1:]):
        best = None
        for i, segs in enumerate(threads):
            while ptr[i] < len(segs) and segs[ptr[i]][1] <= a:
                ptr[i] += 1
            if ptr[i] < len(segs) and segs[ptr[i]][0] <= a:
                seg = segs[ptr[i]]
                if best is None or _rank(seg) > _rank(best):
                    best = seg
        if best is not None:
            if out and out[-1][2] == best[3] and out[-1][1] == a:
                out[-1] = (out[-1][0], b, best[3])
            else:
                out.append((a, b, best[3]))
    return out


def overlap_by_label(gaps: Sequence[Interval],
                     labels: Sequence[Tuple[float, float, str]]
                     ) -> Dict[str, float]:
    """Length of ``gaps`` (disjoint, sorted) under each label; time under no
    host event counts as ``(no host span)``."""
    out: Dict[str, float] = {}
    j = 0
    for a, b in gaps:
        covered = 0.0
        while j < len(labels) and labels[j][1] <= a:
            j += 1
        k = j
        while k < len(labels) and labels[k][0] < b:
            lo, hi = max(a, labels[k][0]), min(b, labels[k][1])
            if hi > lo:
                out[labels[k][2]] = out.get(labels[k][2], 0.0) + (hi - lo)
                covered += hi - lo
            k += 1
        if b - a > covered:
            out["(no host span)"] = out.get("(no host span)", 0.0) + (b - a - covered)
    return out


_HASH = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    """``jit_ttl_cost_surface(1246...)`` -> ``jit_ttl_cost_surface``."""
    return _HASH.sub("", event_name)


@dataclasses.dataclass
class Timeline:
    """One traced window, in nanoseconds."""

    window: Interval
    #: Per device: (program name, start, end) of every program in the window.
    programs: List[List[Tuple[str, float, float]]]
    #: ``bench.`` host spans by name, over all threads.
    spans: Dict[str, List[Interval]]
    #: What the host was doing: (start, end, innermost event name).
    host: List[Tuple[float, float, str]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self, device: int) -> List[Interval]:
        return merge([(a, b) for _n, a, b in self.programs[device]])

    @property
    def busy_s(self) -> float:
        """Seconds some program ran, averaged over the devices."""
        tot = sum(b - a for d in range(len(self.programs))
                  for a, b in self.busy(d))
        return tot / len(self.programs) * 1e-9

    def idle_by_label(self) -> Dict[str, float]:
        """Idle device seconds by what the host was doing, averaged over
        the devices."""
        out: Dict[str, float] = {}
        for d in range(len(self.programs)):
            gaps = complement(self.busy(d), *self.window)
            for k, v in overlap_by_label(gaps, self.host).items():
                out[k] = out.get(k, 0.0) + v * 1e-9 / len(self.programs)
        return out

    def program_seconds(self) -> Dict[str, float]:
        """Device seconds per program name, summed over the devices."""
        out: Dict[str, float] = {}
        for progs in self.programs:
            for name, a, b in progs:
                out[name] = out.get(name, 0.0) + (b - a) * 1e-9
        return out

    def seconds_inside(self, span: str) -> float:
        """Device seconds of the programs that start inside host span
        ``span`` (on any thread), summed over the devices."""
        ivs = merge(self.spans.get(span, []))
        starts = [a for a, _ in ivs]
        tot = 0.0
        for progs in self.programs:
            for _name, a, b in progs:
                i = bisect.bisect_right(starts, a) - 1
                if i >= 0 and a < ivs[i][1]:
                    tot += (b - a) * 1e-9
        return tot


def read_profile(path: Path):
    """The profile at ``path`` (an ``.xplane.pb``, or one gzipped)."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(gzip.decompress(
            path.read_bytes()))
    return ProfileData.from_file(str(path))


def load(path: Path, chips: int, window_span: str = "bench.window") -> Timeline:
    pd = read_profile(path)
    devices, threads = {}, []
    spans: Dict[str, List[Interval]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            line = lines.get("XLA Modules") or lines.get("XLA Ops")
            if line is not None:
                devices[plane.name] = [
                    (program_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events]
                marked = [ev for ev in evs if ev[2].startswith("bench.")]
                if marked:
                    threads.append(evs)
                    for a, b, name in marked:
                        spans.setdefault(name, []).append((a, b))
    if window_span not in spans:
        raise ValueError(f"no {window_span!r} span in {path}")
    lo, hi = spans[window_span][0]
    names = sorted(devices, key=lambda n: int(n.rsplit(":", 1)[1]))[:chips]
    programs = [[(n, max(a, lo), min(b, hi)) for n, a, b in devices[k]
                 if min(b, hi) > max(a, lo)] for k in names]
    host = deepest([innermost([e for e in evs if e[1] > lo and e[0] < hi])
                    for evs in threads])
    host = [(max(a, lo), min(b, hi), n) for a, b, n in host
            if min(b, hi) > max(a, lo)]
    return Timeline((lo, hi), programs, spans, host)


def reduce(directory: Path, chips: int) -> Timeline:
    """The timeline of the profile a traced run wrote under ``directory``."""
    return load(find_profile(directory), chips)
