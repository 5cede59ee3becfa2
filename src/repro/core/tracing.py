"""In-program spans and counters: per-layer self time of a replay or of a
running :class:`~repro.core.s3_proxy.S3Proxy`.

    from repro.core import tracing

    tracing.start()                 # annotate=True also writes each kept span
    ...                             # into a running jax.profiler trace
    snap = tracing.stop()
    snap.layer_seconds()            # {"event spine": ..., "uncovered": ...}

Two kinds of span, both timed on ``time.perf_counter`` (the clock of the
benchmark's host spans; a span never feeds a decision):

* **Per-event spans** (:data:`SPANS`): methods at the boundaries between the
  modules of :mod:`repro.core`, replaced by timing wrappers only while the
  recorder runs.  With recording off the program runs its own methods, with
  no check per call.  These spans are aggregated by name (count, total and
  self seconds) and never stored one by one.
* **Kept spans**: refresh-, run- and request-grain blocks in the code,
  ``with tracing.span(name):``.  With recording off that costs one read of
  this module's recorder and a shared null context.  Each is kept with its
  start, end, self time, parent and thread, and with ``annotate`` on it is
  also a ``jax.profiler.TraceAnnotation`` of the same name.  The spans of
  :data:`PROMOTED` are kept, and annotated, when their parent is a kept span
  (a ``VirtualStore.dispatch`` under an S3 request) and aggregated otherwise.

Each thread keeps its own stack (``S3Proxy`` serves on many threads).  A
span's self time is its duration minus the time its children cover.  The
recorder measures what one span of each kind costs its parent when it
starts, and takes that cost off the parent's self time and off the
recording's interval, so the shares describe the untraced program.

Counters are integers the program keeps where the work happens (for
example ``ExpiryIndex.n_pops``); a replay publishes them once, at its end,
with :func:`count`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import threading
import time
from typing import Dict, List, Optional, Tuple

perf = time.perf_counter

#: Per-event spans: (module under ``repro.core``, class, method, span name).
#: Every entry must exist when recording starts; :func:`start` raises,
#: naming it, when one does not, so a rename cannot silence a metric.
#: A span costs its caller about a microsecond, so a method that does less
#: than that, or whose span would split no layer (the simulator's request
#: handlers are the spine, like the loop that calls them), has none.
SPANS: Tuple[Tuple[str, str, str, str], ...] = (
    ("engine", "EventSpine", "drain_due", "skystore.expiry.drain"),
    ("simulator", "Simulator", "_expire_batch", "skystore.expiry.apply"),
    ("simulator", "Simulator", "_charge_op", "skystore.ledger.charge_op"),
    ("simulator", "Simulator", "_charge_transfer",
     "skystore.ledger.charge_transfer"),
    ("simulator", "Simulator", "_charge_storage",
     "skystore.ledger.charge_storage"),
    ("virtual_store", "VirtualStore", "dispatch", "skystore.store.dispatch"),
    ("virtual_store", "VirtualStore", "_handle_get", "skystore.store.get"),
    ("virtual_store", "VirtualStore", "expire_replicas",
     "skystore.expiry.apply"),
    ("metadata", "MetadataServer", "locate", "skystore.meta.locate"),
    ("metadata", "MetadataServer", "record_get", "skystore.meta.record_get"),
    ("metadata", "MetadataServer", "holders", "skystore.meta.holders"),
    ("metadata", "MetadataServer", "touch_replica",
     "skystore.meta.touch_replica"),
    ("metadata", "MetadataServer", "commit_replica",
     "skystore.meta.commit_replica"),
    ("metadata", "MetadataServer", "drop_replica", "skystore.meta.drop_replica"),
    ("metadata", "MetadataServer", "begin_upload", "skystore.meta.begin_upload"),
    ("metadata", "MetadataServer", "complete_upload",
     "skystore.meta.complete_upload"),
    ("routing", "RoutingMatrix", "route_chunk", "skystore.routing.route_chunk"),
    ("ledger", "CostLedger", "charge_op", "skystore.ledger.charge_op"),
    ("ledger", "CostLedger", "charge_transfer", "skystore.ledger.charge_transfer"),
    ("ledger", "CostLedger", "on_replica_commit",
     "skystore.ledger.on_replica_commit"),
    ("ledger", "CostLedger", "on_replica_drop", "skystore.ledger.on_replica_drop"),
    ("ledger", "CostLedger", "on_replica_drop_batch",
     "skystore.ledger.on_replica_drop_batch"),
    ("policies", "SkyStorePolicy", "observe_get", "skystore.policy.observe_get"),
    ("policies", "SkyStorePolicy", "ttl_on_access",
     "skystore.policy.ttl_on_access"),
    ("policies", "SkyStorePolicy", "periodic", "skystore.policy.periodic"),
    ("ttl_policy", "AdaptiveTTLController", "edge_ttl",
     "skystore.policy.edge_ttl"),
)

#: The spine's batch stream: each batch the consumer processes is one span,
#: ``skystore.spine.<kind>`` (``data``, ``expire``, ``tick``, ...).
SPINE = ("engine", "EventSpine", "iter_batches")

#: Per-event spans kept one by one when their parent span is kept.
PROMOTED = frozenset({"skystore.store.dispatch"})

#: The layer of each span, by name prefix (the layers of PERF.md section 3).
LAYERS: Tuple[Tuple[str, str], ...] = (
    ("skystore.replay.", "event spine"),
    ("skystore.spine.", "event spine"),
    ("skystore.store.", "typed ops"),
    ("skystore.meta.", "control plane"),
    ("skystore.routing.", "control plane"),
    ("skystore.expiry.", "control plane"),
    ("skystore.ledger.", "charges"),
    ("skystore.policy.", "policy TTL selection"),
    ("skystore.ttl.", "policy TTL selection"),
    ("skystore.s3.", "wire codec"),
)


def layer_of(name: str) -> str:
    for prefix, layer in LAYERS:
        if name.startswith(prefix):
            return layer
    raise KeyError(f"span {name!r} has no layer")


# ---------------------------------------------------------------------------
# What a recording returns
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SpanRecord:
    """One kept span.  ``parent`` indexes :attr:`Snapshot.kept` (the nearest
    kept span around it on its thread, -1 for none); ``self_s`` is its
    duration minus its children's, compensated for the recorder's cost."""

    name: str
    start: float
    end: float
    self_s: float
    parent: int
    thread: int

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Aggregate:
    """Every span of one name: how many, their total seconds as measured,
    and their self seconds (compensated)."""

    count: int
    total_s: float
    self_s: float


@dataclasses.dataclass
class Snapshot:
    """One recording: its interval on ``perf_counter``, every span by name,
    the kept spans one by one, the counters, and what one span cost."""

    start: float
    end: float
    spans: Dict[str, Aggregate]
    kept: List[SpanRecord]
    counters: Dict[str, int]
    #: Seconds one span of each kind (``aggregated``, ``kept``) adds to its
    #: parent, measured when recording started.
    span_cost_s: Dict[str, float]
    #: Spans recorded of each kind, on every thread.
    n_spans: Dict[str, int]

    @property
    def overhead_s(self) -> float:
        """The recorder's own time inside the interval."""
        return sum(self.span_cost_s[k] * n for k, n in self.n_spans.items())

    @property
    def interval_s(self) -> float:
        """The interval as the untraced program would have taken it.  Meant
        for a recording whose spans ran on one thread, such as a replay."""
        return self.end - self.start - self.overhead_s

    def layer_seconds(self) -> Dict[str, float]:
        """Self seconds by layer, and ``uncovered``: the interval's time in
        no span.  They add up to :attr:`interval_s`."""
        out: Dict[str, float] = {}
        for name, agg in self.spans.items():
            layer = layer_of(name)
            out[layer] = out.get(layer, 0.0) + agg.self_s
        out["uncovered"] = self.interval_s - sum(out.values())
        return out

    def named(self, name: str) -> List[SpanRecord]:
        return [s for s in self.kept if s.name == name]

    def children(self) -> Dict[int, List[int]]:
        """The indices of each kept span's kept children, by the parent's
        index (-1: spans with no kept parent)."""
        out: Dict[int, List[int]] = {}
        for i, s in enumerate(self.kept):
            out.setdefault(s.parent, []).append(i)
        return out


# ---------------------------------------------------------------------------
# The recorder
# ---------------------------------------------------------------------------

class _Thread:
    """One thread's part of a recording.  ``stack`` holds, per open span,
    the time its children cover so far; ``stats`` the count, total and
    self seconds of each span name; ``kept`` the thread's kept spans as
    tuples ``(name, start, end, self, parent)`` in start order (``None``
    while open: tuples of numbers are no work for the garbage collector),
    and ``kept_at``/``kept_idx`` the stack depth and index of each open
    one."""

    __slots__ = ("stack", "stats", "kept", "kept_at", "kept_idx", "number")

    def __init__(self, number: int) -> None:
        self.stack: List[float] = []
        self.stats: Dict[str, list] = {}
        self.kept: List[Optional[tuple]] = []
        self.kept_at: List[int] = []
        self.kept_idx: List[int] = []
        self.number = number

    def close(self, name: str, d: float, cost: float) -> float:
        """Account the span on top of the stack, ``d`` seconds long, which
        costs its parent ``cost`` seconds beyond that; returns its self
        seconds."""
        own = d - self.stack.pop()
        s = self.stats.get(name)
        if s is None:
            s = self.stats[name] = [0, 0.0, 0.0]
        s[0] += 1
        s[1] += d
        s[2] += own
        if self.stack:
            self.stack[-1] += d + cost
        return own


class _Local(threading.local):
    def __init__(self, rec: "_Recorder") -> None:
        self.state = rec._new_thread()


class _Recorder:
    def __init__(self, annotate: bool) -> None:
        self.annotation = None
        if annotate:
            import jax

            self.annotation = jax.profiler.TraceAnnotation
        self._lock = threading.Lock()
        self._threads: List[_Thread] = []
        self.local = _Local(self)
        self.counters: Dict[str, int] = {}
        self.cost_agg = 0.0
        self.cost_kept = 0.0
        self._restore: List = []
        self.t0 = self.t1 = 0.0

    def _new_thread(self) -> _Thread:
        with self._lock:
            ts = _Thread(len(self._threads))
            self._threads.append(ts)
            return ts

    # -- the two kinds of span ----------------------------------------------
    def aggregated(self, fn, name: str):
        """``fn`` timed as per-event span ``name`` (kept instead when
        ``name`` is in :data:`PROMOTED` and its parent is kept)."""
        rec, local, cost = self, self.local, self.cost_agg
        promote = name in PROMOTED

        def traced(*args, **kwargs):
            ts = local.state
            st = ts.stack
            if promote and ts.kept_at and ts.kept_at[-1] == len(st):
                with _Kept(rec, name, ts):
                    return fn(*args, **kwargs)
            st.append(0.0)
            t0 = perf()
            try:
                return fn(*args, **kwargs)
            finally:
                # _Thread.close, inlined: this runs on every event.
                d = perf() - t0
                covered = st.pop()
                s = ts.stats.get(name)
                if s is None:
                    s = ts.stats[name] = [0, 0.0, 0.0]
                s[0] += 1
                s[1] += d
                s[2] += d - covered
                if st:
                    st[-1] += d + cost

        return traced

    def batches(self, fn):
        """The spine's batch generator, each batch one span of its kind."""
        local, cost = self.local, self.cost_agg

        def traced(*args, **kwargs):
            for batch in fn(*args, **kwargs):
                ts = local.state
                ts.stack.append(0.0)
                t0 = perf()
                try:
                    yield batch
                finally:
                    ts.close("skystore.spine." + batch.kind, perf() - t0,
                             cost)

        return traced

    def kept(self, name: str) -> "_Kept":
        return _Kept(self, name, self.local.state)

    def count(self, name: str, n: int) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + int(n)

    # -- set-up --------------------------------------------------------------
    def calibrate(self, n: int = 1000, repeats: int = 5) -> None:
        """What one span of each kind costs its parent: the time ``n`` spans
        around nothing take beyond the same code untraced and beyond the
        spans' own recorded durations (median of ``repeats``)."""
        def noop():
            return None

        traced = self.aggregated(noop, "skystore.calibrate")

        def aggregated_loop(on: bool) -> None:
            f = traced if on else noop
            for _ in range(n):
                f()

        def kept_loop(on: bool) -> None:
            for _ in range(n):
                with (self.kept("skystore.calibrate") if on else _NULL):
                    pass

        for loop, attr in ((aggregated_loop, "cost_agg"),
                           (kept_loop, "cost_kept")):
            costs = []
            for _ in range(repeats):
                t = perf()
                loop(False)
                untraced = perf() - t
                stats = self.local.state.stats
                stats.clear()
                t = perf()
                loop(True)
                elapsed = perf() - t
                inside = stats["skystore.calibrate"][1]
                costs.append(max(0.0, (elapsed - untraced - inside) / n))
            setattr(self, attr, sorted(costs)[repeats // 2])
        # A fresh state for every thread: the calibration leaves no trace.
        with self._lock:
            self._threads.clear()
        self.local = _Local(self)

    def patch(self) -> None:
        """Replace every method of :data:`SPANS` and the spine's batch
        stream by its timing wrapper; raises before replacing any if one is
        missing."""
        targets = []
        for module, cls_name, attr, name in SPANS + (SPINE + ("",),):
            mod = importlib.import_module(f"repro.core.{module}")
            cls = getattr(mod, cls_name, None)
            if cls is None or attr not in cls.__dict__:
                raise AttributeError(
                    f"tracing: span {name or 'skystore.spine.*'} is placed on "
                    f"repro.core.{module}.{cls_name}.{attr}, which does not "
                    f"exist")
            targets.append((cls, attr, name))
        for cls, attr, name in targets:
            raw = cls.__dict__[attr]
            fn = raw.__func__ if isinstance(raw, staticmethod) else raw
            new = self.batches(fn) if not name else self.aggregated(fn, name)
            setattr(cls, attr, staticmethod(new)
                    if isinstance(raw, staticmethod) else new)
            self._restore.append((cls, attr, raw))

    def unpatch(self) -> None:
        while self._restore:
            cls, attr, raw = self._restore.pop()
            setattr(cls, attr, raw)

    # -- the result ----------------------------------------------------------
    def snapshot(self) -> Snapshot:
        with self._lock:
            threads = list(self._threads)
            counters = dict(self.counters)
        spans: Dict[str, Aggregate] = {}
        kept: List[SpanRecord] = []
        n_all = 0
        for ts in threads:
            for name, (c, total, own) in list(ts.stats.items()):
                n_all += c
                a = spans.setdefault(name, Aggregate(0, 0.0, 0.0))
                a.count += c
                a.total_s += total
                a.self_s += own
            base = len(kept)
            for record in list(ts.kept):
                if record is None:  # still open when recording stopped
                    record = ("", self.t1, self.t1, float("nan"), -1)
                name, t0, t1, own, parent = record
                kept.append(SpanRecord(name, t0, t1, own,
                                       -1 if parent < 0 else base + parent,
                                       ts.number))
        return Snapshot(self.t0, self.t1, spans, kept, counters,
                        {"aggregated": self.cost_agg, "kept": self.cost_kept},
                        {"aggregated": n_all - len(kept), "kept": len(kept)})


class _Kept:
    """A kept span, entered once."""

    __slots__ = ("rec", "name", "ts", "index", "parent", "t0", "annotation")

    def __init__(self, rec: _Recorder, name: str, ts: _Thread) -> None:
        self.rec, self.name, self.ts = rec, name, ts
        self.annotation = None

    # The annotation opens right before the span's start and closes right
    # after its end, so the profile shows the same interval.
    def __enter__(self) -> "_Kept":
        ts = self.ts
        self.index = len(ts.kept)
        self.parent = ts.kept_idx[-1] if ts.kept_idx else -1
        ts.kept.append(None)
        ts.stack.append(0.0)
        ts.kept_at.append(len(ts.stack))
        ts.kept_idx.append(self.index)
        if self.rec.annotation is not None:
            self.annotation = self.rec.annotation(self.name)
            self.annotation.__enter__()
        self.t0 = perf()
        return self

    def __exit__(self, *exc) -> None:
        t1 = perf()
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        ts = self.ts
        ts.kept_at.pop()
        ts.kept_idx.pop()
        own = ts.close(self.name, t1 - self.t0, self.rec.cost_kept)
        ts.kept[self.index] = (self.name, self.t0, t1, own, self.parent)


# ---------------------------------------------------------------------------
# The module's interface
# ---------------------------------------------------------------------------

_active: Optional[_Recorder] = None
_NULL = contextlib.nullcontext()


def start(annotate: bool = False) -> None:
    """Start recording.  ``annotate`` also writes every kept span into a
    running ``jax.profiler`` trace."""
    global _active
    if _active is not None:
        raise RuntimeError("tracing: already recording")
    rec = _Recorder(annotate)
    rec.calibrate()
    rec.patch()
    rec.local.state          # the starting thread is thread 0
    rec.t0 = perf()
    _active = rec


def stop() -> Snapshot:
    """Stop recording, put every patched method back, return the spans."""
    global _active
    rec = _active
    if rec is None:
        raise RuntimeError("tracing: not recording")
    rec.t1 = perf()
    _active = None
    rec.unpatch()
    return rec.snapshot()


def recording() -> bool:
    return _active is not None


def span(name: str):
    """A kept span around a block; a shared null context when off."""
    rec = _active
    if rec is None:
        return _NULL
    return rec.kept(name)


def count(name: str, n: int) -> None:
    """Add ``n`` to counter ``name`` of the running recording, if any."""
    rec = _active
    if rec is not None:
        rec.count(name, n)
