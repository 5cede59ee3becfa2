"""Adaptive TTL selection (paper §3.2.2-§3.3): ExpectedCost(TTL) and its argmin.

Given one (bucket, target-region) histogram pair (``hist``, ``last``) and an
edge's prices (S = storage $/GB/month at the target, N = egress $/GB on the
edge), the expected cost of running a TTL-with-reset eviction policy is the
four-term functional of §3.2.2:

    ExpectedCost(TTL) =   first_read_remote_bytes * N                 (initial GETs)
                        + sum_{j: t(j) <= TTL} hist(j) * t_hat(j) * S (hits)
                        + sum_{j: t(j) >  TTL} hist(j) * (N + TTL*S)  (misses)
                        + sum_{j: t(j) >  TTL} last(j) * TTL * S      (tail storage)
                        [+ sum_{j: t(j) <= TTL} last(j) * age(j) * S  (censored)]

    ``last(j)`` is a census of bytes currently paused (no re-read yet), bucketed
    by pause age.  Bytes paused beyond TTL have, under this TTL, already been
    evicted after paying TTL*S -- the paper's term.  Bytes paused *less* than
    TTL are censored: they may still be re-read (and would then show up in
    ``hist``), but they are certainly being stored right now, so we charge them
    their observed age (the bracketed correction, on by default).  Without it,
    any TTL beyond the observation window zeroes the tail term and the argmin
    runs away to "never evict"; with it the curve converges to the observed
    always-store cost -- see tests/test_ttl_policy.py.

We evaluate it for every candidate TTL (the cell boundaries, plus TTL=0 ==
AlwaysEvict and TTL=inf == AlwaysStore-like) in O(cells) total using
prefix/suffix sums, and return the argmin.  The same computation, batched over
every (bucket x directed-edge) pair of the deployment, is the policy-plane hot
spot that :mod:`repro.kernels.ttl_scan` implements as a Pallas TPU kernel; the
numpy path here doubles as its oracle.

The latency extension of §3.3.2 (``U_perf-val`` $/byte willingness to pay per
extra cache hit) is :func:`choose_ttl_with_perf_value`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

from . import tracing
from .costmodel import GB, SECONDS_PER_MONTH, CostModel
from .histogram import AccessHistogram, RollingHistogram, cell_edges


def _per_byte_prices(storage_gb_month: float, egress_gb: float) -> Tuple[float, float]:
    """Convert catalog prices to ($ per byte-second, $ per byte)."""
    s = storage_gb_month / GB / SECONDS_PER_MONTH
    n = egress_gb / GB
    return s, n


def expected_cost_curve(
    h: AccessHistogram,
    storage_gb_month: float,
    egress_gb: float,
    include_censored_tail: bool = True,
) -> Tuple[np.ndarray, np.ndarray]:
    """ExpectedCost for every candidate TTL.

    Returns ``(candidate_ttls_seconds, cost_dollars)`` where candidates are
    ``[0, t(0), t(1), ..., t(J-1)]`` (TTL=0 prepended -- evict immediately).
    O(cells) total via prefix/suffix sums; mirrored by the Pallas kernel in
    :mod:`repro.kernels.ttl_scan`.
    """
    s, n = _per_byte_prices(storage_gb_month, egress_gb)
    edges, hist, t_hat, last = h.as_arrays()

    hit_cost_csum = np.concatenate([[0.0], np.cumsum(hist * t_hat)]) * s
    hist_csum = np.concatenate([[0.0], np.cumsum(hist)])
    last_csum = np.concatenate([[0.0], np.cumsum(last)])
    total_hist, total_last = hist_csum[-1], last_csum[-1]

    ttls = np.concatenate([[0.0], edges])                  # candidate k keeps cells < k
    miss_bytes = total_hist - hist_csum                    # bytes with t(j) > TTL_k
    tail_bytes = total_last - last_csum                    # paused longer than TTL_k

    cost = (
        h.first_read_remote_bytes * n
        + hit_cost_csum
        + miss_bytes * (n + ttls * s)
        + tail_bytes * ttls * s
    )
    if include_censored_tail:
        # Censored pauses (age <= TTL) are being stored right now: charge the
        # observed age (cell midpoint -- cells are <=2% wide by construction).
        lower = np.concatenate([[0.0], edges[:-1]])
        mid = 0.5 * (lower + edges)
        age_cost_csum = np.concatenate([[0.0], np.cumsum(last * mid)]) * s
        cost = cost + age_cost_csum
    return ttls, cost


def choose_ttl(
    h: AccessHistogram,
    storage_gb_month: float,
    egress_gb: float,
    **kw,
) -> float:
    """argmin_TTL ExpectedCost(TTL), in seconds."""
    ttls, cost = expected_cost_curve(h, storage_gb_month, egress_gb, **kw)
    return float(ttls[int(np.argmin(cost))])


def batched_cost_curves(
    hist: np.ndarray,          # [E, C] re-read bytes per cell
    time_w: np.ndarray,        # [E, C] sum of gap*bytes per cell
    last: np.ndarray,          # [E, C] paused-bytes census per cell
    edges: np.ndarray,         # [C]    shared cell layout
    first_remote: np.ndarray,  # [E]    initial-GET remote bytes
    s: np.ndarray,             # [E]    $ / byte-second at each target
    n: np.ndarray,             # [E]    $ / byte on each edge
    include_censored_tail: bool = True,
) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized float64 ExpectedCost surfaces for E edge problems sharing
    one cell layout: the batched form of :func:`expected_cost_curve`.

    Returns ``(candidate_ttls [C+1], cost [E, C+1])``.  Row ``i`` is
    bit-identical to ``expected_cost_curve`` on the same inputs:
    ``np.cumsum(..., axis=1)`` accumulates each row in the same sequential
    order as the 1-D scan, and every other term is elementwise -- so the
    batched argmin IS the per-edge argmin, not an approximation of it.  This
    is the production refresh path off-TPU; the float32 Pallas kernel
    (:mod:`repro.kernels.ttl_scan`) is the same computation on accelerator
    hardware, with this function as its exact oracle.
    """
    hist = np.asarray(hist, dtype=np.float64)
    time_w = np.asarray(time_w, dtype=np.float64)
    last = np.asarray(last, dtype=np.float64)
    edges = np.asarray(edges, dtype=np.float64)
    first_remote = np.asarray(first_remote, dtype=np.float64)
    s = np.asarray(s, dtype=np.float64)[:, None]
    n = np.asarray(n, dtype=np.float64)[:, None]

    lower = np.concatenate([[0.0], edges[:-1]])
    mid = 0.5 * (lower + edges)
    with np.errstate(invalid="ignore", divide="ignore"):
        t_hat = np.where(hist > 0, time_w / np.maximum(hist, 1e-30), mid)

    zcol = np.zeros((hist.shape[0], 1))
    hit_cost_csum = np.concatenate(
        [zcol, np.cumsum(hist * t_hat, axis=1)], axis=1) * s
    hist_csum = np.concatenate([zcol, np.cumsum(hist, axis=1)], axis=1)
    last_csum = np.concatenate([zcol, np.cumsum(last, axis=1)], axis=1)

    ttls = np.concatenate([[0.0], edges])
    miss_bytes = hist_csum[:, -1:] - hist_csum
    tail_bytes = last_csum[:, -1:] - last_csum

    cost = (
        first_remote[:, None] * n
        + hit_cost_csum
        + miss_bytes * (n + ttls[None, :] * s)
        + tail_bytes * ttls[None, :] * s
    )
    if include_censored_tail:
        age_cost_csum = np.concatenate(
            [zcol, np.cumsum(last * mid, axis=1)], axis=1) * s
        cost = cost + age_cost_csum
    return ttls, cost


def choose_ttl_with_perf_value(
    h: AccessHistogram,
    storage_gb_month: float,
    egress_gb: float,
    u_perf_val_per_gb: float,
    **kw,
) -> float:
    """§3.3.2: lift the TTL above the cost argmin while the *average* extra cost
    per extra locally-hit byte stays below the user performance value.

    Picks the highest TTL with
        (cost(TTL) - cost(TTL*)) / extra_hit_bytes(TTL*, TTL] <= U_perf-val.
    """
    ttls, cost = expected_cost_curve(h, storage_gb_month, egress_gb, **kw)
    k_star = int(np.argmin(cost))
    if u_perf_val_per_gb <= 0:
        return float(ttls[k_star])
    u = u_perf_val_per_gb / GB
    _, hist, _, _ = h.as_arrays()
    hist_csum = np.concatenate([[0.0], np.cumsum(hist)])
    extra_hits = hist_csum - hist_csum[k_star]             # bytes turned into hits
    with np.errstate(invalid="ignore", divide="ignore"):
        rate = (cost - cost[k_star]) / np.maximum(extra_hits, 1e-30)
    ok = np.arange(ttls.shape[0]) >= k_star
    ok &= (extra_hits > 0) | (np.arange(ttls.shape[0]) == k_star)
    ok &= (rate <= u) | (np.arange(ttls.shape[0]) == k_star)
    return float(ttls[np.nonzero(ok)[0].max()])


@dataclasses.dataclass
class EdgeTTL:
    """Chosen TTL for one directed edge of the region graph (Fig. 2)."""

    ttl_seconds: float
    chosen_at: float
    expected_cost: float = np.nan


#: TTL-selection engines the refresh loop can run on (see
#: :meth:`AdaptiveTTLController.resolve_engine`):
#:
#:   numpy   batched float64 :func:`batched_cost_curves` -- bit-identical to
#:           the per-edge scalar path, the off-TPU production default;
#:   kernel  the Pallas float32 kernel via
#:           :func:`repro.kernels.ops.ttl_scan_from_histograms` -- the
#:           production engine on TPU hosts;
#:   jax     the pure-jnp float32 oracle of the same batched path;
#:   python  the legacy per-edge scalar loop (kept as the reference the
#:           equivalence suite pins the batched engines against);
#:   auto    kernel on TPU, numpy everywhere else.
TTL_ENGINES = ("auto", "kernel", "jax", "numpy", "python")

#: When a (bucket, region) pair's incoming edges are re-solved:
#:
#:   access  lazily, by the first read of the pair after its
#:           ``refresh_period`` has run out (one refresh per pair);
#:   tick    at every maintenance tick, after the paused-bytes census: one
#:           sweep solves every pair past warm-up in one engine call.
REFRESH_SCHEDULES = ("access", "tick")

#: Pairs per batched float64 call of a numpy-engine sweep: bounds the
#: ``[pairs x edges, cells]`` temporaries without changing a result (every
#: row of :func:`batched_cost_curves` is computed on its own).
SWEEP_CHUNK_PAIRS = 256


def sweep_prices(cost: CostModel, dsts) -> Tuple[np.ndarray, np.ndarray]:
    """Per pair of a sweep, by its region ``dsts[i]``: the storage price
    there ($ per byte-second, ``[P]``) and the egress price of each
    incoming edge, sources in catalog order ($ per byte, ``[P, k]``)."""
    names = cost.region_names()
    by_dst = {dst: (cost.storage_price(dst) / GB / SECONDS_PER_MONTH,
                    [cost.egress_price(src, dst) / GB
                     for src in names if src != dst])
              for dst in dict.fromkeys(dsts)}
    return (np.asarray([by_dst[d][0] for d in dsts]),
            np.asarray([by_dst[d][1] for d in dsts]).reshape(len(dsts), -1))


class AdaptiveTTLController:
    """Per-(bucket, target region) statistics -> per-edge TTLs (§3.3.1).

    The histogram is collected at the *target* region per bucket (bucket-level
    granularity -- §3.2.3: object-level statistics are misleading under bursts);
    each incoming edge gets its own TTL because only N differs per edge.  The
    object-level TTL is then ``min`` over edges whose source currently holds a
    replica, with the eviction-safety filter applied by the placement layer.

    The refresh loop is *batched* (§6.7.3: 10 regions x 1000 buckets = 100k
    edge problems per cycle): all incoming edges of one (bucket, dst) pair are
    solved in a single call to the selected ``engine`` instead of one Python
    argmin per edge, and under ``refresh_schedule="tick"`` every warm pair of
    the deployment in one call (:meth:`sweep`).  TTLs are always resolved by
    argmin *index* against the float64 candidate grid, so engine choice never
    leaks float32 TTL values into the planes.
    """

    def __init__(
        self,
        cost: CostModel,
        refresh_period: float = 24 * 3600.0,
        warmup_min_samples: int = 32,
        u_perf_val_per_gb: float = 0.0,
        edges: Optional[np.ndarray] = None,
        rotate_multiple_of_t_even: float = 2.0,
        engine: str = "auto",
        refresh_schedule: str = "access",
    ) -> None:
        self.cost = cost
        self.refresh_period = refresh_period
        self.warmup_min_samples = warmup_min_samples
        self.u_perf_val_per_gb = u_perf_val_per_gb
        self._cell_edges = cell_edges() if edges is None else edges
        self.hists: Dict[Tuple[str, str], RollingHistogram] = {}
        self.edge_ttls: Dict[Tuple[str, str, str], EdgeTTL] = {}
        self.last_refresh: Dict[Tuple[str, str], float] = {}
        # (bucket, dst) -> (last_refresh stamp, {src: ttl}): edge TTLs only
        # move inside _maybe_refresh or sweep, so a whole destination's
        # incoming-edge table can be served from cache between refreshes
        # (see edge_ttl_table).
        self._ttl_tables: Dict[Tuple[str, str],
                               Tuple[Optional[float], Dict[str, float]]] = {}
        self.rotate_multiple = rotate_multiple_of_t_even
        if engine not in TTL_ENGINES:
            raise ValueError(f"unknown TTL engine {engine!r}; have {TTL_ENGINES}")
        if refresh_schedule not in REFRESH_SCHEDULES:
            raise ValueError(f"unknown refresh schedule {refresh_schedule!r}; "
                             f"have {REFRESH_SCHEDULES}")
        self.engine = engine
        self.refresh_schedule = refresh_schedule
        self._lazy = refresh_schedule == "access"
        self._engine_resolved: Optional[str] = None
        #: Refreshes that solved edge TTLs (past warmup), all on the one
        #: engine :meth:`resolve_engine` pins.
        self.n_refreshes = 0
        #: Refreshes and sweeps the kernel or jax engine solved on the device
        #: (a refresh in one program, a sweep in one per 2**14 edge rows),
        #: and the ones whose call compiled a program.
        self.n_device_scans = 0
        self.n_scan_compiles = 0
        #: Sweeps that solved at least one pair, the pairs they solved, and
        #: those pairs' edge rows (before any padding).
        self.n_sweeps = 0
        self.n_sweep_pairs = 0
        self.n_sweep_rows = 0
        #: Edge rows the kernel or jax engine left to the host's float64
        #: re-solve: their runner-up lay within ``REFINE_BAND`` of the
        #: minimum.  The device decides every other row.
        self.n_near_tie_rows = 0

    # -- statistics ingestion ------------------------------------------------
    def hist_for(self, bucket: str, region: str) -> RollingHistogram:
        key = (bucket, region)
        if key not in self.hists:
            self.hists[key] = RollingHistogram(self._cell_edges)
        return self.hists[key]

    def record_gap(self, bucket: str, region: str, dt: float, size: float) -> None:
        # Queued, not applied: the per-sample numpy machinery is the live
        # plane's ingestion hot spot.  RollingHistogram flushes the queue in
        # one vectorized (bit-identical) add_gaps before any estimation read.
        self.hist_for(bucket, region).queue_gap(float(dt), float(size))

    def record_gaps(self, bucket: str, region: str, dts, sizes) -> None:
        """Chunk-bulk form of :meth:`record_gap` for offline producers.

        NOT used by the replay hot path -- see
        :meth:`RollingHistogram.queue_gaps` for why chunk-deferred ingestion
        is decision-unsafe when estimation reads can interleave mid-chunk."""
        self.hist_for(bucket, region).queue_gaps(dts, sizes)

    def record_first_read(self, bucket: str, region: str, size: float, remote: bool) -> None:
        self.hist_for(bucket, region).current.add_first_read(size, remote)

    def set_last_snapshot(
        self, bucket: str, region: str, ages: np.ndarray, sizes: np.ndarray
    ) -> None:
        h = self.hist_for(bucket, region).current
        h.last[:] = 0.0
        if len(ages):
            h.add_last(ages, sizes)

    # -- TTL queries ----------------------------------------------------------
    def edge_ttl(self, bucket: str, src: str, dst: str, now: float) -> float:
        """TTL for the (src -> dst) edge; T_even warmup before enough samples."""
        if self._lazy:
            self._maybe_refresh(bucket, dst, now)
        e = self.edge_ttls.get((bucket, src, dst))
        if e is None:
            return self.cost.t_even_seconds(src, dst)
        return e.ttl_seconds

    def edge_ttl_table(self, bucket: str, dst: str, now: float) -> Dict[str, float]:
        """Every incoming edge's TTL for ``(bucket, dst)`` at ``now`` as one
        dict ``{src: ttl}`` -- each value exactly what ``edge_ttl(bucket,
        src, dst, now)`` would return, amortized across the per-GET callers.

        Edge TTLs only change inside :meth:`_maybe_refresh` (refresh or
        rotate), which is gated on ``refresh_period``, or inside
        :meth:`sweep`; between refreshes the table is constant, so it is
        cached against the ``last_refresh`` stamp (and, on the ``access``
        schedule, the same period gate the scalar path applies).  This keeps
        refresh *timing* identical to per-edge ``edge_ttl`` calls: the first
        read past the period boundary triggers the refresh either way.  On
        the ``tick`` schedule a read never refreshes: it gets the last
        sweep's table, T_even on every edge before the pair's first solve."""
        key = (bucket, dst)
        cached = self._ttl_tables.get(key)
        if cached is not None:
            last, tbl = cached
            if self.last_refresh.get(key) == last and (
                    not self._lazy or now - last < self.refresh_period):
                return tbl
        if self._lazy:
            self._maybe_refresh(bucket, dst, now)
        edge_ttls, t_even = self.edge_ttls, self.cost.t_even_seconds
        tbl = {}
        for src in self.cost.regions:
            if src == dst:
                continue
            e = edge_ttls.get((bucket, src, dst))
            tbl[src] = t_even(src, dst) if e is None else e.ttl_seconds
        self._ttl_tables[key] = (self.last_refresh.get(key), tbl)
        return tbl

    def object_ttl(
        self, bucket: str, dst: str, holder_regions, now: float
    ) -> float:
        """min over edges from replica-holding regions (§3.3.1)."""
        ttls = [
            self.edge_ttl(bucket, src, dst, now)
            for src in holder_regions
            if src != dst
        ]
        if not ttls:
            return np.inf
        return float(min(ttls))

    # -- refresh loop ----------------------------------------------------------
    def resolve_engine(self) -> str:
        """Pin the ``auto`` engine choice once per controller: the Pallas
        kernel on TPU hosts, the batched float64 numpy path everywhere else
        (per-refresh jit dispatch overhead dwarfs the arithmetic at replay
        edge counts, and float64 keeps decisions bit-identical to the
        scalar reference)."""
        if self._engine_resolved is None:
            eng = self.engine
            if eng == "auto":
                import jax
                eng = "kernel" if jax.default_backend() == "tpu" else "numpy"
            self._engine_resolved = eng
        return self._engine_resolved

    def _scalar(self, engine: str) -> bool:
        """Whether edges are solved one by one: the §3.3.2 perf-value lift
        walks the per-edge curve beyond the argmin, so it stays on the
        scalar implementation; engine="python" keeps the legacy loop
        selectable as the equivalence oracle."""
        return self.u_perf_val_per_gb > 0 or engine == "python"

    def _srcs(self, dst: str) -> list:
        return [src for src in self.cost.region_names() if src != dst]

    def _maybe_refresh(self, bucket: str, dst: str, now: float) -> None:
        key = (bucket, dst)
        last = self.last_refresh.get(key, -np.inf)
        if now - last < self.refresh_period:
            return
        self.last_refresh[key] = now
        # One refresh span per period; one that stops at the warm-up gate
        # holds only its merge, one that solves also a scan.
        with tracing.span("skystore.ttl.refresh"):
            self._refresh(bucket, dst, now)

    def _refresh(self, bucket: str, dst: str, now: float) -> None:
        roll = self.hist_for(bucket, dst)
        with tracing.span("skystore.ttl.merge"):
            merged = roll.merged()
        if merged.n_samples < self.warmup_min_samples:
            return
        srcs = self._srcs(dst)
        engine = self.resolve_engine()
        self.n_refreshes += 1
        if self._scalar(engine):
            with tracing.span("skystore.ttl.scan"):
                self._solve_scalar(bucket, dst, merged, now)
        else:
            ttls, costs = self._refresh_batched(merged, dst, srcs, engine)
            for src, ttl, c in zip(srcs, ttls, costs):
                self.edge_ttls[(bucket, src, dst)] = EdgeTTL(
                    float(ttl), now, float(c)
                )
        self._rotate(roll, dst, now)

    def _solve_scalar(self, bucket: str, dst: str, merged: AccessHistogram,
                      now: float) -> None:
        """Every incoming edge of (bucket, dst), one scalar argmin each."""
        s = self.cost.storage_price(dst)
        for src in self._srcs(dst):
            n = self.cost.egress_price(src, dst)
            if self.u_perf_val_per_gb > 0:
                ttl = choose_ttl_with_perf_value(
                    merged, s, n, self.u_perf_val_per_gb)
            else:
                ttl = choose_ttl(merged, s, n)
            _ttls_c, cost_c = expected_cost_curve(merged, s, n)
            self.edge_ttls[(bucket, src, dst)] = EdgeTTL(
                ttl, now, float(cost_c.min())
            )

    def _rotate(self, roll: RollingHistogram, dst: str, now: float) -> None:
        """Rotate the collection window once it is comfortably longer than
        the largest T_even of any incoming edge (§3.2.3 guidance)."""
        t_even_max = max(
            self.cost.t_even_seconds(src, dst)
            for src in self.cost.region_names()
            if src != dst
        )
        if now - roll.window_start > self.rotate_multiple * t_even_max:
            roll.rotate(now)

    def _refresh_batched(
        self, merged: AccessHistogram, dst: str, srcs: list, engine: str,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Solve every incoming edge of (bucket, dst) in one batched call.

        All rows share the merged target-side histogram; only the per-edge
        egress price N varies.  Returns ``(ttl_seconds [E], best_cost [E])``
        with TTLs off the float64 candidate grid on every engine.
        """
        if engine == "numpy":
            # A refresh is a one-pair sweep (every row of
            # batched_cost_curves is computed on its own).
            ttls, costs = self._sweep_batched([merged], [dst], engine)
            return ttls[0], costs[0]
        # kernel / jax: the float32 batched scan decided on the device, with
        # near ties resolved in float64 on the host (repro.kernels.ops).
        from repro.kernels import ops
        programs, near = ops.ttl_scan_programs(), ops.near_tie_rows()
        ttls, costs = ops.ttl_scan_from_histograms(
            [merged] * len(srcs), self.cost,
            [(src, dst) for src in srcs], engine=engine)
        self.n_device_scans += 1
        self.n_scan_compiles += ops.ttl_scan_programs() - programs
        self.n_near_tie_rows += ops.near_tie_rows() - near
        return np.asarray(ttls), np.asarray(costs)

    # -- the maintenance tick's sweep ---------------------------------------------
    def sweep(self, now: float) -> int:
        """Re-solve every pair past warm-up (the ``tick`` schedule), after
        the maintenance tick's paused-bytes census.

        Each (bucket, region) pair's rolling histogram is merged; pairs with
        fewer than ``warmup_min_samples`` samples are skipped.  Every
        incoming edge of every other pair is solved in one engine call (the
        kernel and jax engines: one device program; numpy: batched float64;
        python and the §3.3.2 perf-value lift: the scalar loop), each solved
        pair's ``last_refresh`` becomes ``now`` and its window rotates as a
        refresh's would.  Returns the number of pairs solved."""
        with tracing.span("skystore.ttl.sweep"):
            with tracing.span("skystore.ttl.merge"):
                warm = []
                for key, roll in self.hists.items():
                    merged = roll.merged()
                    if merged.n_samples >= self.warmup_min_samples:
                        warm.append((key, roll, merged))
            if not warm:
                return 0
            engine = self.resolve_engine()
            if self._scalar(engine):
                with tracing.span("skystore.ttl.scan"):
                    for (bucket, dst), _roll, merged in warm:
                        self._solve_scalar(bucket, dst, merged, now)
            else:
                ttls, costs = self._sweep_batched(
                    [m for _k, _r, m in warm], [k[1] for k, _r, _m in warm],
                    engine)
                edge_ttls = self.edge_ttls
                for ((bucket, dst), _roll, _m), row_t, row_c in zip(
                        warm, ttls.tolist(), costs.tolist()):
                    for src, ttl, c in zip(self._srcs(dst), row_t, row_c):
                        edge_ttls[(bucket, src, dst)] = EdgeTTL(ttl, now, c)
            for key, roll, _merged in warm:
                self.last_refresh[key] = now
                self._rotate(roll, key[1], now)
            self.n_sweeps += 1
            self.n_sweep_pairs += len(warm)
            self.n_sweep_rows += len(warm) * (len(self.cost.region_names()) - 1)
            return len(warm)

    def _sweep_batched(self, merged: list, dsts: list, engine: str
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """Every incoming edge of the pairs ``(merged[i], dsts[i])``:
        ``(ttl_seconds [P, k], best_cost [P, k])``, sources in catalog order,
        TTLs off the float64 candidate grid on every engine."""
        with tracing.span("skystore.ttl.inputs"):
            rows = np.asarray([(m.hist, m.time_weight, m.last) for m in merged])
            first = np.asarray([m.first_read_remote_bytes for m in merged])
            s, n = sweep_prices(self.cost, dsts)
        if engine != "numpy":
            from repro.kernels import ops
            programs, near = ops.ttl_scan_programs(), ops.near_tie_rows()
            out = ops.ttl_sweep(rows, first, s, n, merged[0].edges,
                                engine=engine)
            self.n_device_scans += 1
            self.n_scan_compiles += ops.ttl_scan_programs() - programs
            self.n_near_tie_rows += ops.near_tie_rows() - near
            return out
        k = n.shape[1]
        ttl_out, cost_out = np.empty((2, len(merged), k))
        for lo in range(0, len(merged), SWEEP_CHUNK_PAIRS):
            hi = min(lo + SWEEP_CHUNK_PAIRS, len(merged))
            with tracing.span("skystore.ttl.scan"):
                ttls, cost = batched_cost_curves(
                    *np.repeat(rows[lo:hi], k, axis=0).transpose(1, 0, 2),
                    merged[0].edges, np.repeat(first[lo:hi], k),
                    np.repeat(s[lo:hi], k), n[lo:hi].ravel())
            with tracing.span("skystore.ttl.resolve"):
                idx = np.argmin(cost, axis=1)
                ttl_out[lo:hi] = ttls[idx].reshape(-1, k)
                cost_out[lo:hi] = cost[np.arange(idx.shape[0]), idx
                                       ].reshape(-1, k)
        return ttl_out, cost_out
