"""Public wrappers around the Pallas kernels.

Each op accepts natural shapes/dtypes, handles padding + layout, calls the
compiled kernel, and exposes the pure-jnp oracle via ``use_kernel=False``.
Off-TPU the kernel runs only where the caller asks for the Pallas
interpreter with ``interpret=True`` (the CPU tests do).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing

from . import ref
from .flash_attention import flash_attention_bhsd
from .ttl_scan import BLOCK_E, ttl_cost_surface


# ---------------------------------------------------------------------------
# TTL expected-cost scan
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("n_rows", "use_kernel", "interpret"))
def ttl_refresh_surface(packed: jax.Array, edges: jax.Array, n_rows: int,
                        use_kernel: bool = True, interpret: bool = False):
    """One refresh or one sweep as one device program: the ``[E, C+1]``
    float32 surface.

    ``packed`` is the float32 buffer :func:`pack_ttl_inputs` builds: ``R``
    rows of (re-read bytes, gap-weighted bytes, paused bytes) over the ``C``
    cells of ``edges``, then the ``E`` first-read bytes, storage prices and
    egress prices.  ``R`` (``n_rows``) divides ``E``: row ``i`` serves
    edges ``i*k .. i*k+k-1`` with ``k = E / R``, so ``R = E`` is a row per
    edge, ``R = 1`` one histogram shared by every edge (a refresh), and
    anything between each pair's histogram shared by its incoming edges (a
    sweep).  Shared rows are broadcast here, on the device.  Column 0 is
    candidate TTL=0 (evict at once: every re-read pays N, no storage),
    column j+1 candidate TTL=edges[j]."""
    c_dim = edges.shape[0]
    split = n_rows * 3 * c_dim
    e_dim = (packed.shape[0] - split) // 3
    if e_dim % n_rows:
        raise ValueError(f"{n_rows} histogram rows cannot serve {e_dim} edges")
    k = e_dim // n_rows
    rows = jnp.broadcast_to(packed[:split].reshape(n_rows, 1, 3, c_dim),
                            (n_rows, k, 3, c_dim)).reshape(e_dim, 3, c_dim)
    hist, time_w, last = rows[:, 0], rows[:, 1], rows[:, 2]
    first, s_price, n_price = packed[split:].reshape(3, e_dim)
    if use_kernel:
        surface = ttl_cost_surface(hist, time_w, last, edges, s_price,
                                   n_price, first, interpret=interpret)
    else:
        surface = ref.ttl_cost_ref(hist, time_w, last, edges, s_price,
                                   n_price, first)
    zero = (first + hist.sum(axis=1)) * n_price
    return jnp.concatenate([zero[:, None], surface], axis=1)


def ttl_scan_programs() -> int:
    """How many programs :func:`ttl_refresh_surface` has compiled in this
    process: one per (E, C, rows, engine)."""
    return ttl_refresh_surface._cache_size()


def device_edges(edges) -> jax.Array:
    """``edges`` as a float32 device array, transferred once per layout."""
    edges = np.asarray(edges)
    return _device_edges(edges.tobytes(), edges.dtype.str)


@functools.lru_cache(maxsize=16)
def _device_edges(raw: bytes, dtype: str) -> jax.Array:
    return jax.device_put(np.frombuffer(raw, dtype).astype(np.float32))


def pack_ttl_inputs(rows, first_remote, s_price, n_price) -> np.ndarray:
    """``rows`` ``[R, 3, C]`` and the three ``[E]`` per-edge values as the
    one float32 host buffer :func:`ttl_refresh_surface` reads."""
    return np.concatenate(
        [np.ravel(rows), np.ravel(first_remote), np.ravel(s_price),
         np.ravel(n_price)], dtype=np.float32, casting="same_kind")


def ttl_scan(
    hist, time_w, last, edges, s_price, n_price, first_remote,
    use_kernel: bool = True,
    interpret: bool = False,
):
    """Batched TTL selection over E directed edges.

    Returns ``(best_ttl [E], best_cost [E], cost_surface [E, C+1])`` where
    candidate 0 is TTL=0 (evict immediately) and candidate j+1 is
    TTL=edges[j].  All inputs may be numpy or jax arrays.  The surface is
    :func:`ttl_refresh_surface`'s; the float32 argmin is taken on it
    afterwards (the refresh loop decides with :func:`canonical_argmin`).
    """
    edges = device_edges(edges)
    rows = np.stack([np.asarray(x) for x in (hist, time_w, last)], axis=1)
    full = ttl_refresh_surface(
        pack_ttl_inputs(rows, first_remote, s_price, n_price), edges,
        n_rows=rows.shape[0], use_kernel=use_kernel, interpret=interpret)
    idx = jnp.argmin(full, axis=1)
    ttls = jnp.concatenate([jnp.zeros_like(edges[:1]), edges])
    return ttls[idx], jnp.take_along_axis(full, idx[:, None], 1)[:, 0], full


#: Float32 candidates closer than this (relative) to a row's minimum are
#: resolved in float64.  Every term of the cost surface is a sum of
#: non-negative values, so either float32 engine sits within about 1e-6 of
#: the float64 cost on every candidate; candidates closer than twice that
#: cannot be ordered in float32, and real replay histograms hold such near
#: ties.  2**-16 leaves an order of magnitude of margin.
REFINE_BAND = 2.0 ** -16


def canonical_argmin(surface: np.ndarray, hist: np.ndarray,
                     last: np.ndarray, exact) -> np.ndarray:
    """The float64 argmin of each row, decided from a float32 surface.

    This is the decision rule both float32 engines share, so that the chosen
    *index* -- and therefore the float64 candidate TTL it maps to -- is the
    one the pure-float64 ``choose_ttl`` argmin picks.  ``hist`` and ``last``
    hold one row per surface row, or one row per ``k`` consecutive surface
    rows (a pair's histogram, shared by its incoming edges).  Two steps:

    * Plateau.  Past the last cell holding re-read (``hist``) or paused
      (``last``) bytes nothing misses and nothing is paused, so in float64
      every later candidate costs exactly what the plateau start costs and
      the argmin never lies beyond it.  The float32 scan sums the same bytes
      in a different grouping on every lane, so its plateau wobbles by a few
      ulps; the surface is cut at the plateau start.
    * Near ties.  A row whose cut surface has more than one candidate within
      :data:`REFINE_BAND` of its minimum takes the argmin of ``exact(rows)``,
      the float64 surfaces of those rows.  Other rows take the float32
      argmin, which the band proves is the float64 one.
    """
    surf = np.asarray(surface, dtype=np.float64)
    held = (np.asarray(hist) > 0) | (np.asarray(last) > 0)        # [R, C]
    c_dim = held.shape[1]
    last_cell = np.where(held.any(axis=1),
                         c_dim - 1 - np.argmax(held[:, ::-1], axis=1), -1)
    last_cell = np.repeat(last_cell, surf.shape[0] // held.shape[0])
    beyond = np.arange(surf.shape[1])[None, :] > last_cell[:, None] + 1
    surf = np.where(beyond, np.inf, surf)
    mn = surf.min(axis=1, keepdims=True)
    idx = np.argmin(surf, axis=1)
    rows = np.flatnonzero((surf <= mn * (1.0 + REFINE_BAND)).sum(axis=1) > 1)
    if rows.size:
        idx[rows] = np.argmin(exact(rows), axis=1)
    return idx


def _solve(problems, edges, engine: str, interpret: bool):
    """Device surfaces and canonical argmins of one or more problems.

    Each problem is ``(rows, first, s, n, n_edges)``: ``rows`` ``[R, 3, C]``
    float64 (re-read, gap-weighted and paused bytes) serve the ``[E]``
    per-edge ``first``, ``s``, ``n`` as :func:`ttl_refresh_surface` lays
    them out, and edges past ``n_edges`` (with their rows) are padding,
    copied back and dropped before the decision.  Every program is started
    before the first copy back.  Returns ``(best_ttl, best_cost, surface)``
    of each problem's first ``n_edges`` edges, in float64."""
    from repro.core.ttl_policy import batched_cost_curves

    if engine not in ("kernel", "jax"):
        raise ValueError(f"unknown ttl_scan engine {engine!r}")
    # One float32 buffer in, one device program, one copy back, a problem.
    with tracing.span("skystore.ttl.scan"):
        out = [ttl_refresh_surface(
                   pack_ttl_inputs(rows, first, s, n), device_edges(edges),
                   n_rows=rows.shape[0], use_kernel=(engine == "kernel"),
                   interpret=interpret)
               for rows, first, s, n, _ in problems]
        surfaces = [np.asarray(surface)[:p[4]].astype(np.float64)
                    for surface, p in zip(out, problems)]
    candidates = np.concatenate([[0.0], np.asarray(edges, dtype=np.float64)])
    results = []
    with tracing.span("skystore.ttl.resolve"):
        for (rows, first, s, n, n_edges), surface in zip(problems, surfaces):
            per_row = first.shape[0] // rows.shape[0]

            def exact(near):
                # Near-tie rows are resolved, and reported, in float64.
                r = rows[near // per_row]
                _, cost = batched_cost_curves(r[:, 0], r[:, 1], r[:, 2],
                                              edges, first[near], s[near],
                                              n[near])
                surface[near] = cost
                return cost

            held = rows[:-(-n_edges // per_row)]
            idx = canonical_argmin(surface, held[:, 0], held[:, 2], exact)
            results.append((candidates[idx],
                            surface[np.arange(n_edges), idx], surface))
    return results


def ttl_scan_from_histograms(
    histograms, cost_model, targets,
    use_kernel: bool = True,
    engine: str | None = None,
    interpret: bool = False,
):
    """Batched TTL selection for problems built from
    :class:`repro.core.histogram.AccessHistogram` objects.

    ``histograms`` -- list of AccessHistogram (one per problem, target-side);
    ``targets``    -- list of (src_region, dst_region) edges aligned with it;
    ``engine``     -- "kernel" (Pallas) or "jax" (jnp oracle); defaults from
                      ``use_kernel`` for backward compatibility.

    Returns ``(best_ttl [E], best_cost [E], cost_surface [E, C+1])`` as
    float64 numpy arrays.  TTLs are resolved by canonical argmin *index*
    against the float64 candidate grid ``[0, edges...]``, so the returned TTL
    values are exact candidate boundaries, never float32 roundings of them.

    Raises ``ValueError`` if the histograms do not share one cell layout
    (mirroring :meth:`AccessHistogram.merge`): a silent mismatch would price
    every row against the wrong cell boundaries.
    """
    from repro.core.costmodel import GB, SECONDS_PER_MONTH

    if engine is None:
        engine = "kernel" if use_kernel else "jax"
    edges = histograms[0].edges
    for h in histograms[1:]:
        if h.edges is not edges and (h.edges.shape != edges.shape
                                     or not np.allclose(h.edges, edges)):
            raise ValueError("histograms with different cell layouts")
    # The refresh loop passes one merged histogram for every edge: its rows
    # then go to the device once and are broadcast there.
    shared = all(h is histograms[0] for h in histograms)
    with tracing.span("skystore.ttl.inputs"):
        rows = np.stack([(h.hist, h.time_weight, h.last)
                         for h in histograms[:1 if shared else None]])
        first = np.asarray([h.first_read_remote_bytes for h in histograms])
        s = np.asarray([
            cost_model.storage_price(dst) / GB / SECONDS_PER_MONTH
            for (_src, dst) in targets
        ])
        n = np.asarray([
            cost_model.egress_price(src, dst) / GB for (src, dst) in targets
        ])
    [out] = _solve([(rows, first, s, n, len(histograms))], edges, engine,
                   interpret)
    return out


#: Edge rows one sweep program holds at most.  At 2**15 rows the v5e
#: compiler places the kernel's per-edge price vectors in scoped VMEM and
#: runs out of its 16 MiB; 2**14 rows compile.  Larger sweeps are split.
SWEEP_MAX_EDGES = 1 << 14


def sweep_rows(n_pairs: int, k: int) -> int:
    """The histogram rows one sweep program of ``n_pairs`` pairs of ``k``
    edges each is padded to: as many pairs as fit in the smallest power of
    two of edges, at least ``BLOCK_E``, that holds them all.  Sweeps of
    every size then share one program per power of two."""
    e_pad = max(BLOCK_E, 1 << (n_pairs * k - 1).bit_length())
    return e_pad // k


def sweep_chunks(n_pairs: int, k: int) -> list:
    """``(lo, hi)`` pair ranges of a sweep's programs: as many pairs as
    :data:`SWEEP_MAX_EDGES` holds, then the rest."""
    per = SWEEP_MAX_EDGES // k
    return [(lo, min(lo + per, n_pairs)) for lo in range(0, n_pairs, per)]


def sweep_programs(max_pairs: int, k: int) -> list:
    """The padded row counts of every program that sweeps of 1 to
    ``max_pairs`` pairs of ``k`` edges run, smallest first."""
    top = sweep_rows(min(max_pairs, SWEEP_MAX_EDGES // k), k)
    sizes = [sweep_rows(1, k)]
    while sizes[-1] < top:
        sizes.append(sweep_rows(sizes[-1] + 1, k))
    return sizes


def ttl_sweep(rows, first, s, n, edges, engine: str = "kernel",
              interpret: bool = False):
    """Every incoming edge of ``P`` (bucket, region) pairs, solved on the
    device: the maintenance tick's sweep.

    ``rows`` ``[P, 3, C]`` holds pair ``i``'s merged histogram (re-read,
    gap-weighted and paused bytes over the cells ``edges``), ``first``
    ``[P]`` its first-read bytes and ``s`` ``[P]`` the storage price at its
    region; ``n`` ``[P, k]`` the egress price of each of its ``k`` incoming
    edges.  The pairs go to the device in one program per
    :func:`sweep_chunks` range, each padded with zero rows to
    :func:`sweep_rows` (padded edges have zero bytes and zero prices); the
    padding is dropped before the decision, which is
    :func:`canonical_argmin`'s, as for one refresh.

    Returns ``(best_ttl [P, k], best_cost [P, k])`` as float64 arrays."""
    n_pairs, k = n.shape
    problems = []
    with tracing.span("skystore.ttl.inputs"):
        for lo, hi in sweep_chunks(n_pairs, k):
            p, r_pad = hi - lo, sweep_rows(hi - lo, k)
            chunk = np.zeros((r_pad, 3, rows.shape[2]))
            chunk[:p] = rows[lo:hi]
            per_edge = np.zeros((3, r_pad, k))
            per_edge[0, :p] = first[lo:hi, None]
            per_edge[1, :p] = s[lo:hi, None]
            per_edge[2, :p] = n[lo:hi]
            problems.append((chunk, *per_edge.reshape(3, -1), p * k))
    out = _solve(problems, edges, engine, interpret)
    ttls = np.concatenate([t for t, _c, _s in out])
    costs = np.concatenate([c for _t, c, _s in out])
    return ttls.reshape(-1, k), costs.reshape(-1, k)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def flash_attention(
    q: jax.Array,            # [B, Hq, Sq, D]
    k: jax.Array,            # [B, Hkv, Skv, D]
    v: jax.Array,            # [B, Hkv, Skv, D]
    causal: bool = True,
    q_offset: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    use_kernel: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """GQA-aware fused attention: repeats kv heads to q heads, folds (B, H)
    into the kernel batch, unpads on the way out."""
    if not use_kernel:
        b, hq, sq, d = q.shape
        hkv = k.shape[1]
        k_ = jnp.repeat(k, hq // hkv, axis=1)
        v_ = jnp.repeat(v, hq // hkv, axis=1)
        return ref.mha_ref(q, k_, v_, causal=causal, q_offset=q_offset)

    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    fold = lambda x: x.reshape(b * hq, x.shape[2], d)
    out = flash_attention_bhsd(
        fold(q), fold(k), fold(v),
        causal=causal, q_offset=q_offset,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
    )
    return out.reshape(b, hq, sq, d)


def rwkv6_scan(r, k, v, w, u, state=None):
    """RWKV6 recurrence; pure-jnp implementation (jax.lax.scan) -- the
    recurrence is bandwidth-bound and already maps well onto the VPU via
    scan, so no hand kernel is warranted."""
    return ref.rwkv6_ref(r, k, v, w, u, state)
