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
    """The ``[E, C+1]`` float32 surface of one refresh or one sweep: the
    body of the program the refresh loop runs (:func:`ttl_refresh_decision`)
    and, called alone, one program of its own for :func:`ttl_scan`.

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


def ttl_row_decision(surface: jax.Array, last_cell: jax.Array) -> jax.Array:
    """What the host's float64 decision needs of each row of a ``[E, C+1]``
    surface, as one float32 ``[3, E]`` array: the first-index argmin
    ``idx`` of the row cut at its plateau, its value ``mn``, and
    ``second``, the least value of the cut row with ``idx`` masked out (so
    an exact tie gives ``second == mn``).

    The plateau: past the last cell holding re-read or paused bytes
    (``last_cell`` ``[E]``, -1 for a row holding none) nothing misses and
    nothing is paused, so in float64 every later candidate costs exactly
    what the plateau start (candidate ``last_cell + 1``) costs, and the
    argmin never lies beyond it.  The float32 surface sums the same bytes
    in a different grouping on every lane, so its plateau wobbles by a few
    ulps; candidates past the plateau start are cut (``+inf``).  Candidate
    0, TTL=0, is never cut."""
    cols = jnp.arange(surface.shape[1], dtype=surface.dtype)
    cut = jnp.where(cols > last_cell[:, None] + 1, jnp.inf, surface)
    idx = jnp.argmin(cut, axis=1)
    mn = jnp.min(cut, axis=1)
    second = jnp.min(jnp.where(cols == idx[:, None], jnp.inf, cut), axis=1)
    return jnp.stack([idx.astype(surface.dtype), mn, second])


@functools.partial(jax.jit,
                   static_argnames=("n_rows", "use_kernel", "interpret"))
def ttl_refresh_decision(packed: jax.Array, edges: jax.Array, n_rows: int,
                         use_kernel: bool = True, interpret: bool = False):
    """One refresh or one sweep decided on the device, as one program: the
    float32 ``[3, E]`` :func:`ttl_row_decision` of
    :func:`ttl_refresh_surface`'s surface.

    ``packed`` is :func:`ttl_refresh_surface`'s buffer followed by the
    ``R`` (``n_rows``) rows' last held cells (:func:`last_held_cells`),
    each serving the same edges as its row."""
    split = packed.shape[0] - n_rows
    surface = ttl_refresh_surface(packed[:split], edges, n_rows=n_rows,
                                  use_kernel=use_kernel, interpret=interpret)
    last_cell = jnp.repeat(packed[split:], surface.shape[0] // n_rows)
    return ttl_row_decision(surface, last_cell)


def ttl_scan_programs() -> int:
    """How many programs :func:`ttl_refresh_decision`, the program the
    refresh loop and the sweep run, has compiled in this process: one per
    (E, C, rows, engine)."""
    return ttl_refresh_decision._cache_size()


def near_tie_rows() -> int:
    """How many edge rows the device engines have decided in float64 on
    the host in this process: the rows whose runner-up lay within
    :data:`REFINE_BAND` of their minimum."""
    return _near_tie_rows


def device_edges(edges) -> jax.Array:
    """``edges`` as a float32 device array, transferred once per layout."""
    edges = np.asarray(edges)
    return _device_edges(edges.tobytes(), edges.dtype.str)


@functools.lru_cache(maxsize=16)
def _device_edges(raw: bytes, dtype: str) -> jax.Array:
    return jax.device_put(np.frombuffer(raw, dtype).astype(np.float32))


def pack_ttl_inputs(rows, first_remote, s_price, n_price, *tail
                    ) -> np.ndarray:
    """``rows`` ``[R, 3, C]`` and the three ``[E]`` per-edge values as the
    one float32 host buffer :func:`ttl_refresh_surface` reads, then any
    ``tail`` arrays (:func:`ttl_refresh_decision` reads the ``[R]`` last
    held cells there)."""
    return np.concatenate(
        [np.ravel(rows), np.ravel(first_remote), np.ravel(s_price),
         np.ravel(n_price), *map(np.ravel, tail)], dtype=np.float32,
        casting="same_kind")


def last_held_cells(rows) -> np.ndarray:
    """The last cell of each of ``rows`` ``[R, 3, C]`` that holds re-read
    or paused bytes, -1 where none does: where each row's plateau starts,
    one candidate later (:func:`ttl_row_decision`)."""
    held = (rows[:, 0] > 0) | (rows[:, 2] > 0)                    # [R, C]
    c_dim = held.shape[1]
    return np.where(held.any(axis=1),
                    c_dim - 1 - np.argmax(held[:, ::-1], axis=1), -1)


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
    afterwards (the refresh loop decides on the device's
    :func:`ttl_refresh_decision` with :func:`_solve`).
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

_near_tie_rows = 0


def decide_rows(decision, candidates: np.ndarray, exact):
    """Float64 ``(best_ttl, best_cost)`` of each row of a copied-back
    :func:`ttl_row_decision` ``[3, E]``, on the candidate grid
    ``candidates`` ``[C+1]``.

    A row whose runner-up lies within :data:`REFINE_BAND` of its minimum
    -- more than one candidate float32 cannot order, an exact tie among
    them -- takes the argmin of ``exact(rows)``, the float64 surfaces of
    those rows, and its cost there.  Every other row takes the device's
    argmin, which the band proves is the float64 one, and its float32
    cost."""
    idx, mn, second = np.asarray(decision)
    ttl = candidates[idx.astype(np.intp)]
    cost = mn.astype(np.float64)
    near = np.flatnonzero(second.astype(np.float64)
                          <= cost * (1.0 + REFINE_BAND))
    if near.size:
        full = exact(near)
        j = np.argmin(full, axis=1)
        ttl[near] = candidates[j]
        cost[near] = full[np.arange(near.size), j]
    return ttl, cost


def _solve(problems, edges, engine: str, interpret: bool):
    """Device decisions of one or more problems, completed on the host by
    :func:`decide_rows`.

    Each problem is ``(rows, first, s, n, n_edges)``: ``rows`` ``[R, 3, C]``
    float64 (re-read, gap-weighted and paused bytes) serve the ``[E]``
    per-edge ``first``, ``s``, ``n`` as :func:`ttl_refresh_surface` lays
    them out, and edges past ``n_edges`` (with their rows) are padding,
    copied back and dropped before the decision.  Every program is started
    before the first copy back.  Returns ``(best_ttl, best_cost)`` of each
    problem's first ``n_edges`` edges, in float64."""
    from repro.core.ttl_policy import batched_cost_curves

    if engine not in ("kernel", "jax"):
        raise ValueError(f"unknown ttl_scan engine {engine!r}")
    # One float32 buffer in, one device program, one copy back, a problem.
    with tracing.span("skystore.ttl.scan"):
        out = [ttl_refresh_decision(
                   pack_ttl_inputs(rows, first, s, n, last_held_cells(rows)),
                   device_edges(edges), n_rows=rows.shape[0],
                   use_kernel=(engine == "kernel"), interpret=interpret)
               for rows, first, s, n, _ in problems]
        out = [np.asarray(o)[:, :p[4]] for o, p in zip(out, problems)]
    candidates = np.concatenate([[0.0], np.asarray(edges, dtype=np.float64)])
    results = []
    with tracing.span("skystore.ttl.resolve"):
        for (rows, first, s, n, _), decision in zip(problems, out):
            per_row = first.shape[0] // rows.shape[0]

            def exact(near):
                # Near-tie rows are decided, and priced, in float64.
                global _near_tie_rows
                _near_tie_rows += near.size
                r = rows[near // per_row]
                return batched_cost_curves(r[:, 0], r[:, 1], r[:, 2], edges,
                                           first[near], s[near], n[near])[1]

            results.append(decide_rows(decision, candidates, exact))
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

    Returns ``(best_ttl [E], best_cost [E])`` as float64 numpy arrays,
    decided as :func:`_solve` decides: TTLs are picked by *index* on the
    float64 candidate grid ``[0, edges...]``, so the returned TTL values are
    exact candidate boundaries, never float32 roundings of them.

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
    padding is dropped before the decision, which is :func:`_solve`'s, as
    for one refresh.

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
    ttls = np.concatenate([t for t, _c in out])
    costs = np.concatenate([c for _t, c in out])
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
