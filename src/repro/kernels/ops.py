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

from . import ref
from .flash_attention import flash_attention_bhsd
from .ttl_scan import ttl_cost_surface


# ---------------------------------------------------------------------------
# TTL expected-cost scan
# ---------------------------------------------------------------------------

def ttl_scan(
    hist, time_w, last, edges, s_price, n_price, first_remote,
    use_kernel: bool = True,
    interpret: bool = False,
):
    """Batched TTL selection over E directed edges.

    Returns ``(best_ttl [E], best_cost [E], cost_surface [E, C+1])`` where
    candidate 0 is TTL=0 (evict immediately) and candidate j+1 is
    TTL=edges[j].  All inputs may be numpy or jax arrays.
    """
    hist, time_w, last = (jnp.asarray(x, jnp.float32) for x in (hist, time_w, last))
    edges = jnp.asarray(edges, jnp.float32)
    s_price = jnp.asarray(s_price, jnp.float32)
    n_price = jnp.asarray(n_price, jnp.float32)
    first_remote = jnp.asarray(first_remote, jnp.float32)

    if use_kernel:
        surface = ttl_cost_surface(
            hist, time_w, last, edges, s_price, n_price, first_remote,
            interpret=interpret,
        )
    else:
        surface = ref.ttl_cost_ref(
            hist, time_w, last, edges, s_price, n_price, first_remote
        )

    # Candidate TTL=0: every re-read pays N; no storage at all.
    zero = (first_remote + hist.sum(axis=1)) * n_price
    full = jnp.concatenate([zero[:, None], surface], axis=1)
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
    one the pure-float64 ``choose_ttl`` argmin picks.  Two steps:

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
    held = (np.asarray(hist) > 0) | (np.asarray(last) > 0)        # [E, C]
    c_dim = held.shape[1]
    last_cell = np.where(held.any(axis=1),
                         c_dim - 1 - np.argmax(held[:, ::-1], axis=1), -1)
    beyond = np.arange(surf.shape[1])[None, :] > last_cell[:, None] + 1
    surf = np.where(beyond, np.inf, surf)
    mn = surf.min(axis=1, keepdims=True)
    idx = np.argmin(surf, axis=1)
    rows = np.flatnonzero((surf <= mn * (1.0 + REFINE_BAND)).sum(axis=1) > 1)
    if rows.size:
        idx[rows] = np.argmin(exact(rows), axis=1)
    return idx


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
    from repro.core.ttl_policy import batched_cost_curves

    if engine is None:
        engine = "kernel" if use_kernel else "jax"
    if engine not in ("kernel", "jax"):
        raise ValueError(f"unknown ttl_scan engine {engine!r}")
    edges = histograms[0].edges
    for h in histograms[1:]:
        if h.edges.shape != edges.shape or not np.allclose(h.edges, edges):
            raise ValueError("histograms with different cell layouts")
    hist = np.stack([h.hist for h in histograms])
    time_w = np.stack([h.time_weight for h in histograms])
    last = np.stack([h.last for h in histograms])
    first = np.asarray([h.first_read_remote_bytes for h in histograms])
    s = np.asarray([
        cost_model.storage_price(dst) / GB / SECONDS_PER_MONTH
        for (_src, dst) in targets
    ])
    n = np.asarray([
        cost_model.egress_price(src, dst) / GB for (src, dst) in targets
    ])
    _ttl32, _cost32, surface = ttl_scan(
        hist, time_w, last, edges, s, n, first,
        use_kernel=(engine == "kernel"), interpret=interpret,
    )
    surface = np.asarray(surface, dtype=np.float64)

    def exact(rows):
        # Near-tie rows are resolved, and reported, in float64.
        _, cost = batched_cost_curves(hist[rows], time_w[rows], last[rows],
                                      edges, first[rows], s[rows], n[rows])
        surface[rows] = cost
        return cost

    idx = canonical_argmin(surface, hist, last, exact)
    candidates = np.concatenate([[0.0], np.asarray(edges, dtype=np.float64)])
    return candidates[idx], surface[np.arange(idx.shape[0]), idx], surface


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
    scan, so no hand kernel is warranted (see DESIGN.md §5)."""
    return ref.rwkv6_ref(r, k, v, w, u, state)
