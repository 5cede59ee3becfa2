"""Pallas TPU kernel: batched ExpectedCost(TTL) scan (paper §3.2.2).

The metadata server periodically recomputes, for every (bucket x directed
edge) pair, the expected cost of all ~800 candidate TTLs and takes the argmin
(§6.7.3: 10 regions x 1000 buckets = 100k edge problems per refresh).  That is
the control-plane hot spot, and it is embarrassingly parallel over edges with
a cumulative-sum structure over cells -- a natural VPU (8x128 vector unit)
workload with zero MXU involvement.

TPU adaptation (DESIGN.md §5): we lay the histograms out as (edges x cells)
tiles. Each grid step loads a (BLOCK_E, C_PAD) tile of the per-cell arrays
into VMEM, computes two prefix and two suffix sums along the cell axis in
fp32, forms the cost terms, and writes the (BLOCK_E, C_PAD) cost surface back
to HBM.
C_PAD rounds 800 up to the next multiple of 128 lanes -- 896 (7 x 128); block
height defaults to 256 sublanes, so the working set is

    5 arrays x 256 x 896 x 4 B ~= 4.6 MB  << 16 MB VMEM.

The kernel avoids `jnp.cumsum` (which lowers to a serial loop on some
backends) in favour of a ceil(log2(C)) Hillis-Steele shift-add scan: 10
shifted adds over the lane axis at C_PAD=896, each a full-width VPU op.

Oracle: :func:`repro.kernels.ref.ttl_cost_ref`; jit wrapper + argmin epilogue:
:func:`repro.kernels.ops.ttl_scan`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_E = 256          # edge rows per grid step (sublane axis)
LANES = 128


def _inclusive_scan(x: jax.Array) -> jax.Array:
    """Hillis-Steele inclusive prefix sum along the last axis.  Works for any
    length (the shift-add loop runs ceil(log2(n)) rounds; no power-of-2
    requirement -- see the non-power-of-2 regression in tests/test_kernels.py)."""
    n = x.shape[-1]
    shift = 1
    while shift < n:
        shifted = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(shift, 0)])[..., :-shift]
        x = x + shifted
        shift *= 2
    return x


def _exclusive_suffix_scan(x: jax.Array) -> jax.Array:
    """``out[..., j] = sum(x[..., j+1:])`` along the last axis: the same
    shift-add rounds as :func:`_inclusive_scan`, run toward lower lanes.

    The miss and tail terms need these suffix sums.  Forming them as
    ``total - prefix`` cancels in float32 once the suffix is small against
    the total -- the late, long-TTL candidates -- and moved replay decisions
    off the float64 argmin; summing the suffix directly keeps its error
    relative to its own size."""
    n = x.shape[-1]
    pad = [(0, 0)] * (x.ndim - 1)
    x = jnp.pad(x, pad + [(0, 1)])[..., 1:]
    shift = 1
    while shift < n:
        x = x + jnp.pad(x, pad + [(0, shift)])[..., shift:]
        shift *= 2
    return x


def _ttl_scan_kernel(
    hist_ref, time_w_ref, last_ref, edges_ref, mid_ref,
    s_ref, n_ref, first_ref, cost_ref,
):
    hist = hist_ref[...].astype(jnp.float32)          # [BE, C]
    time_w = time_w_ref[...].astype(jnp.float32)
    last = last_ref[...].astype(jnp.float32)
    edges = edges_ref[...].astype(jnp.float32)        # [1, C]
    mid = mid_ref[...].astype(jnp.float32)            # [1, C]
    s = s_ref[...].astype(jnp.float32)                # [BE, 1]
    n = n_ref[...].astype(jnp.float32)                # [BE, 1]
    first = first_ref[...].astype(jnp.float32)        # [BE, 1]

    # hist * t_hat with t_hat = time_w / hist is time_w wherever a cell holds
    # re-reads (0 elsewhere); taking it directly skips a float32 divide.
    hit_csum = _inclusive_scan(jnp.where(hist > 0, time_w, 0.0))
    age_csum = _inclusive_scan(last * mid)
    miss = _exclusive_suffix_scan(hist)
    tail = _exclusive_suffix_scan(last)

    cost_ref[...] = (
        first * n
        + s * hit_csum
        + miss * (n + edges * s)
        + tail * edges * s
        + s * age_csum
    )


@functools.partial(jax.jit, static_argnames=("block_e", "interpret"))
def ttl_cost_surface(
    hist: jax.Array,          # [E, C]
    time_w: jax.Array,        # [E, C]
    last: jax.Array,          # [E, C]
    edges: jax.Array,         # [C]
    s_price: jax.Array,       # [E]  $ / byte-second
    n_price: jax.Array,       # [E]  $ / byte
    first_remote: jax.Array,  # [E]
    block_e: int = BLOCK_E,
    interpret: bool = False,
) -> jax.Array:
    """[E, C] expected-cost surface via the Pallas kernel (padded + tiled)."""
    e_dim, c_dim = hist.shape
    c_pad = -(-c_dim // LANES) * LANES
    e_pad = -(-e_dim // block_e) * block_e

    def pad2(x):
        return jnp.pad(x, ((0, e_pad - e_dim), (0, c_pad - c_dim)))

    # Padded candidate cells replicate the final edge: duplicate candidates
    # never win the argmin and keep every lane's math finite.
    edges_p = jnp.pad(edges, (0, c_pad - c_dim), mode="edge")
    lower = jnp.concatenate([jnp.zeros_like(edges_p[:1]), edges_p[:-1]])
    mid_p = 0.5 * (lower + edges_p)

    def pad1(x):
        return jnp.pad(x, (0, e_pad - e_dim))[:, None]

    grid = (e_pad // block_e,)
    row = pl.BlockSpec((block_e, c_pad), lambda i: (i, 0))
    vec = pl.BlockSpec((block_e, 1), lambda i: (i, 0))
    brd = pl.BlockSpec((1, c_pad), lambda i: (0, 0))

    cost = pl.pallas_call(
        _ttl_scan_kernel,
        grid=grid,
        in_specs=[row, row, row, brd, brd, vec, vec, vec],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((e_pad, c_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="ttl_cost_scan",
    )(
        pad2(hist), pad2(time_w), pad2(last),
        edges_p[None, :], mid_p[None, :],
        pad1(s_price), pad1(n_price), pad1(first_remote),
    )
    return cost[:e_dim, :c_dim]
