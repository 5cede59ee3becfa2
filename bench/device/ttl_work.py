"""The least work of one TTL refresh, counted from the problem's real shape.

A refresh of a (bucket, region) pair solves one problem per incoming edge:
``E`` edges (the other regions), each over the ``C`` cells of the shared
histogram.  Whatever implements it -- one kernel call per refresh, a batch
of refreshes, or no kernel at all -- it has to read, in the float32 the
configuration computes the surface in:

* per edge, the ``C``-cell re-read bytes, gap-weighted bytes and paused
  bytes: ``3 * E * C`` values;
* the ``C`` cell edges, and per edge its storage price, egress price and
  first-read bytes: ``C + 3 * E`` values;

and write one chosen candidate per edge (``E`` 4-byte indices).  Padding to
the kernel's tiles, and the candidate TTL=0 column, are not counted.

Operations, per edge and cell: four running sums (hit, age, miss, tail: 4
adds, and the age term's product: 1 mul), the cost terms (s*hit, t*s,
miss*(n + t*s), tail*(t*s), s*age: 5 muls, and n + t*s: 1 add), 4 adds to
total the five terms, and one compare for the argmin: 16.  Against the
chip's bf16 peak (the only published one) the operation bound is far below
the byte bound, so the count only has to be no larger than the truth.
"""

from __future__ import annotations

BYTES_PER_VALUE = 4
OPS_PER_CELL = 16


def refresh_bytes(edges: int, cells: int) -> int:
    values_in = 3 * edges * cells + cells + 3 * edges
    return BYTES_PER_VALUE * (values_in + edges)


def refresh_ops(edges: int, cells: int) -> int:
    return OPS_PER_CELL * edges * cells


def least_seconds(refreshes: int, edges: int, cells: int, peak: dict) -> float:
    """The least time a chip with ``peak`` needs for ``refreshes``: the
    larger of the byte bound and the operation bound."""
    t_bytes = refreshes * refresh_bytes(edges, cells) / peak["hbm_bytes_per_s"]
    t_ops = refreshes * refresh_ops(edges, cells) / peak["flops_per_s"]
    return max(t_bytes, t_ops)
