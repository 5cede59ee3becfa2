"""Production mesh construction (deliverable e).

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) -- the leading
"pod" axis is the DCN-connected dimension; the dry-run proves every program
shards over it.

Defined as FUNCTIONS so importing this module never touches jax device state
(the 512-device XLA_FLAGS hack is dryrun.py's first two lines, nobody else's).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_host_mesh(model: int = 1):
    """Degenerate mesh over however many devices this host actually has --
    used by smoke tests and the CPU examples."""
    n = jax.device_count()
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


#: TPU v5e hardware constants used by the roofline (per chip).
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW_PER_LINK = 50e9            # B/s, ~per link
