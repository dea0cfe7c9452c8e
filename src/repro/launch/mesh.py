"""Production mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state. The production target is a TPU v5e pod of 16x16 =
256 chips (axes data x model), and 2 pods = 512 chips with a leading 'pod'
axis for the multi-pod dry-run.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants used by the roofline (per chip).
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    The model code shards through ``with_sharding_constraint`` and lets the
    compiler propagate the rest; ``jax.make_mesh``'s default ``Explicit``
    axes would instead demand explicit output shardings on gathers and
    reject those constraints."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
