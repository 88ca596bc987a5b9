"""Production mesh definitions (TPU v5e).

Single pod: 16×16 = 256 chips, axes (data, model).
Multi-pod:  2×16×16 = 512 chips, axes (pod, data, model) — the ``pod``
axis carries the data-parallel gradient all-reduce across the inter-pod
links (DCN in real deployments; the dry-run proves the sharding is
coherent across the axis).

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first init — see dryrun.py, which
must set XLA_FLAGS before anything else).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Whatever this host actually has — for smoke tests / CPU runs."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link (conservative single link)
VMEM_BYTES = 16 * 2 ** 20
