"""Production mesh construction (TPU v5e pods).

Single pod: (data=16, model=16) = 256 chips. Multi-pod: (pod=2, data=16,
model=16) = 512 chips; the "pod" axis carries pure data parallelism whose
all-reduce crosses the inter-pod DCN (gradient compression hooks live in
`repro.distributed.compression`).

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before its first import).
"""

from __future__ import annotations

import jax


def make_auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis of type Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Degenerate 1-device mesh for CPU smoke tests of sharded code paths."""
    return make_auto_mesh((1, 1), ("data", "model"))


# v5e hardware constants for the roofline (per chip).
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link
