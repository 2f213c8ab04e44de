"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run must
set XLA_FLAGS before any jax initialisation.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    """jax.make_mesh with every axis Auto-sharded."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one v5e pod, 256 chips) or 2x16x16 (two pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_detection_mesh(devices=None):
    """1-D data-parallel mesh over the local devices for the detection
    pipeline's sharded ``run_batch`` (batch dim sharded on ``data``,
    everything else replicated)."""
    import numpy as np
    devs = list(devices) if devices is not None else jax.devices()
    return jax.sharding.Mesh(np.array(devs), ("data",))


def make_local_mesh(model: int = 1):
    """Whatever this host has (CPU smoke tests / examples)."""
    n = len(jax.devices())
    data = n // model
    return _mesh((data, model), ("data", "model"))


# Hardware constants for the roofline (TPU v5e per chip)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # bytes/s
ICI_LINK_BW = 50e9            # bytes/s per link
