"""Mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state.

* :func:`make_device_mesh` — a ``(data, model)`` mesh over the devices
  present (e.g. one TPU v5e host of 4 chips as ``data=1, model=4``).
* :func:`make_production_mesh` — the pod-scale meshes the dry-run compiles
  against on forced host devices.  Single pod: 16x16 = 256 chips (TPU v5e
  pod, 2-D ICI torus).  Multi-pod: 2 pods x 256 chips; the leading "pod"
  axis crosses the inter-pod links (data-parallel outer axis, where the
  gradient compression of `optim.compression` applies).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_device_mesh(model: int, devices=None):
    """A ``(data, model)`` mesh over ``devices`` (default: every device
    present), ``model`` devices per model-parallel group.  Raises when the
    device count does not factor — no device is left out."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    if model < 1 or n % model:
        raise ValueError(f"{n} devices do not factor into model-parallel "
                         f"groups of {model}")
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


# TPU v5e hardware constants (per chip) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW_PER_LINK = 50e9            # bytes/s/link
ICI_LINKS_PER_RING = 2            # bidirectional ring on one torus dim
