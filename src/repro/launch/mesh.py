"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state. The dry-run calls these after forcing 512
host-platform devices; real launches get the same topology from the TPU
runtime.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int | None = None) -> Mesh:
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    if model is None:
        model = 1
        for cand in (2, 4):
            if n % cand == 0 and n >= cand * 2:
                model = cand
    data = n // model
    return _make_mesh((data, model), ("data", "model"))


def make_elastic_mesh(n_devices: int) -> Mesh:
    """Largest (data, model) mesh for an arbitrary live-device count —
    used by ft/elastic.py after shrink/grow events. Prefers model=16 when
    divisible, else the largest power-of-two divisor ≤ 16."""
    devices = jax.devices()[:n_devices]
    model = 1
    for cand in (16, 8, 4, 2):
        if n_devices % cand == 0:
            model = cand
            break
    data = n_devices // model
    import numpy as np

    dev_array = np.array(devices).reshape(data, model)
    return Mesh(dev_array, ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))
