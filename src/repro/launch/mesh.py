"""Production mesh definitions.

A *function*, not a module-level constant, so importing this module never
touches jax device state (the dry-run process sets
``--xla_force_host_platform_device_count=512`` before any jax import; test
processes see the single real device).

Topology (TPU v5e pods):
  single-pod  (16, 16)       axes ("data", "model")   — 256 chips
  multi-pod   (2, 16, 16)    axes ("pod", "data", "model") — 512 chips
The "pod" axis carries only batch (pure DP across pods: cross-pod traffic
is one gradient all-reduce per step, the slowest link is used the least).
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_host_mesh", "mesh_from_arg"]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)} — "
            "run under launch/dryrun.py (which forces 512 host devices) "
            "or on a real pod slice")
    import numpy as np

    dev_array = np.asarray(devices[:need]).reshape(shape)
    return Mesh(dev_array, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_from_arg(spec: str, *, verbose: bool = True) -> Mesh:
    """Parse a ``--mesh DATA,MODEL`` CLI value (e.g. ``"4,2"``) into a
    host mesh — the shared helper behind the examples' ``--mesh`` flags.
    On CPU, force host devices first:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<DATA*MODEL>``."""
    try:
        data, model = (int(x) for x in spec.split(","))
    except ValueError as e:
        raise ValueError(
            f"--mesh expects DATA,MODEL (e.g. 4,2), got {spec!r}") from e
    mesh = make_host_mesh(data, model)
    if verbose:
        print(f"mesh: data={data} model={model} ({data * model} devices)")
    return mesh


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Tiny mesh over however many devices the test process has."""
    import numpy as np

    need = data * model
    devices = jax.devices()[:need]
    if len(devices) < need:
        raise RuntimeError(f"need {need} devices, have {len(jax.devices())}")
    return Mesh(np.asarray(devices).reshape(data, model), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
