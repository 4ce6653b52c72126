"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run process sets the 512-device
XLA flag before first jax init, other processes see real devices.

Single pod:  (16, 16)      axes ('data', 'model')   — 256 chips (v5e pod)
Multi-pod:   (2, 16, 16)   axes ('pod', 'data', 'model') — 512 chips,
             the 'pod' axis crossing DCN.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from repro.distributed.sharding import MeshAxes

__all__ = ["make_production_mesh", "mesh_axes_for", "local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def local_mesh(dp: int = 0, tp: int = 0) -> Mesh:
    """``(data, model)`` mesh over this host's devices. A ``dp`` or
    ``tp`` of 0 is derived from the device count: TP takes up to 4
    devices, DP the rest."""
    devs = jax.devices()
    dp = dp or max(len(devs) // (tp or 4), 1)
    tp = tp or len(devs) // dp
    if dp * tp > len(devs):
        raise ValueError(f"mesh dp={dp} x tp={tp} needs {dp * tp} devices; "
                         f"this host has {len(devs)}")
    return Mesh(np.asarray(devs[:dp * tp]).reshape(dp, tp),
                ("data", "model"))


def mesh_axes_for(mesh) -> MeshAxes:
    if "pod" in mesh.shape:
        return MeshAxes(data=("pod", "data"), model="model")
    return MeshAxes(data=("data",), model="model")
