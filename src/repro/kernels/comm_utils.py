"""Shared plumbing for communication kernels: shard_map wrappers,
interpret-mode selection, and shape checking."""
from __future__ import annotations

import functools
from typing import Callable

import jax
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from repro.core.primitives import INTERPRET_PARAMS

__all__ = ["interpret_mode", "on_tpu", "ring_neighbors", "check_2d"]


def on_tpu() -> bool:
    """True when the kernel is being built for TPU devices: the default
    backend is a TPU, or the enclosing ``shard_map`` mesh is made of TPU
    devices (a described topology compiled from a CPU host included)."""
    if jax.default_backend() == "tpu":
        return True
    dev = jax.sharding.get_abstract_mesh().abstract_device
    return dev is not None and dev.device_kind.startswith("TPU")


def interpret_mode():
    """``interpret=`` argument for pallas_call: False (compiled Mosaic)
    whenever the kernel targets TPU devices, the eager-DMA interpreter
    only for CPU meshes (CI / laptop validation)."""
    return False if on_tpu() else INTERPRET_PARAMS


def ring_neighbors(axis: str):
    """(prev, next) logical ring neighbors along a mesh axis."""
    num = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    return jax.lax.rem(me - 1 + num, num), jax.lax.rem(me + 1, num)


def check_2d(x, name: str = "x") -> None:
    if x.ndim != 2:
        raise ValueError(f"{name} must be 2D (rows, cols); got {x.shape}")
