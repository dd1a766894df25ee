"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (device count is locked at first jax init — the dry-run must set
XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the model code places
    arrays through sharding constraints and leaves the rest to GSPMD."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod (v5e pod); 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1):
    """Whatever this host has (tests / CPU examples)."""
    n = jax.device_count()
    dp = n // model_parallel
    return make_mesh((dp, model_parallel), ("data", "model"))
