"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count before first jax init.

Every program mesh is built through ``make_mesh``: its axes are ``Auto``
(GSPMD propagates shardings, ``with_sharding_constraint`` pins them, and
Pallas calls inside ``shard_map`` see manual axes). ``jax.make_mesh``
defaults to ``Explicit`` axes, under which the GradAccum scan and the
sharding constraints of core/gradaccum.py are rejected.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Tiny mesh over the actually-present devices (tests / examples)."""
    n = jax.device_count()
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
