"""Production meshes.

Single pod: 16x16 = 256 chips ("data", "model").
Multi-pod:  2x16x16 = 512 chips ("pod", "data", "model") — "pod" is the
outer data-parallel/FSDP axis (DCN-ish in real deployments); nothing below
binds to these sizes, so 1000+-node meshes are a parameter change here.

NOTE: functions, not module constants — importing this module must never
touch jax device state (device count is locked at first use).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules and
    ``with_sharding_constraint`` calls of this package name mesh axes
    the compiler partitions over, which ``Explicit`` axes (the
    ``make_mesh`` default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Tiny mesh for the 8-fake-device subprocess tests."""
    shape = (2, 2, 2) if multi_pod else (2, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_sim_mesh(n: int | None = None, devices=None):
    """1-D mesh for the sharded-PDES engine workload over the first ``n``
    of ``devices`` (default: every device of the default backend)."""
    devices = jax.devices() if devices is None else list(devices)
    n = n or len(devices)
    return _mesh((n,), ("sim",), devices=devices[:n])
