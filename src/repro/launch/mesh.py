"""Production meshes (task spec, MULTI-POD DRY-RUN §1).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state; callers (dryrun.py) must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to get the 512 placeholder devices.
"""
from __future__ import annotations

from repro.distributed.meshes import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """8-device mini mesh for CI (same axis structure)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
