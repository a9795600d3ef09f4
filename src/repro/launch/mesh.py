"""Mesh construction: every mesh of the repo is built here.

These are FUNCTIONS (not module-level state) so importing this module never
touches jax device initialization. The dry-run entry point
(launch/dryrun.py) sets XLA_FLAGS for 512 placeholder devices before any jax
import; everything else (tests, benches) sees the real device count.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

from repro.configs.base import MeshConfig


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Sequence | None = None) -> jax.sharding.Mesh:
    """A mesh with Auto axes. ``jax.make_mesh`` defaults to Explicit axes,
    under which GSPMD no longer propagates the shardings the model relies on:
    ``with_sharding_constraint`` and sharded contractions are refused."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), devices=devices)


def mesh_for(cfg: MeshConfig, *, devices: Sequence | None = None) -> jax.sharding.Mesh:
    return make_mesh(cfg.shape, cfg.axes, devices=devices)


def describe(mesh: jax.sharding.Mesh) -> dict:
    return {
        "shape": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "n_devices": mesh.devices.size,
        "platform": mesh.devices.reshape(-1)[0].platform,
    }
