"""Production meshes.

Single pod: a v5e pod of 256 chips as (data=16, model=16).
Multi-pod:  2 pods = 512 chips as (pod=2, data=16, model=16); the pod axis
carries data parallelism whose collectives cross the inter-pod links (DCN/
optical), so shardings keep param all-gathers *within* a pod (fsdp uses the
intra-pod "data" axis only).

Functions, not module constants: importing this module never touches jax
device state (device count is locked at first jax init, and the 512-device
dry-run must set XLA_FLAGS before that).
"""

from __future__ import annotations

import jax
import numpy as np

from repro.compat import make_mesh

# the chip the production meshes are made of (a ``launch.roofline.PEAKS`` key)
PRODUCTION_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=None, axes=None):
    """Small mesh over however many (possibly fake) devices exist — used by
    tests, benchmarks, and the elastic re-mesh path."""
    n = len(jax.devices())
    if shape is None:
        shape, axes = (n,), ("data",)
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    """Mesh axes carrying data parallelism (pod folds into DP)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis(mesh) -> str | None:
    return "model" if "model" in mesh.axis_names else None


def axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))
