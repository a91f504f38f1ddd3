"""Mesh, shard_map and multi-process set-up, spelled once for the repo.

Every mesh and shard_map in the repo goes through these helpers so each
makes the same choices: ``Auto`` mesh axes (``jax.make_mesh`` defaults to
``Explicit`` sharding, which the partitioned engines do not use), no
replication check inside ``shard_map``, and gloo collectives for
multi-process CPU runs.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication (VMA) check disabled."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def distributed_initialize(coordinator_address: str, num_processes: int,
                           process_id: int) -> None:
    """``jax.distributed.initialize`` with CPU cross-process collectives
    enabled first.

    On the CPU backend multi-process psums need the gloo collectives
    implementation; without ``jax_cpu_collectives_implementation = "gloo"``
    set *before* initialization, every collective (and even the implicit
    ``assert_equal`` inside multi-process ``device_put``) fails with
    "Multiprocess computations aren't implemented on the CPU backend".
    """
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
