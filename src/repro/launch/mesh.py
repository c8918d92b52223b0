"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing never touches jax
device state; the dry-run sets XLA_FLAGS for 512 host devices before any
jax import and then calls this.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def mesh_shape_dict(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def make_host_mesh(shape: tuple[int, ...] = (1, 1),
                   axes: tuple[str, ...] = ("data", "model")):
    """Mesh over however many (host) devices exist — tests/examples."""
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    """The LM stack places activations with ``with_sharding_constraint``
    and lets the partitioner resolve the rest, so its mesh axes are
    Auto (``jax.make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_spectral_mesh(n_shards: int, axis: str = "shard"):
    """1-D mesh for sharded spectral inference (ISSUE 9).

    Uses the FIRST ``n_shards`` devices so a plan built for a small
    mesh runs on a machine exposing more (e.g. a 2-shard plan on the
    CI's forced 8-device CPU mesh).  The axis name must match
    ``ShardedNetworkPlan.axis`` — the executor's collectives
    (ppermute/psum) are written against it.
    """
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < n_shards:
        raise ValueError(
            f"need {n_shards} devices for the spectral mesh, have "
            f"{len(devs)} (forced host meshes: set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards} "
            f"BEFORE importing jax)")
    return Mesh(np.asarray(devs[:n_shards]), (axis,))
