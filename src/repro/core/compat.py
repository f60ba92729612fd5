"""The jax mesh and shard_map surface, in one place.

Everything in the repo builds meshes and SPMD programs through these
helpers, so the axis types and the replication-check setting are chosen
once: every mesh axis is ``Auto``, and ``shard_map`` runs with
``check_vma=False``.
"""
from __future__ import annotations

import jax


def _auto(axis_names):
    return (jax.sharding.AxisType.Auto,) * len(axis_names)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(axis_shapes, axis_names, axis_types=_auto(axis_names))


def make_mesh_of(devices, axis_names):
    """A Mesh over an EXPLICIT device array — the communicator-group path
    (``IContext.split``/``group``): sub-meshes must pin their device subset,
    which ``jax.make_mesh`` (auto device selection) cannot express."""
    return jax.sharding.Mesh(devices, axis_names, axis_types=_auto(axis_names))


def get_ambient_mesh():
    """The abstract mesh installed by ``set_mesh`` (empty when none is)."""
    return jax.sharding.get_abstract_mesh()


def set_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def shard_map(f, mesh=None, in_specs=None, out_specs=None):
    """``jax.shard_map`` with replication checking off (our collectives use
    unreduced intermediates that the checker rejects). Without ``mesh`` the
    ambient mesh of ``set_mesh`` is used."""
    kw = {} if mesh is None else {"mesh": mesh}
    return jax.shard_map(f, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False, **kw)
