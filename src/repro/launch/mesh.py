"""The design-point mesh the simulator sweeps shard over.

``make_points_mesh`` is a function (not a module constant) so that
importing this module never touches JAX device state: a process that
sets ``XLA_FLAGS=--xla_force_host_platform_device_count`` must do so
before the first JAX initialisation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

POINTS_AXIS = "points"


def make_points_mesh() -> jax.sharding.Mesh | None:
    """1-D ``("points",)`` mesh over every device — the design-point /
    batch-row sharding axis of the simulator sweeps (DESIGN.md §2.7).
    Returns None with a single device so the sweep entry points fall
    back to their plain vmap path instead of paying shard_map overhead
    for nothing.  A function, so importing never touches JAX device
    state (``--xla_force_host_platform_device_count`` must win the
    race)."""
    n = len(jax.devices())
    if n < 2:
        return None
    # Auto axes: callers index, slice and gather sharded arrays as plain
    # arrays, which explicit (typed) axes — the default of
    # ``jax.make_mesh`` — would reject
    return jax.make_mesh((n,), (POINTS_AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def shard_points(mesh: jax.sharding.Mesh, fn, *, n_sharded: int):
    """Wrap a batched-over-leading-axis ``fn`` with ``jax.shard_map``
    over the 1-D ``("points",)`` mesh of :func:`make_points_mesh`: every
    leaf of the first ``n_sharded`` arguments (arrays or pytrees of
    them) shards its leading axis across devices, the rest (shared trace
    arrays) replicate, and every output leaf stays sharded over the
    points.

    The batch pads to a device multiple by repeating row 0 — padded
    rows simulate harmless garbage that is sliced off every output leaf
    before returning, so callers see exactly their B results.  The
    shard_map program is built (and jitted) once per argument count and
    reused, so repeated sweeps through one wrapper stay retrace-free."""
    size = mesh.devices.size
    programs: dict[int, object] = {}

    def grow(a, pad):
        a = jnp.asarray(a)
        return jnp.concatenate([a, jnp.repeat(a[:1], pad, axis=0)])

    def call(*args):
        n = int(jax.tree.leaves(args[0])[0].shape[0])
        pad = -n % size
        if pad:
            args = tuple(jax.tree.map(lambda a: grow(a, pad), x)
                         if i < n_sharded else x
                         for i, x in enumerate(args))
        sm = programs.get(len(args))
        if sm is None:
            # each device folds its own points with no collective, so
            # there is nothing for the varying-axis check to verify; its
            # scan-carry rule would reject the folds' replicated initial
            # states
            specs = tuple(P(POINTS_AXIS) if i < n_sharded else P()
                          for i in range(len(args)))
            sm = programs[len(args)] = jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=specs, out_specs=P(POINTS_AXIS),
                check_vma=False))
        out = sm(*args)
        return jax.tree.map(lambda x: x[:n], out) if pad else out

    return call


def mesh_chip_count(mesh: jax.sharding.Mesh) -> int:
    return int(mesh.devices.size)
