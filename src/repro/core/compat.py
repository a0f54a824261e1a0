"""The two jax entry points every mesh and round body goes through.

Everything else in the codebase imports these helpers instead of calling
``jax.shard_map`` / ``jax.make_mesh`` directly, so the choices below are
made in one place.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off.

    Replication checking is disabled uniformly because several round
    bodies mix ``psum``-ed (replicated) and worker-local outputs in one
    pytree, which the static checker cannot always prove consistent.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    jax's own default makes every axis Explicit, under which gathers and
    scatters on row-sharded operands (MF/LDA ``query``, streaming
    ``ingest``, the LM embedding lookup) raise ``ShardingTypeError``."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(AxisType.Auto,) * len(names))
