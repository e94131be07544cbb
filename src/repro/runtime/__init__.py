"""Runtime layer: device/mesh/sharding construction, the Pallas
interpret-mode choice and the persistent compilation cache.

No module outside this package may touch ``jax.sharding.AxisType``,
``jax.make_mesh``'s ``axis_types=``, or the ``shard_map`` entry point
directly — import from here instead.
"""

from .compat import (AXIS_TYPE_AUTO, axis_size, axis_types_kwargs,
                     enable_compile_cache, make_host_mesh, make_mesh,
                     named_sharding, pallas_interpret, shard_map)

__all__ = [
    "AXIS_TYPE_AUTO",
    "axis_size",
    "axis_types_kwargs",
    "enable_compile_cache",
    "make_host_mesh",
    "make_mesh",
    "named_sharding",
    "pallas_interpret",
    "shard_map",
]
