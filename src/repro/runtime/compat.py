"""The jax device/mesh/sharding layer.

Every other module builds meshes and shard_maps through *this* file, so
one place holds the spelling of jax's public surface instead of every
call site:

  * meshes carry ``jax.sharding.AxisType.Auto`` on every axis;
  * ``shard_map`` is ``jax.shard_map`` with its ``check_vma=`` flag;
  * Pallas kernels run compiled on a TPU and interpreted elsewhere —
    ``pallas_interpret`` is the one place that decides;
  * ``enable_compile_cache`` places JAX's persistent compilation cache.

The application-facing API is deliberately tiny (the Puddles argument:
recovery/runtime layers should be application independent):

  ``make_mesh``, ``make_host_mesh``, ``axis_types_kwargs``,
  ``shard_map``, ``named_sharding``, ``AXIS_TYPE_AUTO``, ``axis_size``,
  ``pallas_interpret``, ``enable_compile_cache``.
"""

from __future__ import annotations

import os
import pathlib
from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding

AXIS_TYPE_AUTO = jax.sharding.AxisType.Auto

axis_size = jax.lax.axis_size

# <repo>/src/repro/runtime/compat.py -> <repo>
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


# ------------------------------------------------------------------- mesh
def axis_types_kwargs(n_axes: int) -> dict:
    """``{"axis_types": (Auto,) * n}`` for ``jax.make_mesh``."""
    return {"axis_types": (AXIS_TYPE_AUTO,) * n_axes}


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices=None) -> Mesh:
    """Build a ``Mesh`` with Auto axis types."""
    kw = axis_types_kwargs(len(axis_names))
    if devices is not None:
        kw["devices"] = devices
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kw)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """The (data, model) mesh the serving engine runs on."""
    return make_mesh((data, model), ("data", "model"))


# -------------------------------------------------------------- shard_map
def shard_map(f, *, mesh, in_specs, out_specs, check_replication: bool = False):
    """``jax.shard_map`` with one boolean replication-check knob."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_replication)


# ------------------------------------------------------------- shardings
def named_sharding(mesh: Mesh, spec) -> NamedSharding:
    """``NamedSharding`` constructor (single choke point should the class
    move again, as ``MeshPspecSharding`` → ``NamedSharding`` once did)."""
    return NamedSharding(mesh, spec)


# ---------------------------------------------------------------- pallas
def pallas_interpret() -> bool:
    """Whether a Pallas kernel called without an explicit ``interpret=``
    runs in the interpreter: everywhere but on a TPU."""
    return jax.devices()[0].platform != "tpu"


# --------------------------------------------------------- compile cache
def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set; otherwise the cache lives at the fixed
    ``<repo>/.jax_cache`` (the path is part of each entry's key, so it
    must not move between runs).  Every compiled program is kept, small
    allocator programs included.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
