"""The mesh/sharding API names the codebase uses, plus two runtime helpers.

The repo targets one installed JAX (0.9.x, see DESIGN.md §"JAX runtime
contract"). These names are plain aliases of that version's public API,
kept in one module so the mesh vocabulary the sharded code speaks is
listed in one place:

  get_abstract_mesh  jax.sharding.get_abstract_mesh()  (empty-shape mesh
                     outside a mesh context: test ``not mesh.shape``)
  shard_map          jax.shard_map (check_vma honoured)
  pvary              jax.lax.pcast(..., to="varying")
  set_mesh           jax.set_mesh
  make_mesh          jax.make_mesh
  AxisType           jax.sharding.AxisType
  axis_size          jax.lax.axis_size
"""

from __future__ import annotations

import functools
import os
import pathlib

import jax

__all__ = [
    "get_abstract_mesh", "shard_map", "pvary", "set_mesh", "make_mesh",
    "AxisType", "axis_size", "enable_compilation_cache",
    "DEFAULT_COMPILATION_CACHE_DIR", "supports_float8",
]

get_abstract_mesh = jax.sharding.get_abstract_mesh
shard_map = jax.shard_map
set_mesh = jax.set_mesh
make_mesh = jax.make_mesh
AxisType = jax.sharding.AxisType
axis_size = jax.lax.axis_size


def pvary(x, axis_names):
    """Mark `x` device-varying over `axis_names` (vma typing in shard_map)."""
    return jax.lax.pcast(x, axis_names, to="varying")


# <checkout>/.jax_cache: a fixed path (the path is part of the cache key, so
# a directory that moved would never hit), listed in .gitignore.
DEFAULT_COMPILATION_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on XLA's persistent compilation cache; return its directory.

    Warm restarts (DESIGN.md §9) and cold chip runs both pay every compile
    again without it. The directory is ``$JAX_COMPILATION_CACHE_DIR`` when
    that is set — the deployment decides, and no other directory is set in
    code — and otherwise the fixed in-checkout
    `DEFAULT_COMPILATION_CACHE_DIR`. The eviction thresholds are lowered to
    "cache everything": serving programs are many and individually small,
    and the defaults skip sub-second compiles, which is exactly the retrace
    cost a restart pays N times over.
    """
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or DEFAULT_COMPILATION_CACHE_DIR)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # The cache module latches its state at the FIRST compilation: enabling
    # the dir after anything has jitted (service constructed mid-process,
    # after planner/test warmup) would silently no-op. Reset so the dir
    # takes effect from the next compile.
    from jax.experimental.compilation_cache import compilation_cache as _cc

    _cc.reset_cache()
    return cache_dir


@functools.lru_cache(maxsize=1)
def supports_float8() -> bool:
    """True when this jax build has a usable float8_e4m3fn storage dtype.

    Capability probe for the precision policy's fp8 storage hook
    (`core.precision`): the dtype attribute must exist AND a round-trip
    cast through it must execute on the default backend — attribute
    presence alone is not enough on builds where ml_dtypes registers the
    type but the backend rejects it at lowering time.
    """
    import jax.numpy as jnp

    if not hasattr(jnp, "float8_e4m3fn"):
        return False
    try:
        x = jnp.ones((2, 2), dtype=jnp.float32)
        roundtrip = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return bool((roundtrip == x).all())
    except Exception:                                  # pragma: no cover
        return False
