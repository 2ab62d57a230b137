"""Strassen base-case dispatch: route recursion leaves to the best GEMM.

The Strassen recursion (core/strassen.py) bottoms out in classical
multiplies at/below its crossover cutoff. This layer picks what runs each
leaf:

  * Pallas (`kernels/matmul`: `grid_matmul` / `grid_schur_update`, i.e.
    `matmul_pallas`/`schur_update_pallas` on the flattened grid) when the
    kernels are compiled (TPU) or interpret mode is forced
    (``SPIN_PALLAS_INTERPRET=1`` — the CI correctness path) AND the
    flattened leaf dimension is Mosaic-legal; under a mesh the SUMMA
    gathers stay and only the local GEMM swaps to the kernel (the
    ``pallas`` engine's own composition rule).
  * XLA otherwise: the shard_map SUMMA engine under an active mesh —
    which itself falls back to a local einsum wherever the (halved,
    possibly padded) grid no longer divides the mesh, the Strassen
    recursion's SUMMA-style fallback — and a plain einsum off-mesh.

Dispatch happens at trace time (backend/env/mesh are trace-time facts), so
the chosen leaf bakes into the jitted program like every other engine
decision.
"""

from __future__ import annotations

import jax

from repro import compat
from repro.core.precision import dot_precision

from .. import pallas_interpret_default, pallas_routes_forced

__all__ = ["pallas_base_default", "mosaic_legal", "base_matmul",
           "base_matmul_blocks", "base_schur_update"]


def pallas_base_default() -> bool:
    """Should Strassen leaves compose with the Pallas kernels?

    True where the kernels run compiled (TPU) and, off-TPU, where the
    interpreted route is explicitly forced (``SPIN_PALLAS_INTERPRET=1`` —
    CI exercises the composed base case on CPU runners). Plain off-TPU
    runs use XLA: an implicitly interpreted kernel would be orders of
    magnitude slower than the einsum it replaces, inverting the crossover
    the engine exists for.
    """
    return not pallas_interpret_default() or pallas_routes_forced()


def mosaic_legal(n: int, full_tile_max: int = 512) -> bool:
    """Whether an (n, n) flattened-leaf GEMM gets a Mosaic-legal tiling.

    `kernels.matmul.auto_tiles` picks, per dim, a multiple of 128 that
    divides it (sized by its VMEM reckoning) and falls back to one full-dim
    tile otherwise; a full-dim tile is only safe while the n×n f32 tiles
    and the dot's temporaries fit VMEM comfortably (n ≤ 512 ⇒ at most 14 MiB).
    Outside both regimes the leaf stays on XLA rather than risk a Mosaic
    layout failure.
    """
    return n % 128 == 0 or n <= full_tile_max


def _mesh_active() -> bool:
    mesh = compat.get_abstract_mesh()
    return mesh is not None and bool(mesh.shape)


def _leaf_engine(n: int) -> str:
    if pallas_base_default() and mosaic_legal(n):
        return "pallas"
    # SUMMA under a mesh (multiply_blocks itself falls back to a local
    # einsum where the grid doesn't divide the mesh), plain einsum off it.
    return "allgather" if _mesh_active() else "einsum"


def base_matmul_blocks(a: jax.Array, b: jax.Array) -> jax.Array:
    """One classical leaf multiply on (g, g, bs, bs) block grids.

    The off-mesh XLA leaf flattens the grid to ONE dense (n, n) GEMM
    instead of the block einsum: a single dot_general keeps the vendor
    GEMM's cache blocking and thread saturation, where the grid einsum
    measures ~20% slower at the leaf sizes Strassen bottoms out at — the
    difference between the engine winning and losing its crossover. Under
    a mesh the blocks must stay blocks (the flatten would be a gather), so
    the SUMMA route keeps the grid layout.
    """
    import jax.numpy as jnp

    eng = _leaf_engine(a.shape[0] * a.shape[2])
    if eng == "einsum":
        g, _, bs, _ = a.shape
        n = g * bs
        ad = a.transpose(0, 2, 1, 3).reshape(n, n)
        bd = b.transpose(0, 2, 1, 3).reshape(n, n)
        acc = (jnp.float32
               if a.dtype in (jnp.bfloat16, jnp.float16, jnp.float32)
               else a.dtype)
        cd = jnp.matmul(ad, bd, preferred_element_type=acc,
                        precision=dot_precision(a.dtype, b.dtype)
                        ).astype(a.dtype)
        return cd.reshape(g, bs, g, bs).transpose(0, 2, 1, 3)
    # Late import: core.multiply dispatches into us. Import from the
    # submodule directly — `repro.core.multiply` the *attribute* is the
    # `multiply` function re-exported by core/__init__, not the module.
    from repro.core.multiply import multiply_blocks

    return multiply_blocks(a, b, eng)


def base_schur_update(c: jax.Array, a: jax.Array, b: jax.Array, *,
                      negate_c: bool) -> jax.Array:
    """One classical leaf Schur update (A·B − C or C − A·B), fused on Pallas.

    The XLA routes compose `base_matmul_blocks` with the elementwise
    subtract — the SAME product computation as the unfused path, so
    Strassen's fused Schur route stays bitwise identical to
    multiply-then-subtract everywhere the Pallas kernel isn't fusing.
    """
    eng = _leaf_engine(a.shape[0] * a.shape[2])
    if eng == "pallas":
        from repro.core.multiply import schur_update_blocks

        return schur_update_blocks(c, a, b, negate_c=negate_c, engine=eng)
    prod = base_matmul_blocks(a, b)
    return prod - c if negate_c else c - prod


def base_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """One classical leaf multiply on dense (n, n) operands."""
    import jax.numpy as jnp

    n = a.shape[0]
    if pallas_base_default() and mosaic_legal(n):
        from ..matmul import ops as mm_ops

        return mm_ops.matmul(a, b)
    acc = (jnp.float32 if a.dtype in (jnp.bfloat16, jnp.float16, jnp.float32)
           else a.dtype)
    return jnp.matmul(a, b, preferred_element_type=acc,
                      precision=dot_precision(a.dtype, b.dtype)
                      ).astype(a.dtype)
