"""Jit'd public wrappers for the Gauss-Jordan leaf inverse family.

Interpret mode is resolved through the package-wide policy
(`repro.kernels.pallas_interpret_default`): compiled on TPU, interpreted
elsewhere, overridable with ``SPIN_PALLAS_INTERPRET=1``.

`batched_blocked_leaf_inverse` books the diagonal blocks its kernel
inverts, bs/t per block, as `leaf_panels` in an active `count_ops`
context (trace time only).
"""

from __future__ import annotations

import functools

import jax

from repro.core.blockmatrix import _bump

from .. import mesh_safe, pallas_interpret_default
from .kernel import (blocked_leaf_inverse_pallas, leaf_inverse_pallas,
                     leaf_panel, triangular_solve_pallas)

__all__ = ["leaf_inverse", "batched_leaf_inverse", "blocked_leaf_inverse",
           "batched_blocked_leaf_inverse", "triangular_solve"]


def leaf_inverse(block: jax.Array, out_dtype=None) -> jax.Array:
    """Invert one (bs, bs) block (SPIN's Algorithm-2 leaf, scalar GJ).

    out_dtype=float32 keeps the f32 GJ sweep un-rounded on the final write
    even for low-precision blocks (same contract as the matmul wrappers).
    """
    return batched_leaf_inverse(block[None], out_dtype=out_dtype)[0]


def batched_leaf_inverse(blocks: jax.Array, out_dtype=None) -> jax.Array:
    """Invert (batch, bs, bs) blocks — one grid program per block."""
    return mesh_safe(functools.partial(
        leaf_inverse_pallas, interpret=pallas_interpret_default(),
        out_dtype=out_dtype))(blocks)


def blocked_leaf_inverse(block: jax.Array, panel: int | None = None,
                         out_dtype=None) -> jax.Array:
    """Invert one (bs, bs) block with the blocked (rank-t MXU) GJ sweep."""
    return batched_blocked_leaf_inverse(block[None], panel=panel,
                                        out_dtype=out_dtype)[0]


def batched_blocked_leaf_inverse(blocks: jax.Array, panel: int | None = None,
                                 out_dtype=None) -> jax.Array:
    """Blocked-GJ inverse of (batch, bs, bs) blocks."""
    t = panel or leaf_panel(blocks.shape[-1])
    _bump("leaf_panels", blocks.shape[0] * (blocks.shape[-1] // t))
    return mesh_safe(functools.partial(
        blocked_leaf_inverse_pallas, panel=t,
        interpret=pallas_interpret_default(), out_dtype=out_dtype))(blocks)


def triangular_solve(t: jax.Array, b: jax.Array, *, lower: bool = True,
                     unit_diagonal: bool = False,
                     panel: int | None = None) -> jax.Array:
    """Solve T X = B for one (bs, bs) triangular T and (bs, k) B."""
    return mesh_safe(functools.partial(
        triangular_solve_pallas, panel=panel, lower=lower,
        unit_diagonal=unit_diagonal,
        interpret=pallas_interpret_default()))(t[None], b[None])[0]
