"""Leaf solvers: the grid == 1 step of both SPIN recursions (paper
Algorithm 2, `if` branch), on the one device that holds the block.

`LEAF_SOLVERS` inverts one bs×bs block; `leaf_solve` solves one bs×bs
system for a panel of right-hand sides. The block containers
(`BlockMatrix.leaf_inverse`), the recursions (`core.recursion`) and the
planner (`planner.plan`) name solvers by their key here.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from .precision import dot_precision

__all__ = ["LEAF_SOLVERS", "leaf_solve"]


def _leaf_linalg(block: jax.Array) -> jax.Array:
    # LAPACK-style getrf/getri; the oracle everything else is tested against.
    f32 = block.astype(jnp.float32)
    return jnp.linalg.inv(f32).astype(block.dtype)


def _leaf_gauss_jordan(block: jax.Array) -> jax.Array:
    # Pallas scalar Gauss-Jordan kernel (TPU target, interpret=True on CPU).
    from repro.kernels.leaf_inverse import ops as gj_ops

    return gj_ops.leaf_inverse(block)


def _leaf_pallas(block: jax.Array) -> jax.Array:
    # Pallas BLOCKED Gauss-Jordan: panel elimination with rank-t MXU updates
    # (kernels/leaf_inverse.blocked_leaf_inverse_pallas) — the leaf half of
    # the `pallas` engine family.
    from repro.kernels.leaf_inverse import ops as gj_ops

    return gj_ops.blocked_leaf_inverse(block)


def _leaf_qr(block: jax.Array) -> jax.Array:
    f32 = block.astype(jnp.float32)
    q, r = jnp.linalg.qr(f32)
    n = block.shape[-1]
    rinv = jax.scipy.linalg.solve_triangular(r, jnp.eye(n, dtype=jnp.float32))
    return jnp.matmul(rinv, q.T, precision=dot_precision(jnp.float32)
                      ).astype(block.dtype)


LEAF_SOLVERS: dict[str, Callable[[jax.Array], jax.Array]] = {
    "linalg": _leaf_linalg,
    "gauss_jordan": _leaf_gauss_jordan,
    "pallas": _leaf_pallas,
    "qr": _leaf_qr,
}


def leaf_solve(block: jax.Array, rhs: jax.Array, solver: str) -> jax.Array:
    """Solve the grid==1 system with the shared leaf-solver registry.

    `linalg` uses the LAPACK solve directly (cheaper + better conditioned
    than inverse-then-multiply); `pallas` factorizes with XLA's LU and runs
    both substitution sweeps through the blocked Pallas triangular-solve
    kernel — also inverse-free, with the O(bs²·k) substitutions on the
    kernel path; the remaining kernel-backed solvers go through their
    explicit inverse, which is the point of having them pluggable.
    """
    f32 = block.astype(jnp.float32)
    r32 = rhs.astype(jnp.float32)
    if solver == "linalg":
        return jnp.linalg.solve(f32, r32).astype(rhs.dtype)
    if solver == "pallas":
        from repro.kernels.leaf_inverse import ops as tri_ops  # late import

        lu, _, perm = jax.lax.linalg.lu(f32)
        y = tri_ops.triangular_solve(lu, r32[perm], lower=True,
                                     unit_diagonal=True)
        x = tri_ops.triangular_solve(lu, y, lower=False)
        return x.astype(rhs.dtype)
    inv = LEAF_SOLVERS[solver](block)
    return jnp.matmul(inv.astype(jnp.float32), r32,
                      precision=dot_precision(jnp.float32)).astype(rhs.dtype)
