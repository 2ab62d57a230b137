"""The two SPIN recursions, each written once for every placement of blocks.

Strassen's block-recursive inversion (paper Algorithm 1/2, §3.1), one node:

    I    <- Inverse(A11)                   leaf (grid == 1):
    II   <- A21 . I                        invert the single block locally
    III  <- I . A12                        (core.leaf: Pallas Gauss-Jordan
    IV   <- A21 . III                       kernel or jnp.linalg.inv oracle)
    V    <- IV - A22
    VI   <- Inverse(V)
    C12  <- III . VI
    C21  <- VI . II
    VII  <- III . C21
    C11  <- I - VII
    C22  <- -VI

Exactly 6 distributed multiplies + 2 subtracts + 1 scalarMul per level and
ONE local O(bs^3) op per leaf — vs the LU baseline's ~9x leaf work and extra
multiplies (see lu_inverse.py and costmodel.py). Valid for matrices whose
leading principal blocks are invertible (SPD in particular — the class the
paper targets).

The inverse-free solve reuses the same quadrant products (I/III/V) in their
Schur form, for SPD `A` and a block of right-hand sides `B`:

    [A11 A12] [X1]   [B1]      III = A11⁻¹ A12   (recursive solve)
    [A21 A22] [X2] = [B2]      Y1  = A11⁻¹ B1    (same recursive call —
                                                  the RHS blocks ride along)
    V  = A21·III − A22         (= −Schur complement, the paper's V)
    X2 = V⁻¹ (A21·Y1 − B2)     (recursive solve on V)
    X1 = Y1 − III·X2

Per level this is 2 recursive solves + 3 block-times-panel products — it
drops the 3 quadrant-assembly multiplies (C12, C21, VII) and the arrange
that full inversion pays, and the only dense objects ever formed are n×(n/2)
panels, never A⁻¹.

Both recursions are structural (depth = log2(b) fixed at trace time), so a
jitted entry compiles the ENTIRE multi-level algorithm into one XLA program
— no per-level Spark job scheduling (DESIGN.md §11). They walk a node
through the block container's own operations, so the same walk runs a
`BlockMatrix` on one device and a `repro.parallel.ShardedBlockMatrix` on a
mesh; what differs is the container's placement hooks (`BlockMatrix`):
how a node splits and arranges, where each product is placed, how solve
panels are placed and stacked, what a leaf books, and whether the two
Schur steps fuse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs.trace import TRACER as _TRACER
from repro.obs.trace import level_scope, step_scope

from .blockmatrix import BlockMatrix, _bump
from .leaf import leaf_solve
from .multiply import (_accum_dtype, current_engine, multiply,
                       multiply_subtract, subtract_multiply)
from .precision import dot_precision

__all__ = ["invert", "solve"]


def invert(a: BlockMatrix, leaf_solver: str = "linalg",
           level: int = 0) -> BlockMatrix:
    """Algorithm-2 inversion of a block container (grid must be 2^m).

    `level` is the recursion depth. Each internal node runs under the
    named scope `spin.L<level>` with one scope per step, and a leaf under
    `spin.L<level>/leaf` (repro.obs.trace; HLO metadata only, always on).
    Under $SPIN_TRACE each node and leaf also records a
    kind="recursion_level" point event at trace time.
    """
    b = a.grid
    if b & (b - 1):
        raise ValueError(f"grid must be a power of two, got {b}")
    if b == 1:
        if _TRACER.enabled:
            _TRACER.event("spin.leaf", "recursion_level", level=level,
                          grid=1, op="leaf", solver=leaf_solver,
                          block_size=a.block_size,
                          dtype=str(a.blocks.dtype))
        with level_scope(level), step_scope("leaf"):
            return a.leaf_inverse(leaf_solver)

    if _TRACER.enabled:
        _TRACER.event("spin.level", "recursion_level", level=level, grid=b,
                      op="inverse_node", block_size=a.block_size,
                      dtype=str(a.blocks.dtype),
                      engine=current_engine() or "einsum")
    with level_scope(level):
        with step_scope("split"):
            (a11, a12, a21, a22), arrange = a.node_split()
        i_ = invert(a11, leaf_solver, level + 1)          # I   = A11^-1
        with step_scope("II"):
            ii = multiply(a21, i_)                        # II  = A21 I
        with step_scope("III"):
            iii = multiply(i_, a12)                       # III = I A12
        # IV = A21·III and V = IV − A22 (= −Schur) as ONE fused Schur
        # update: bitwise-identical multiply-then-subtract on the XLA
        # engines, a single Pallas kernel under engine="pallas". Op counts
        # book 1 multiply + 1 subtract either way.
        with step_scope("schur"):
            v = multiply_subtract(a21, iii, a22)
        vi = invert(v, leaf_solver, level + 1)            # VI  = V^-1
        with step_scope("C12"):
            c12 = multiply(iii, vi)
        with step_scope("C21"):
            c21 = multiply(vi, ii)
        # VII = III·C21 and C11 = I − VII, same fused Schur-update contract.
        with step_scope("C11"):
            c11 = subtract_multiply(i_, iii, c21)
        with step_scope("neg"):
            c22 = vi.neg()                                # scalarMul(VI, -1)
        with step_scope("arrange"):
            return arrange(c11, c12, c21, c22)


def _apply_blocks(a: BlockMatrix, x: jax.Array) -> jax.Array:
    """Distributed A·X for a BlockMatrix A and a dense (n, k) panel X.

    The panel is reshaped onto A's block rows so each (bs×bs)·(bs×k) product
    is a local GEMM; the k-axis stays replicated (RHS panels are thin
    relative to A). Accumulates in f32 like the multiply engines. Under the
    ``pallas`` engine the whole panel product runs as one fused kernel with
    the k-sum in VMEM scratch.
    """
    _bump("solve_applies")
    if current_engine() == "pallas":
        from repro.kernels.matmul import ops as mm_ops  # late: optional layer

        # out_dtype keeps the kernel's f32 accumulator un-rounded on the
        # flush: a bf16 block matrix must not squeeze an f32 RHS panel
        # through bf16 on the way out (the einsum branch below never does).
        out = mm_ops.matmul(mm_ops.blocks_to_dense(a.blocks), x,
                            out_dtype=_accum_dtype(a.blocks.dtype))
        return out.astype(x.dtype)
    b, _, bs, _ = a.blocks.shape
    xb = x.reshape(b, bs, x.shape[-1])
    acc = _accum_dtype(a.blocks.dtype)
    out = jnp.einsum("ijab,jbk->iak", a.blocks, xb,
                     preferred_element_type=acc,
                     precision=dot_precision(a.blocks.dtype, xb.dtype))
    return out.reshape(b * bs, x.shape[-1]).astype(x.dtype)


def _solve(a: BlockMatrix, b: jax.Array, leaf_solver: str) -> jax.Array:
    if a.grid == 1:
        _bump("leaf_solves")
        return a.place_panel(leaf_solve(a.blocks[0, 0], b, leaf_solver),
                             "leaf_solve")

    bs = a.block_size
    a11, a12, a21, a22 = a.split()
    half = a11.n
    b1, b2 = b[:half], b[half:]

    # One recursive solve covers both III (= A11⁻¹A12) and Y1 (= A11⁻¹B1):
    # the B1 columns ride along as extra RHS. On a mesh, column
    # concatenation is safe ONLY because both operands are first pinned to
    # row-only sharding (concat dim replicated); row stacking goes through
    # the container's `stack_rows` instead.
    z = _solve(a11, a.place_panel(jnp.concatenate(
        [a.place_panel(a12.to_dense(), "solve_rhs"),
         a.place_panel(b1, "solve_rhs")], axis=1), "solve_rhs"), leaf_solver)
    iii, y1 = z[:, :half], z[:, half:]

    v = (a.place_panel(_apply_blocks(a21, iii), "solve_apply")
         - a22.to_dense())                                # −Schur complement
    _bump("subtracts")
    rhs2 = a.place_panel(_apply_blocks(a21, y1), "solve_apply") - b2
    _bump("subtracts")
    x2 = _solve(a.placed(BlockMatrix.from_dense(v, bs).blocks, "from_dense"),
                a.place_panel(rhs2, "solve_rhs"), leaf_solver)

    acc = _accum_dtype(iii.dtype)
    _bump("solve_applies")                                # III·X2 panel GEMM
    x1 = y1 - jnp.matmul(iii, x2, preferred_element_type=acc,
                         precision=dot_precision(iii.dtype, x2.dtype)
                         ).astype(y1.dtype)
    _bump("subtracts")
    return a.stack_rows(x1, x2)


def solve(a: BlockMatrix, b: jax.Array, leaf_solver: str = "linalg"
          ) -> jax.Array:
    """Solve A X = B by the inverse-free Schur recursion; B (n, k) or (n,).

    Returns X with b's shape; never materializes A⁻¹.
    """
    grid = a.grid
    if grid & (grid - 1):
        raise ValueError(f"grid must be a power of two, got {grid}")
    if b.shape[0] != a.n:
        raise ValueError(f"rhs rows {b.shape[0]} != matrix dim {a.n}")
    vector = b.ndim == 1
    rhs = b[:, None] if vector else b
    x = _solve(a, a.place_panel(rhs, "solve_rhs"), leaf_solver)
    return x[:, 0] if vector else x
