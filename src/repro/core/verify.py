"""SPIN conformance harness: residual checks + the paper's op-count oracle.

Three layers, all reusable from tests, benchmarks, and ad-hoc scripts:

  * dtype-aware residual checks — `inverse_residual` / `solve_residual`
    compute ‖AX − I‖∞ / ‖AX − B‖∞ (normalized), and `residual_tolerance`
    maps a storage dtype to the bound a correct implementation must meet
    (f32 recursion ⇒ 1e-3-grade residuals; bf16 storage ⇒ 2e-2).
  * the op-count oracle — `expected_spin_counts(grid)` is the closed form of
    paper Algorithm 2's costs (6 multiplies, 2 subtract-class, 1 scalarMul
    per internal node; one leaf inversion per leaf), checked against what
    `count_ops()` actually recorded by `assert_paper_op_counts`.
  * the conformance sweep — `run_conformance` drives SPIN + spin_solve over
    the matrix-family zoo × grid sizes and returns structured reports; a
    non-empty `failures` list is the machine-readable verdict.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from .blockmatrix import BlockMatrix, OpCounts, count_ops
from .solve import spin_solve
from .spin import spin_inverse
from .testing import MATRIX_FAMILIES

__all__ = [
    "residual_tolerance", "inverse_residual", "solve_residual",
    "expected_spin_counts", "assert_paper_op_counts",
    "expected_strassen_counts", "expected_spin_strassen_counts",
    "assert_strassen_op_counts",
    "ConformanceReport", "run_conformance",
]

# Storage dtype -> max allowed normalized ∞-norm residual on the zoo's
# well-posed families. f64 is listed for completeness (x64 mode).
_RESIDUAL_TOL = {
    jnp.dtype(jnp.float64): 1e-9,
    jnp.dtype(jnp.float32): 1e-3,
    jnp.dtype(jnp.bfloat16): 2e-2,
    jnp.dtype(jnp.float16): 1e-2,
}


def residual_tolerance(dtype) -> float:
    """The residual bound a conformant implementation meets for `dtype`."""
    try:
        return _RESIDUAL_TOL[jnp.dtype(dtype)]
    except KeyError:
        raise ValueError(f"no conformance tolerance for dtype {dtype}")


def _inf_norm(x: jax.Array) -> jax.Array:
    return jnp.max(jnp.abs(x.astype(jnp.float32)))


def _product(a: jax.Array, x: jax.Array) -> jax.Array:
    # f32 at full precision: a residual taken with a TPU's default
    # one-pass bf16 dot would hide the error it exists to measure.
    return jnp.matmul(a.astype(jnp.float32), x.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def inverse_residual(a: jax.Array, x: jax.Array) -> float:
    """‖AX − I‖∞ / ‖I‖∞ (= ‖AX − I‖∞) for a claimed inverse X."""
    n = a.shape[-1]
    prod = _product(a, x)
    return float(_inf_norm(prod - jnp.eye(n, dtype=jnp.float32)))


def solve_residual(a: jax.Array, x: jax.Array, b: jax.Array) -> float:
    """‖AX − B‖∞ / ‖B‖∞ for a claimed solution X of AX = B."""
    prod = _product(a, x)
    return float(_inf_norm(prod - b.astype(jnp.float32))
                 / (_inf_norm(b) + 1e-30))


# ---------------------------------------------------------------------------
# Op-count oracle (paper Algorithm 2)
# ---------------------------------------------------------------------------


def expected_spin_counts(grid: int) -> OpCounts:
    """Closed-form op counts for SPIN on a b×b grid (b a power of two).

    The recursion tree over a grid of b = 2^m has 2^i internal nodes at
    level i, so b − 1 internal nodes total and b leaves. Each internal node
    performs exactly 6 distributed multiplies, 2 subtract-class ops
    (V = IV − A22 and C11 = I − VII), 1 scalarMul (C22 = −VI), 1 split and
    1 arrange; each leaf performs one local block inversion. Each multiply
    at a node of half-grid h contributes h³ block GEMMs.
    """
    if grid < 1 or grid & (grid - 1):
        raise ValueError(f"grid must be a power of two ≥ 1, got {grid}")
    internal = grid - 1
    gemms = 0
    level_nodes, h = 1, grid // 2
    while h >= 1:
        gemms += level_nodes * 6 * h ** 3
        level_nodes, h = level_nodes * 2, h // 2
    return OpCounts(
        multiplies=6 * internal,
        block_gemms=gemms,
        subtracts=2 * internal,
        scalar_muls=internal,
        leaf_inversions=grid,
        splits=internal,
        arranges=internal,
    )


def assert_paper_op_counts(grid: int, counts: OpCounts) -> None:
    """Assert `counts` (from count_ops over spin_inverse) match the paper.

    Engine-blind: the Strassen-internal counters are excluded here (a
    Strassen product is still ONE Algorithm-2 multiply) and checked by
    their own oracle, `assert_strassen_op_counts`; so are the Pallas
    kernels' grid-step and leaf-panel counts, which follow their tiles
    and panels, and so are the
    mesh counters (`gather_bytes`, `replicated_*`, `local_*`), which
    follow the placement.
    """
    want = expected_spin_counts(grid)
    got = counts.as_dict()
    mismatches = {
        k: (got[k], v) for k, v in want.as_dict().items()
        if k in got and got[k] != v
        and k not in ("leaf_lu", "leaf_solves", "solve_applies",
                      "strassen_base_multiplies", "strassen_adds",
                      "pallas_grid_steps", "leaf_panels", "gather_bytes",
                      "replicated_block_gemms", "replicated_leaves",
                      "local_splits", "local_arranges")
    }
    if mismatches:
        raise AssertionError(
            f"op counts diverge from paper Algorithm 2 at grid {grid} "
            f"(got, want): {mismatches}")


def expected_strassen_counts(grid: int, block_size: int,
                             cutoff: int | None = None) -> tuple[int, int]:
    """(base_multiplies, adds) of ONE Strassen multiply on a grid×grid grid.

    Each split level performs exactly 7 recursive multiplies and 18
    quadrant add/sub passes; an odd grid pads to grid+1 before splitting.
    The recursion goes classical (1 base multiply, 0 adds) at grid == 1 or
    when the operand dimension grid·block_size is at/below the cutoff
    (None reads the live `strassen_cutoff()`), mirroring
    core.strassen.strassen_matmul_blocks exactly.
    """
    if cutoff is None:
        from .strassen import strassen_cutoff

        cutoff = strassen_cutoff()
    if grid == 1 or grid * block_size <= cutoff:
        return 1, 0
    padded = grid + (grid % 2)
    base, adds = expected_strassen_counts(padded // 2, block_size, cutoff)
    return 7 * base, 18 + 7 * adds


def expected_spin_strassen_counts(grid: int, block_size: int,
                                  cutoff: int | None = None
                                  ) -> tuple[int, int]:
    """Strassen-internal totals for one spin_inverse under engine='strassen'.

    Each internal node of the SPIN tree at half-grid h runs its 6
    Algorithm-2 multiplies (4 plain + 2 fused Schur updates — the fused
    route books identically) as Strassen multiplies on an h-grid.
    """
    if grid < 1 or grid & (grid - 1):
        raise ValueError(f"grid must be a power of two ≥ 1, got {grid}")
    total_base = total_adds = 0
    level_nodes, h = 1, grid // 2
    while h >= 1:
        base, adds = expected_strassen_counts(h, block_size, cutoff)
        total_base += level_nodes * 6 * base
        total_adds += level_nodes * 6 * adds
        level_nodes, h = level_nodes * 2, h // 2
    return total_base, total_adds


def assert_strassen_op_counts(grid: int, block_size: int, counts: OpCounts,
                              cutoff: int | None = None) -> None:
    """Assert the Strassen-internal counters match the 7/18 recurrence."""
    want = expected_spin_strassen_counts(grid, block_size, cutoff)
    got = (counts.strassen_base_multiplies, counts.strassen_adds)
    if got != want:
        raise AssertionError(
            f"Strassen op counts diverge at grid {grid} bs {block_size}: "
            f"(base_multiplies, adds) got {got}, want {want}")


# ---------------------------------------------------------------------------
# Conformance sweep
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ConformanceReport:
    family: str
    grid: int
    block_size: int
    dtype: str
    inverse_residual: float
    solve_residual: float
    tolerance: float
    op_counts_ok: bool
    path: str = "dense"                      # "dense" | "sharded"
    parity_vs_dense: float | None = None     # sharded only: rel. max |Δ|

    @property
    def ok(self) -> bool:
        return (self.op_counts_ok
                and self.inverse_residual < self.tolerance
                and self.solve_residual < self.tolerance
                and (self.parity_vs_dense is None
                     or self.parity_vs_dense < self.tolerance))

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["ok"] = self.ok
        return d


def run_conformance(grids: Sequence[int] = (2, 4, 8), block_size: int = 32,
                    n_rhs: int = 4, dtype=jnp.float32,
                    families: Sequence[str] = ("spd", "diag_dominant",
                                               "ill_conditioned_spd",
                                               "block_banded_spd"),
                    seed: int = 0,
                    sharded: bool = False) -> list[ConformanceReport]:
    """Sweep SPIN inversion + multi-RHS solve over the zoo; return reports.

    Every report's `.ok` must hold for a conformant build; callers assert
    `not [r for r in reports if not r.ok]`.

    sharded=True runs the mesh-resident recursion
    (repro.parallel.sharded_blockmatrix) instead of the dense one — same
    op-count oracle, since the sharded ops bump the same counters — and
    additionally records `parity_vs_dense`, the relative max deviation from
    the dense path's result, which `.ok` holds to the same dtype tolerance.
    Run it under an active mesh (e.g. the tests' fake-device harness) to
    exercise real sharding; without one it degrades to the dense semantics.
    """
    if sharded:
        from repro.parallel.sharded_blockmatrix import (
            ShardedBlockMatrix, sharded_spin_inverse, solve_program)

    reports = []
    key = jax.random.PRNGKey(seed)
    for family in families:
        gen = MATRIX_FAMILIES[family]
        for grid in grids:
            n = grid * block_size
            key, ka, kb = jax.random.split(key, 3)
            kwargs = {}
            if family == "ill_conditioned_spd":
                kwargs["cond"] = 1e4      # stress, but within f32 reach
            if family == "block_banded_spd":
                kwargs["band"] = block_size
            a = gen(n, ka, dtype=dtype, **kwargs)
            bm = BlockMatrix.from_dense(a, block_size)
            rhs = jax.random.normal(kb, (n, n_rhs), jnp.float32).astype(dtype)

            parity = None
            if sharded:
                sbm = ShardedBlockMatrix.from_blockmatrix(bm)
                with count_ops() as counts:
                    inv = sharded_spin_inverse(sbm)
                # The solve runs as the one jitted program users call:
                # op-by-op, each eager op's output sharding is converted
                # back to a mesh spec, which JAX cannot do for the
                # row-sharded reshapes on a (4, 2) mesh (KeyError).
                x = solve_program(sbm, rhs)
                inv_dense = inv.to_dense()
                ref = spin_inverse(bm).to_dense()
                parity = float(_inf_norm(inv_dense - ref)
                               / (_inf_norm(ref) + 1e-30))
            else:
                with count_ops() as counts:
                    inv = spin_inverse(bm)
                x = spin_solve(bm, rhs)
                inv_dense = inv.to_dense()
            try:
                assert_paper_op_counts(grid, counts)
                counts_ok = True
            except AssertionError:
                counts_ok = False

            tol = residual_tolerance(dtype)
            if family == "ill_conditioned_spd":
                # residual scales with κ·ε; κ=1e4 in f32 eats ~2-3 digits
                tol = tol * 1e2
            reports.append(ConformanceReport(
                family=family, grid=grid, block_size=block_size,
                dtype=str(jnp.dtype(dtype)),
                inverse_residual=inverse_residual(a, inv_dense),
                solve_residual=solve_residual(a, x, rhs),
                tolerance=tol, op_counts_ok=counts_ok,
                path="sharded" if sharded else "dense",
                parity_vs_dense=parity,
            ))
    return reports
