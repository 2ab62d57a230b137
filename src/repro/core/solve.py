"""Batched / multi-RHS solve subsystem on the SPIN recursion.

`spin_solve` answers the workload the paper's users actually have (ridge
regression, Shampoo preconditioning, Earth-science normal equations): given
SPD `A` and a block of right-hand sides `B`, produce `X = A⁻¹B` WITHOUT
materializing `A⁻¹` and multiplying. It reuses the SPIN recursion's quadrant
products (paper Algorithm 2's I/III/V names) in their inverse-free Schur
form (`core.recursion.solve`, which also serves the mesh). Leaf systems go
through the same pluggable leaf solvers as `spin_inverse` (`core.leaf`).

`spin_inverse_batched` maps the whole SPIN recursion over a leading batch
axis of SPD matrices — the shape Shampoo's stacked-layer factor refresh
needs (L, d, d) — compiling ONE program for the batch instead of L;
`batched_op_scopes` maps that program's instructions to their scopes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.obs.trace import BATCH, hlo_op_scopes, host_span, step_scope

from .blockmatrix import BlockMatrix, _bump
from .multiply import current_engine, multiply_engine, validate_engine
from .precision import dot_precision
from .recursion import solve
from .spin import spin_inverse_dense

__all__ = ["spin_solve", "spin_solve_dense", "spin_solve_sharded",
           "spin_inverse_batched", "batched_op_scopes", "solve_grid_for",
           "SketchedInverse", "sketched_approx_inverse"]


def solve_grid_for(n: int, max_grid: int = 8, min_block: int = 64) -> int:
    """Largest power-of-two grid ≤ max_grid dividing n with blocks ≥ min_block.

    Legacy manual heuristic, kept as a public utility for callers that want
    a grid without consulting the planner; production paths now use
    `repro.planner.planned_block_size` (cost-model-driven) instead.
    """
    g = 1
    while (g * 2 <= max_grid and n % (g * 2) == 0
           and n // (g * 2) >= min_block):
        g *= 2
    return g


def spin_solve(a: BlockMatrix, b: jax.Array, *,
               leaf_solver: str = "linalg", auto: bool = False,
               precision=None) -> jax.Array:
    """Solve A X = B for multi-RHS B via the inverse-free SPIN recursion.

    a: BlockMatrix with power-of-two grid (SPD / leading-blocks-invertible,
       the paper's class). b: (n, k) or (n,) right-hand side(s).
    Returns X with b's shape; never materializes A⁻¹. auto=True asks the
    planner for the leaf solver (the grid is fixed by `a`'s structure).
    precision (PrecisionPolicy | preset string | None) runs the recursion's
    GEMMs at the policy's compute dtype (f32 accumulation as always) and
    returns X at b's dtype; the default is bitwise-unchanged.
    """
    if auto:
        from repro.planner import planned_leaf_solver

        leaf_solver = planned_leaf_solver(a.n, a.block_size, a.dtype,
                                          kind="solve")
    if precision is not None:
        from .precision import resolve_precision
        from .spin import _policy_active

        policy = resolve_precision(precision)
        if not policy.is_exact and _policy_active(policy, a.blocks.dtype):
            cd = jnp.dtype(policy.resolve_compute(a.blocks.dtype))
            x = spin_solve(BlockMatrix(a.blocks.astype(cd)), b.astype(cd),
                           leaf_solver=leaf_solver)
            return x.astype(b.dtype)
    return solve(a, b, leaf_solver)


@functools.partial(jax.jit,
                   static_argnames=("block_size", "leaf_solver", "engine"))
def _spin_solve_dense(a: jax.Array, b: jax.Array, block_size: int,
                      leaf_solver: str = "linalg",
                      engine: str | None = None) -> jax.Array:
    # `engine` is static for the same reason as in _spin_inverse_dense: the
    # multiply engine is resolved at trace time from a contextvar.
    ctx = multiply_engine(engine) if engine else contextlib.nullcontext()
    with ctx:
        return spin_solve(BlockMatrix.from_dense(a, block_size), b,
                          leaf_solver=leaf_solver)


def spin_solve_dense(a: jax.Array, b: jax.Array,
                     block_size: int | None = None,
                     leaf_solver: str = "linalg", *,
                     engine: str | None = None,
                     auto: bool = False,
                     precision=None,
                     compute_dtype=None) -> jax.Array:
    """Convenience: dense (n,n) A, (n,k) B -> X, jitted end to end.

    auto=True (or block_size=None) routes through the planner; the planned
    path re-enters this function with explicit static arguments, so it is
    bitwise identical to the equivalent explicit call. engine=None inherits
    the ambient `multiply_engine` context — resolved BEFORE the jit
    boundary so the concrete engine is always the static cache key.
    precision (PrecisionPolicy | preset string | None→$SPIN_PRECISION/exact)
    runs the solve at the policy's compute dtype and returns X at b's
    dtype; `compute_dtype=` is the deprecated spelling and forwards with a
    one-time warning.
    """
    validate_engine(engine)
    from .precision import resolve_precision
    from .spin import _policy_active

    if compute_dtype is not None:
        from .precision import (policy_from_compute_dtype,
                                warn_deprecated_dtype_kwarg)

        warn_deprecated_dtype_kwarg("spin_solve_dense")
        if precision is None:
            precision = policy_from_compute_dtype(compute_dtype)
    policy = resolve_precision(precision)
    active = not policy.is_exact and _policy_active(policy, a.dtype)
    if auto or block_size is None:
        from repro.planner import plan_solve

        if not active:
            return plan_solve(a, b)
        cd = policy.resolve_compute(a.dtype)
        return plan_solve(a.astype(cd), b.astype(cd),
                          precision=policy).astype(b.dtype)
    if active:
        cd = policy.resolve_compute(a.dtype)
        return _spin_solve_dense(a.astype(cd), b.astype(cd), block_size,
                                 leaf_solver,
                                 engine or current_engine()).astype(b.dtype)
    return _spin_solve_dense(a, b, block_size, leaf_solver,
                             engine or current_engine())


def spin_solve_sharded(a, b: jax.Array, block_size: int | None = None, *,
                       leaf_solver: str | None = None,
                       engine: str | None = None,
                       auto: bool = False,
                       precision=None) -> jax.Array:
    """Mesh-resident multi-RHS solve: one pjit program, row-sharded panels.

    The inverse-free Schur recursion with every dense panel pinned to the
    `data` axis between levels (see repro.parallel.sharded_blockmatrix).
    `a`: dense (n, n) array (block_size required unless auto/planner),
    BlockMatrix, or ShardedBlockMatrix; `b`: (n, k) or (n,). Returns X with
    b's shape; never materializes A⁻¹. auto=True consults the planner under
    the sharded placement; explicit block_size / leaf_solver / engine
    arguments always override the planner's choices.
    """
    from repro.parallel.sharded_blockmatrix import solve_program

    from .spin import _policy_active, _resolve_sharded_config

    validate_engine(engine)
    if precision is not None:
        from .precision import resolve_precision

        policy = resolve_precision(precision)
        dense_in = not isinstance(a, BlockMatrix)
        if not policy.is_exact and _policy_active(
                policy, a.dtype if dense_in else a.blocks.dtype):
            if not dense_in:
                raise ValueError(
                    "low-precision policies on the sharded solve path need "
                    f"a dense operand; got {type(a).__name__}")
            cd = policy.resolve_compute(a.dtype)
            return spin_solve_sharded(a.astype(cd), b.astype(cd), block_size,
                                      leaf_solver=leaf_solver, engine=engine,
                                      auto=auto).astype(b.dtype)
    a, leaf_solver, engine, _ = _resolve_sharded_config(
        "solve", a, block_size, leaf_solver, engine, auto)
    return solve_program(a, b, leaf_solver=leaf_solver, engine=engine)


# ---------------------------------------------------------------------------
# Degraded-mode (sketched) approximate inverse — DESIGN.md §10
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SketchedInverse:
    """A servable approximate inverse with its reported residual bound."""

    inverse: jax.Array        # dense (n, n), caller's dtype
    residual_est: float       # probe estimate of ‖A X − I‖∞ at return
    sweeps: int               # Newton–Schulz sweeps spent
    converged: bool           # residual_est ≤ tol when we stopped


def sketched_approx_inverse(a: jax.Array, key: jax.Array, *,
                            block_size: int | None = None,
                            tol: float | None = None, max_sweeps: int = 60,
                            probes: int = 2) -> SketchedInverse:
    """Approximate A⁻¹ servable before (or without) the full recursion.

    The degraded-mode path of the straggler-robust layer: when too many
    workers are lost or a shard hangs, the service must still answer with a
    *bounded, reported* residual. Recipe (per PAPERS.md's straggler-robust
    inverse approximation): a randomized sketch — power iteration on AᵀA
    with a random probe — estimates σ_max, seeding X₀ = Aᵀ/(1.1·σ̂²), for
    which ‖I − AX₀‖₂ < 1 for ANY nonsingular A; Newton–Schulz sweeps
    (core.newton_schulz — two BlockMatrix multiplies each, inheriting the
    active multiply engine) then polish quadratically, and the DriftTracker
    probe machinery (core.update.estimate_inverse_residual) is re-used to
    measure the residual after every sweep, stopping at `tol`.

    tol=None uses `verify.residual_tolerance(a.dtype)`. Returns a
    SketchedInverse whose `residual_est` is the value the serving layer
    reports alongside degraded answers.
    """
    from .newton_schulz import newton_schulz_polish
    from .update import estimate_inverse_residual
    from .verify import residual_tolerance

    n = a.shape[0]
    dtype = a.dtype
    if tol is None:
        tol = residual_tolerance(dtype)
    f32 = a.astype(jnp.float32)

    # Randomized sketch of σ_max² (8 power steps on AᵀA; the 1.1 safety
    # factor keeps α·σ_max² < 2 — the Newton–Schulz convergence condition —
    # under mild power-iteration underestimation).
    key, sub = jax.random.split(key)
    v = jax.random.normal(sub, (n,), dtype=jnp.float32)
    hi = dot_precision(jnp.float32)

    def gram(v):
        return jnp.matmul(f32.T, jnp.matmul(f32, v, precision=hi),
                          precision=hi)

    for _ in range(8):
        v = gram(v)
        v = v / jnp.linalg.norm(v)
    sigma2 = float(jnp.linalg.norm(gram(v)))
    x0 = f32.T / (1.1 * sigma2)

    bs = block_size or n // solve_grid_for(n)
    a_bm = BlockMatrix.from_dense(f32, bs)
    x = BlockMatrix.from_dense(x0, bs)

    def probe_residual(x_bm: BlockMatrix, k: jax.Array) -> float:
        return float(estimate_inverse_residual(
            lambda p: jnp.matmul(f32, p, precision=hi), x_bm.to_dense(), k, n,
            probes=max(1, probes)))

    key, sub = jax.random.split(key)
    residual = probe_residual(x, sub)
    sweeps = 0
    while residual > tol and sweeps < max_sweeps:
        x = newton_schulz_polish(a_bm, x, sweeps=1)
        sweeps += 1
        key, sub = jax.random.split(key)
        residual = probe_residual(x, sub)
    return SketchedInverse(inverse=x.to_dense().astype(dtype),
                           residual_est=residual, sweeps=sweeps,
                           converged=residual <= tol)


def spin_inverse_batched(batch: jax.Array, block_size: int | None = None,
                         leaf_solver: str = "linalg", *,
                         engine: str | None = None,
                         precision=None,
                         compute_dtype=None) -> jax.Array:
    """SPIN-invert a (batch, n, n) stack of SPD matrices in one program.

    block_size=None asks the planner (cost-model path, no measurement —
    safe under an enclosing jit trace) for the per-matrix block size.
    `engine` selects the multiply engine for every slice (static jit
    argument, like the dense entry points); None inherits the ambient
    `multiply_engine` context.

    Uses lax.map (a scan over the leading axis) rather than vmap: the scan
    body is the SAME traced computation as `spin_inverse_dense`, so each
    slice's result is bitwise identical to the per-matrix call — vmap's
    batched GEMM/getrf reassociate reductions and drift in the last ulp.
    The price is sequential execution over the stack inside the scan; if
    refresh latency on deep stacks ever outweighs exact reproducibility,
    swap in jax.vmap and relax the exactness test to allclose.
    One program is compiled for the whole stack either way, which is the
    batched L/R factor refresh Shampoo's stacked layers need.

    The call runs inside the always-on host span `spin.inverse_batched`;
    the scan's slicing and stacking run under the scope `spin.batch`, and
    tracing the program books the stack's depth as
    `OpCounts.batched_matrices`.
    """
    with host_span("spin.inverse_batched"):
        if batch.ndim != 3:
            raise ValueError(f"expected (batch, n, n), got {batch.shape}")
        validate_engine(engine)
        from .precision import resolve_precision
        from .spin import _policy_active

        if compute_dtype is not None:
            from .precision import (policy_from_compute_dtype,
                                    warn_deprecated_dtype_kwarg)

            warn_deprecated_dtype_kwarg("spin_inverse_batched")
            if precision is None:
                precision = policy_from_compute_dtype(compute_dtype)
        policy = resolve_precision(precision)
        if block_size is None:
            from repro.planner import planned_block_size

            block_size = planned_block_size(batch.shape[-1], batch.dtype)
        if not policy.is_exact and _policy_active(policy, batch.dtype):
            cd = policy.resolve_compute(batch.dtype)
            out = _spin_inverse_batched(batch.astype(cd), block_size,
                                        leaf_solver,
                                        engine or current_engine())
            return out.astype(policy.resolve_store(batch.dtype))
        return _spin_inverse_batched(batch, block_size, leaf_solver,
                                     engine or current_engine())


@functools.partial(jax.jit,
                   static_argnames=("block_size", "leaf_solver", "engine"))
def _spin_inverse_batched(batch: jax.Array, block_size: int,
                          leaf_solver: str = "linalg",
                          engine: str | None = None) -> jax.Array:
    _bump("batched_matrices", batch.shape[0])
    fn = functools.partial(spin_inverse_dense, block_size=block_size,
                           leaf_solver=leaf_solver, engine=engine)
    with step_scope(BATCH):
        return jax.lax.map(fn, batch)


def batched_op_scopes(batch: int, n: int, block_size: int,
                      leaf_solver: str = "linalg",
                      engine: str | None = None, *, sharding=None
                      ) -> dict[str, dict[str, tuple]]:
    """{module: {instruction: (level, step)}} of the two programs that
    `optim.spin_shampoo.invert_spd` runs on a (batch, n, n) float32 stack:
    its damping and `spin_inverse_batched`'s scan. Like
    `core.spin.inverse_op_scopes`, it compiles the same jitted entries from
    shapes (a cache hit once the caller has run them); give the `sharding`
    of an operand committed to a device."""
    from repro.optim.spin_shampoo import damp

    shape = jax.ShapeDtypeStruct((batch, n, n), jnp.float32,
                                 sharding=sharding)
    texts = [damp.lower(shape, 0.0).compile().as_text(),
             _spin_inverse_batched.lower(
                 shape, block_size, leaf_solver,
                 engine or current_engine()).compile().as_text()]
    return {mod: ops for text in texts
            for mod, ops in hlo_op_scopes(text).items()}
