"""SPIN's inversion entry points: a block container, a dense matrix, a mesh.

`spin_inverse` runs the paper's Algorithm 2 (`core.recursion.invert`) on a
block container; `spin_inverse_dense` jits it end to end for a dense
matrix; `spin_inverse_sharded` runs the same recursion on a
`ShardedBlockMatrix`, whose blocks stay on the mesh, as one program
(`repro.parallel.sharded_blockmatrix`). Each entry resolves the planner's
choices and the precision policy before the recursion is traced.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from repro.obs.trace import LAYOUT
from repro.obs.trace import hlo_op_scopes, host_span, step_scope

from .blockmatrix import BlockMatrix
from .multiply import current_engine, multiply_engine, validate_engine
from .recursion import invert

__all__ = ["spin_inverse", "spin_inverse_dense", "spin_inverse_sharded",
           "inverse_op_scopes"]


def _policy_active(policy, operand_dtype) -> bool:
    """True when `policy` changes the compute or storage dtype for this
    operand (an "auto" policy over an already-matching dtype is a no-op —
    running its polish anyway would change bits for nothing)."""
    name = jnp.dtype(operand_dtype).name
    return (policy.resolve_store(name) != name
            or policy.resolve_compute(name) != name)


def _lowp_inverse_blocks(a: BlockMatrix, leaf_solver: str,
                         policy) -> BlockMatrix:
    """Low-precision BlockMatrix inversion: recurse at the policy's compute
    dtype, Newton–Schulz-polish in f32, store at the policy's store dtype."""
    op = a.blocks.dtype
    cd = jnp.dtype(policy.resolve_compute(op))
    x = spin_inverse(BlockMatrix(a.blocks.astype(cd)),
                     leaf_solver=leaf_solver)
    if policy.polish_sweeps:
        from .newton_schulz import newton_schulz_polish

        a32 = BlockMatrix(a.blocks.astype(jnp.float32))
        x32 = BlockMatrix(x.blocks.astype(jnp.float32))
        x = newton_schulz_polish(a32, x32, sweeps=policy.polish_sweeps)
    return BlockMatrix(x.blocks.astype(jnp.dtype(policy.resolve_store(op))))


def spin_inverse(a: BlockMatrix, *, leaf_solver: str = "linalg",
                 auto: bool = False, precision=None,
                 _level: int = 0) -> BlockMatrix:
    """Distributed Strassen inversion of a BlockMatrix (grid must be 2^m).

    auto=True consults the planner (repro.planner) for the leaf solver —
    the block grid is already fixed by `a`'s structure. The result is
    bitwise identical to passing the planned solver explicitly.
    precision (PrecisionPolicy | preset string | None→env/exact) runs the
    recursion at the policy's compute dtype, polishes with Newton–Schulz in
    f32, and returns blocks at the policy's store dtype; the default is
    bitwise-unchanged.

    `_level` is the depth `a` sits at, which names its scopes
    (`core.recursion.invert`).
    """
    if auto:
        from repro.planner import planned_leaf_solver

        leaf_solver = planned_leaf_solver(a.n, a.block_size, a.dtype)
    if precision is not None:
        from .precision import resolve_precision

        policy = resolve_precision(precision)
        if not policy.is_exact and _policy_active(policy, a.blocks.dtype):
            return _lowp_inverse_blocks(a, leaf_solver, policy)
    return invert(a, leaf_solver, _level)


@functools.partial(jax.jit,
                   static_argnames=("block_size", "leaf_solver", "engine"))
def _spin_inverse_dense(dense: jax.Array, block_size: int,
                        leaf_solver: str = "linalg",
                        engine: str | None = None) -> jax.Array:
    # `engine` must be a STATIC argument: the multiply engine is read from a
    # contextvar at trace time, so without it in the jit key a cached
    # executable traced under one engine would silently serve another.
    ctx = multiply_engine(engine) if engine else contextlib.nullcontext()
    with ctx:
        with step_scope(LAYOUT):
            a = BlockMatrix.from_dense(dense, block_size)
        x = spin_inverse(a, leaf_solver=leaf_solver)
        with step_scope(LAYOUT):
            return x.to_dense()


def inverse_op_scopes(n: int, block_size: int, leaf_solver: str = "linalg",
                      engine: str | None = None, *, mesh=None, sharding=None
                      ) -> dict[str, dict[str, tuple]]:
    """{module: {instruction: (level, step)}} of the inversion program that
    `spin_inverse_dense` (or, given `mesh`, `spin_inverse_sharded`) runs for
    an (n, n) float32 operand: the join from a profiler trace's op names to the
    recursion's named scopes (`repro.obs.trace.op_scope`).

    It lowers and compiles the same jitted entry from shapes, so once the
    caller has run it the compile is a cache hit. The executable depends on
    where the operand lives: give the `sharding` of the caller's operand
    when it was committed to a device (`jax.device_put`, or made there),
    None when it was not; on a mesh the blocks carry the recursion's grid
    sharding, which is used in its place. The instruction names are those of the executable
    the backend built, which are the names a device trace gives its ops.
    """
    engine = engine or current_engine()
    if mesh is None:
        shape = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=sharding)
        lowered = _spin_inverse_dense.lower(shape, block_size, leaf_solver,
                                            engine)
    else:
        from repro.parallel.sharded_blockmatrix import lower_inverse_program

        lowered = lower_inverse_program(n, block_size, leaf_solver, engine,
                                        mesh)
    return hlo_op_scopes(lowered.compile().as_text())


@functools.partial(jax.jit, static_argnames=("block_size", "engine",
                                             "sweeps"))
def _polish_dense(dense: jax.Array, approx: jax.Array, block_size: int,
                  engine: str | None, sweeps: int) -> jax.Array:
    # One program, not op-by-op: eager sweeps over an n=16384 pair hold
    # several transient f32 copies of the matrix at once (a TPU's HBM
    # peaks near full); jitted, XLA schedules and reuses the buffers.
    from .newton_schulz import newton_schulz_polish

    a32 = BlockMatrix.from_dense(dense.astype(jnp.float32), block_size)
    x32 = BlockMatrix.from_dense(approx.astype(jnp.float32), block_size)
    ctx = multiply_engine(engine) if engine else contextlib.nullcontext()
    with ctx:
        return newton_schulz_polish(a32, x32, sweeps=sweeps).to_dense()


def _lowp_inverse_dense(dense: jax.Array, block_size: int, leaf_solver: str,
                        engine: str | None, policy) -> jax.Array:
    """Dense low-precision inversion: recursion at the policy's compute
    dtype, f32 Newton–Schulz polish, result at the policy's store dtype."""
    cd = policy.resolve_compute(dense.dtype)
    approx = _spin_inverse_dense(dense.astype(cd), block_size, leaf_solver,
                                 engine)
    if policy.polish_sweeps:
        approx = _polish_dense(dense, approx, block_size, engine,
                               policy.polish_sweeps)
    return approx.astype(policy.resolve_store(dense.dtype))


def spin_inverse_dense(dense: jax.Array, block_size: int | None = None,
                       leaf_solver: str = "linalg", *,
                       engine: str | None = None,
                       auto: bool = False,
                       precision=None,
                       compute_dtype=None) -> jax.Array:
    """Convenience: dense (n,n) -> dense (n,n) inverse via SPIN.

    With auto=True (or block_size=None) the planner picks block size, leaf
    solver, and multiply engine; the planned execution calls this very
    function with the chosen static arguments, so `auto=True` is bitwise
    identical to the explicit call for plans without a refinement stage.
    engine=None inherits the ambient `multiply_engine` context — resolved
    HERE, before the jit boundary, so the concrete engine name is always
    the static cache key (an executable traced under one ambient engine
    must never be served under another).

    precision (PrecisionPolicy | preset string | None→$SPIN_PRECISION/exact)
    runs the recursion at the policy's compute dtype, polishes in f32, and
    returns the policy's store dtype; combined with auto=True the policy
    rides the planner signature so the plan is priced (and cached) per
    policy. `compute_dtype=` is the deprecated pre-policy spelling and
    forwards to an equivalent policy with a one-time warning.

    The call runs inside the always-on host span `spin.inverse_dense`
    (argument and precision resolution, and the jit dispatch), on the
    profiler's clock.
    """
    with host_span("spin.inverse_dense"):
        validate_engine(engine)
        from .precision import resolve_precision

        if compute_dtype is not None:
            from .precision import (policy_from_compute_dtype,
                                    warn_deprecated_dtype_kwarg)

            warn_deprecated_dtype_kwarg("spin_inverse_dense")
            if precision is None:
                precision = policy_from_compute_dtype(compute_dtype)
        policy = resolve_precision(precision)
        if auto or block_size is None:
            from repro.planner import plan_inverse

            if policy.is_exact:
                return plan_inverse(dense)
            return plan_inverse(dense, precision=policy)
        if not policy.is_exact and _policy_active(policy, dense.dtype):
            return _lowp_inverse_dense(dense, block_size, leaf_solver,
                                       engine or current_engine(), policy)
        return _spin_inverse_dense(dense, block_size, leaf_solver,
                                   engine or current_engine())


def _resolve_sharded_config(kind: str, a, block_size: int | None,
                            leaf_solver: str | None, engine: str | None,
                            auto: bool):
    """Shared planner dispatch for the sharded entry points.

    Returns (ShardedBlockMatrix, leaf_solver, engine, dense_in). Explicit
    arguments always win: a given block_size constrains the plan's candidate
    space instead of being clobbered, and explicit leaf_solver/engine are
    kept over the planner's picks. The planner is consulted cost-model-only
    here (measurement of sharded plans goes through the planner's own
    `execute_* (placement="sharded")`).
    """
    from repro.parallel.sharded_blockmatrix import ShardedBlockMatrix

    dense_in = not isinstance(a, BlockMatrix)
    n = a.shape[0] if dense_in else a.n
    if auto or (dense_in and block_size is None):
        from repro.planner import get_plan

        fixed = block_size if dense_in else a.block_size
        kw = {"block_sizes": (int(fixed),)} if fixed else {}
        plan = get_plan(kind, int(n), a.dtype, measure=False,
                        placement="sharded", **kw)
        if dense_in and block_size is None:
            block_size = plan.block_size
        leaf_solver = leaf_solver or plan.leaf_solver
        engine = engine or plan.multiply_engine

    if dense_in:
        a = ShardedBlockMatrix.from_dense(a, block_size)
    elif not isinstance(a, ShardedBlockMatrix):
        a = ShardedBlockMatrix.from_blockmatrix(a)
    return a, leaf_solver or "linalg", engine, dense_in


def spin_inverse_sharded(a, block_size: int | None = None, *,
                         leaf_solver: str | None = None,
                         engine: str | None = None, auto: bool = False,
                         coded=None, fault_plan=None, precision=None):
    """Mesh-resident SPIN inversion: one pjit program, no inter-level gathers.

    The whole Algorithm-2 recursion — quadrant views, 6 multiplies,
    subtracts, leaf inversions — executes as ONE jitted program whose
    intermediates carry explicit grid-over-mesh sharding constraints
    (see repro.parallel.sharded_blockmatrix), so blocks stay device-resident
    between recursion levels instead of replicating.

    `a`: dense (n, n) array (block_size required unless auto/planner),
    BlockMatrix, or ShardedBlockMatrix. Dense in -> dense out; block input
    -> ShardedBlockMatrix (blocks stay on the mesh). Outside any mesh
    context the constraints are skipped and the result is bitwise identical
    to the dense path with the same configuration. auto=True consults the
    planner under the sharded placement; explicit block_size / leaf_solver /
    engine arguments always override the planner's choices.

    coded=CodedConfig(...) routes through the straggler-robust execution
    layer (repro.parallel.straggler): the inverse is assembled from w coded
    worker panel-solves, any w−s of which suffice, so an overdue or failed
    worker never stalls the inversion. `fault_plan` scripts deterministic
    stragglers/failures for tests (None picks up the SPIN_FAULT_PLAN env
    schedule). The coded path takes a dense (n, n) or BlockMatrix operand
    and returns a dense inverse — it is a per-panel execution model, not
    the single-program mesh recursion.

    The call runs inside the always-on host span `spin.inverse_sharded`.
    """
    with host_span("spin.inverse_sharded"):
        from repro.parallel.sharded_blockmatrix import inverse_program

        validate_engine(engine)
        if precision is not None:
            from .precision import resolve_precision

            policy = resolve_precision(precision)
            dense_in = not isinstance(a, BlockMatrix)
            if not policy.is_exact and _policy_active(
                    policy, a.dtype if dense_in else a.blocks.dtype):
                if not dense_in:
                    raise ValueError(
                        "low-precision policies on the sharded path need a "
                        "dense operand (cast-in/cast-out semantics); got "
                        f"{type(a).__name__}")
                # Cast-in / cast-out: the mesh recursion has no polish stage,
                # so the sharded low-precision contract is compute-dtype only.
                cd = policy.resolve_compute(a.dtype)
                out = spin_inverse_sharded(a.astype(cd), block_size,
                                           leaf_solver=leaf_solver,
                                           engine=engine, auto=auto,
                                           coded=coded, fault_plan=fault_plan)
                return out.astype(policy.resolve_store(a.dtype))
        if coded is not None:
            from repro.parallel.sharded_blockmatrix import ShardedBlockMatrix
            from repro.parallel.straggler import coded_inverse

            if isinstance(a, ShardedBlockMatrix):
                raise ValueError(
                    "coded execution assembles the inverse from worker panels "
                    "and needs a dense or BlockMatrix operand, not a "
                    "mesh-resident ShardedBlockMatrix")
            dense = a.to_dense() if isinstance(a, BlockMatrix) else a
            bs = block_size or (a.block_size if isinstance(a, BlockMatrix)
                                else None)
            inv, _ = coded_inverse(dense, coded, block_size=bs,
                                   leaf_solver=leaf_solver or "linalg",
                                   engine=engine, sharded=True,
                                   fault_plan=fault_plan)
            return inv

        a, leaf_solver, engine, dense_in = _resolve_sharded_config(
            "inverse", a, block_size, leaf_solver, engine, auto)
        out = inverse_program(a, leaf_solver=leaf_solver, engine=engine)
        return out.to_dense() if dense_in else out
