"""ShardedBlockMatrix: the mesh-resident distributed SPIN data structure.

The dense-path recursion (core/spin.py) is numerically the paper's
Algorithm 2, but between levels its quadrants are plain unconstrained
arrays: under pjit the SPMD partitioner is free to replicate every
intermediate, so nothing larger than one device's HBM can be inverted and
the 6 multiplies per level pay full-replication traffic — exactly the
between-stage movement Gittens et al. blame for Spark's gap vs MPI.

`ShardedBlockMatrix` closes that gap: the (b, b, bs, bs) block grid carries
an explicit grid-over-mesh sharding (`PartitionSpec(data, model, None,
None)`) that is re-asserted by EVERY producing operation — quadrant views,
the 6 multiplies, subtracts, scalarMul, arrange, and leaf inversions — so
the whole Algorithm-2 recursion lowers to ONE pjit program in which no
inter-level gather-to-dense exists. The sharding contract per recursion
level:

    grid (g_r, g_c) blocks  ->  P(data if g_r % |data| == 0 else None,
                                  model if g_c % |model| == 0 else None,
                                  None, None)

i.e. a level stays fully grid-sharded as long as its (halved) grid still
covers the mesh axis; when the grid outgrows divisibility the undivisible
axis degrades to replicated-along-that-axis. Once a node's quadrants no
longer divide the mesh, its products run replicated: every device computes
them whole (at grid 32 on a (2, 2) mesh, the 16 nodes of depth 4 with 96
bs×bs GEMMs), and so does every leaf inversion (all 32 there). What is
replicated is small, never the matrix, but it is repeated work, booked as
`OpCounts.replicated_block_gemms` and `replicated_leaves`. Dense solve
panels shard their row axis over `data` under the same rule.

On a square mesh (|data| == |model|) the recursion splits a node into
*interleaved* quadrants wherever each device holds an even number of its
block rows: along both grid axes, the leading half is the first half of
every device's local rows, the trailing half the rest. Stored in that
order, each quadrant is again `P(data, model)` in contiguous chunks, so
the same rule holds one level down, and split and arrange are local
slices and a local concatenate inside a `shard_map` — no bytes cross
chips (`OpCounts.local_splits`, `local_arranges`). Contiguous halves
would each lie on one device, and re-anchoring them to the mesh reshards
¾ of every quadrant each way. The interleaved halves are a symmetric
block permutation of the node, which Algorithm 2 admits wherever the
leading half and its Schur complement are invertible: SPD and
diagonally dominant matrices (the zoo's families) stay so under it; a
general matrix needs its interleaved, not its contiguous, leading blocks
nonsingular. The result is inv(A) in A's own layout: arrange undoes the
permutation split made. Elsewhere — off a mesh, a non-square mesh, or a
node whose quadrants no longer divide it — the contiguous `split` and
`arrange` run. The public `split()` and `arrange()` keep their natural
quadrant meaning (the solve uses them).

Every constraint is also recorded in a trace-time *spec ledger*
(`record_specs`), which is how tests assert the no-replication property
from the jaxpr rather than trusting this docstring: each
`with_sharding_constraint` this module issues appears once in the ledger
and once as a `sharding_constraint` eqn in the lowered program.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
from typing import Iterator

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.blockmatrix import BlockMatrix, _bump
from repro.core.multiply import (current_engine, multiply_blocks,
                                 multiply_engine)
from repro.core.precision import dot_precision
from repro.obs.trace import LAYOUT, level_scope, step_scope

__all__ = [
    "ShardedBlockMatrix", "SpecRecord", "record_specs",
    "assert_mesh_resident", "grid_spec", "panel_spec", "mesh_fingerprint",
    "sharded_spin_inverse", "sharded_spin_solve",
    "inverse_program", "solve_program",
]


# ---------------------------------------------------------------------------
# Spec ledger: what this module constrained, recorded at trace time.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SpecRecord:
    """One with_sharding_constraint issued by the sharded recursion."""

    op: str                                  # producing op ("split", "multiply", …)
    kind: str                                # "grid" (b,b,bs,bs) | "panel" (n,k)
    shape: tuple[int, ...]                   # array shape at the constraint
    spec: tuple | None                       # P as a tuple, None if skipped
    axes: tuple[str, str]                    # intended (data, model) axis names
    mesh_axes: tuple[tuple[str, int], ...]   # mesh shape at trace time

    @property
    def grid_sharded(self) -> bool:
        """Both grid axes mapped to mesh axes (nothing replicated)."""
        return (self.spec is not None and self.spec[0] is not None
                and self.spec[1] is not None)


_LEDGER: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "sharded_blockmatrix_spec_ledger", default=None
)


@contextlib.contextmanager
def record_specs() -> Iterator[list[SpecRecord]]:
    """Collect every sharding constraint the sharded ops issue (trace-time).

    Like `count_ops`, records only accumulate while something is actually
    tracing/executing the ops — a jit cache hit replays the compiled
    program and records nothing.
    """
    records: list[SpecRecord] = []
    token = _LEDGER.set(records)
    try:
        yield records
    finally:
        _LEDGER.reset(token)


def _record(op: str, kind: str, shape: tuple[int, ...], spec,
            axes: tuple[str, str], mesh) -> None:
    ledger = _LEDGER.get()
    if ledger is None:
        return
    mesh_axes = (tuple(sorted(dict(mesh.shape).items()))
                 if mesh is not None else ())
    ledger.append(SpecRecord(op=op, kind=kind, shape=tuple(shape),
                             spec=None if spec is None else tuple(spec),
                             axes=axes, mesh_axes=mesh_axes))


def assert_mesh_resident(records: list[SpecRecord],
                         min_records: int = 1) -> dict[str, int]:
    """Assert the ledger shows a mesh-resident recursion; return a tally.

    Every grid record whose grid axes are divisible by the mesh MUST have
    been constrained onto both mesh axes, and every panel record with a
    data-divisible row count must be row-sharded — i.e. no intermediate
    that *could* stay distributed was left for the partitioner to
    replicate. Returns {"total", "grid_sharded", "panel_sharded",
    "partial"} counts ("grid_sharded" counts grid records only).
    """
    if len(records) < min_records:
        raise AssertionError(
            f"expected >= {min_records} sharding records, got {len(records)} "
            "(was the program served from the jit cache?)")
    bad = []
    tally = {"total": len(records), "grid_sharded": 0, "panel_sharded": 0,
             "partial": 0}
    for r in records:
        sizes = dict(r.mesh_axes)
        d_size = sizes.get(r.axes[0], 0)
        m_size = sizes.get(r.axes[1], 0)
        if r.kind == "grid":
            resident = r.grid_sharded
            expect = (d_size and m_size and r.shape[0] % d_size == 0
                      and r.shape[1] % m_size == 0)
            bucket = "grid_sharded"
        else:                                   # panel: rows over data only
            resident = r.spec is not None and r.spec[0] is not None
            expect = bool(d_size) and r.shape[0] % d_size == 0
            bucket = "panel_sharded"
        tally[bucket if resident else "partial"] += 1
        if expect and not resident:
            bad.append(r)
    if bad:
        raise AssertionError(
            "mesh-divisible intermediates were not grid-sharded "
            f"(replication leak): {bad[:5]}")
    return tally


# ---------------------------------------------------------------------------
# Spec computation + constraint application
# ---------------------------------------------------------------------------


def grid_spec(grid_rows: int, grid_cols: int, mesh,
              axes: tuple[str, str] = ("data", "model")) -> P:
    """Divisibility-aware grid-over-mesh spec for a (gr, gc, bs, bs) array."""
    shape = dict(mesh.shape)
    d, m = axes
    row = d if d in shape and grid_rows % shape[d] == 0 else None
    col = m if m in shape and grid_cols % shape[m] == 0 else None
    return P(row, col, None, None)


def panel_spec(rows: int, mesh, axes: tuple[str, str] = ("data", "model")
               ) -> P:
    """Row-sharding spec for a dense (rows, k) solve panel."""
    d = axes[0]
    shape = dict(mesh.shape)
    row = d if d in shape and rows % shape[d] == 0 else None
    return P(row, None)


def mesh_fingerprint(mesh=None) -> str:
    """Canonical string for the ambient mesh, e.g. "data2:model2" ("" = none).

    Used (a) as the static jit-cache key component of the sharded programs
    and (b) by the planner's ProblemSignature as its mesh dimension. It is
    topology only: the constraints name mesh AXES, and jit places each
    compiled program on the devices of the concrete mesh that `set_mesh`
    installed, so two same-topology meshes over different devices share a
    trace but never a device assignment (DESIGN.md §6).
    """
    if mesh is None:
        mesh = compat.get_abstract_mesh()
    if mesh is None or not mesh.shape:
        return ""
    return ":".join(f"{k}{v}" for k, v in mesh.shape.items())


def _constrain(blocks: jax.Array, op: str,
               axes: tuple[str, str]) -> jax.Array:
    """Re-assert the grid-over-mesh sharding on a freshly produced grid."""
    mesh = compat.get_abstract_mesh()
    if mesh is None or not mesh.shape:
        _record(op, "grid", blocks.shape, None, axes, None)
        return blocks
    spec = grid_spec(blocks.shape[0], blocks.shape[1], mesh, axes)
    blocks = jax.lax.with_sharding_constraint(blocks, spec)
    _record(op, "grid", blocks.shape, spec, axes, mesh)
    return blocks


def _constrain_panel(x: jax.Array, op: str,
                     axes: tuple[str, str]) -> jax.Array:
    mesh = compat.get_abstract_mesh()
    if mesh is None or not mesh.shape:
        _record(op, "panel", x.shape, None, axes, None)
        return x
    spec = panel_spec(x.shape[0], mesh, axes)
    x = jax.lax.with_sharding_constraint(x, spec)
    _record(op, "panel", x.shape, spec, axes, mesh)
    return x


# ---------------------------------------------------------------------------
# ShardedBlockMatrix
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ShardedBlockMatrix:
    """A BlockMatrix whose grid carries (and re-asserts) a mesh sharding.

    Same (b, b, bs, bs) storage and paper-method API as `BlockMatrix`;
    every producing method ends in a grid-over-mesh sharding constraint so
    intermediates never silently replicate. Outside any mesh context the
    constraints are skipped and the ops are bit-identical to BlockMatrix's.
    """

    blocks: jax.Array
    axes: tuple[str, str] = ("data", "model")

    # -- pytree protocol (axes are static structure) ------------------------
    def tree_flatten(self):
        return (self.blocks,), self.axes

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)

    # -- shape accessors ----------------------------------------------------
    @property
    def grid(self) -> int:
        return self.blocks.shape[0]

    @property
    def block_size(self) -> int:
        return self.blocks.shape[2]

    @property
    def n(self) -> int:
        return self.grid * self.block_size

    @property
    def dtype(self):
        return self.blocks.dtype

    def _wrap(self, blocks: jax.Array, op: str) -> "ShardedBlockMatrix":
        return ShardedBlockMatrix(_constrain(blocks, op, self.axes),
                                  self.axes)

    def constrain(self, op: str = "input") -> "ShardedBlockMatrix":
        """Re-assert this matrix's own grid sharding (entry-point anchor)."""
        return self._wrap(self.blocks, op)

    # -- conversions ----------------------------------------------------------
    @classmethod
    def from_dense(cls, dense: jax.Array, block_size: int,
                   axes: tuple[str, str] = ("data", "model")
                   ) -> "ShardedBlockMatrix":
        bm = BlockMatrix.from_dense(dense, block_size)
        return cls(bm.blocks, axes).constrain("from_dense")

    @classmethod
    def from_blockmatrix(cls, bm: BlockMatrix,
                         axes: tuple[str, str] = ("data", "model")
                         ) -> "ShardedBlockMatrix":
        return cls(bm.blocks, axes).constrain("from_blockmatrix")

    def to_blockmatrix(self) -> BlockMatrix:
        return BlockMatrix(self.blocks)

    def to_dense(self) -> jax.Array:
        """Gather-free reshape to (n, n); the RESULT may be densified — the
        no-gather contract covers the levels in between, not the output."""
        return self.to_blockmatrix().to_dense()

    # -- paper methods -------------------------------------------------------
    def split(self) -> tuple["ShardedBlockMatrix", "ShardedBlockMatrix",
                             "ShardedBlockMatrix", "ShardedBlockMatrix"]:
        """breakMat + quadrant views, each re-anchored to the mesh."""
        b = self.grid
        if b % 2:
            raise ValueError(f"cannot split odd grid b={b}")
        h = b // 2
        _bump("splits")
        blk = self.blocks
        return (
            self._wrap(blk[:h, :h], "split"),
            self._wrap(blk[:h, h:], "split"),
            self._wrap(blk[h:, :h], "split"),
            self._wrap(blk[h:, h:], "split"),
        )

    @staticmethod
    def arrange(c11: "ShardedBlockMatrix", c12: "ShardedBlockMatrix",
                c21: "ShardedBlockMatrix", c22: "ShardedBlockMatrix"
                ) -> "ShardedBlockMatrix":
        """Quadrants -> matrix via dynamic_update_slice into a grid whose
        sharding is anchored FIRST (see core.blockmatrix.assemble_quadrants
        on why concatenate must not be used here); the updates inherit the
        anchor's sharding, so no second constraint is needed."""
        from repro.core.blockmatrix import assemble_quadrants

        _bump("arranges")
        h = c11.grid
        anchor = jnp.zeros((2 * h, 2 * h) + c11.blocks.shape[2:], c11.dtype)
        mesh = compat.get_abstract_mesh()
        spec = None
        if mesh is not None and mesh.shape:
            spec = grid_spec(2 * h, 2 * h, mesh, c11.axes)
            anchor = jax.lax.with_sharding_constraint(anchor, spec)
        out = assemble_quadrants(c11.blocks, c12.blocks, c21.blocks,
                                 c22.blocks, into=anchor)
        _record("arrange", "grid", out.shape, spec, c11.axes,
                mesh if spec is not None else None)
        return ShardedBlockMatrix(out, c11.axes)

    def subtract(self, other: "ShardedBlockMatrix") -> "ShardedBlockMatrix":
        _bump("subtracts")
        return self._wrap(self.blocks - other.blocks, "subtract")

    def scalar_mul(self, scalar) -> "ShardedBlockMatrix":
        _bump("scalar_muls")
        return self._wrap(self.blocks * scalar, "scalar_mul")

    def neg(self) -> "ShardedBlockMatrix":
        return self.scalar_mul(-1.0)

    def multiply(self, other: "ShardedBlockMatrix") -> "ShardedBlockMatrix":
        """Distributed multiply through the shared engine dispatcher.

        All engines — including the fused-kernel ``pallas`` engine, whose
        per-shard grid GEMMs run the Pallas kernel inside shard_map — go
        through `multiply_blocks`, so `inverse_program(engine="pallas")`
        needs no sharded-path special casing (the engine remains a static
        jit key of the one-program entry points).
        """
        if self.grid != other.grid or self.block_size != other.block_size:
            raise ValueError(f"grid mismatch: {self.blocks.shape} vs "
                             f"{other.blocks.shape}")
        _bump("multiplies")
        _bump("block_gemms", self.grid ** 3)
        return self._wrap(multiply_blocks(self.blocks, other.blocks),
                          "multiply")

    def leaf_inverse(self, solver: str = "linalg") -> "ShardedBlockMatrix":
        """Algorithm-2 `if` branch: invert the single block where it lives."""
        from repro.core.spin import LEAF_SOLVERS  # late: spin imports multiply

        if self.grid != 1:
            raise ValueError(f"leaf_inverse expects grid==1, got {self.grid}")
        _bump("leaf_inversions")
        mesh = compat.get_abstract_mesh()
        if mesh is not None and mesh.shape:
            _bump("replicated_leaves")        # every device inverts it
        inv = LEAF_SOLVERS[solver](self.blocks[0, 0])
        return self._wrap(inv[None, None], "leaf_inverse")


# ---------------------------------------------------------------------------
# Interleaved quadrants: split and arrange on the device that holds them
# ---------------------------------------------------------------------------


def _on_device_spec(a: ShardedBlockMatrix) -> P | None:
    """The node's grid spec when its interleaved split stays on device.

    That needs both grid axes sharded over equal mesh axes (rows and
    columns then partition alike, a symmetric permutation) and an even
    local extent; None sends the node down the contiguous `split`.
    """
    mesh = compat.get_abstract_mesh()
    if mesh is None or not mesh.shape:
        return None
    spec = grid_spec(a.grid, a.grid, mesh, a.axes)
    if spec[0] is None or spec[1] is None:
        return None
    local = a.grid // mesh.shape[spec[0]]
    if mesh.shape[spec[0]] != mesh.shape[spec[1]] or local % 2:
        return None
    return spec


def _split_on_device(a: ShardedBlockMatrix, spec: P
                     ) -> tuple[ShardedBlockMatrix, ...]:
    """Interleaved quadrants (module docstring): each device cuts its
    own (l, l) blocks into four (l/2, l/2) pieces."""
    _bump("splits")
    _bump("local_splits")

    def local(blk):
        h = blk.shape[0] // 2
        return blk[:h, :h], blk[:h, h:], blk[h:, :h], blk[h:, h:]

    quads = compat.shard_map(local, mesh=compat.get_abstract_mesh(),
                             in_specs=spec, out_specs=(spec,) * 4)(a.blocks)
    return tuple(a._wrap(q, "split") for q in quads)


def _arrange_on_device(c11: ShardedBlockMatrix, c12: ShardedBlockMatrix,
                       c21: ShardedBlockMatrix, c22: ShardedBlockMatrix,
                       spec: P) -> ShardedBlockMatrix:
    """Inverse of `_split_on_device`: each device concatenates its four
    pieces in place (inside the shard_map no partitioner sees the
    concatenate, so `assemble_quadrants`' hazard cannot arise)."""
    _bump("arranges")
    _bump("local_arranges")

    def local(q11, q12, q21, q22):
        return jnp.concatenate([jnp.concatenate([q11, q12], axis=1),
                                jnp.concatenate([q21, q22], axis=1)], axis=0)

    out = compat.shard_map(local, mesh=compat.get_abstract_mesh(),
                           in_specs=(spec,) * 4, out_specs=spec)(
        c11.blocks, c12.blocks, c21.blocks, c22.blocks)
    return c11._wrap(out, "arrange")


# ---------------------------------------------------------------------------
# The mesh-resident recursion (paper Algorithm 2)
# ---------------------------------------------------------------------------


def sharded_spin_inverse(a: ShardedBlockMatrix, leaf_solver: str = "linalg",
                         _level: int = 0) -> ShardedBlockMatrix:
    """Algorithm-2 recursion with every intermediate pinned to the mesh.

    Identical op sequence to `core.spin.spin_inverse` (the op-count oracle
    holds level for level) under the same named scopes (`spin.L<_level>`
    and its steps, repro.obs.trace); the differences are the sharding
    constraint each op re-asserts, so quadrants stay device-resident
    between levels, and, where `_on_device_spec` allows, interleaved
    quadrants split and arranged without leaving their device.
    """
    b = a.grid
    if b & (b - 1):
        raise ValueError(f"grid must be a power of two, got {b}")
    if b == 1:
        with level_scope(_level), step_scope("leaf"):
            return a.leaf_inverse(leaf_solver)

    spec = _on_device_spec(a)
    with level_scope(_level):
        with step_scope("split"):
            a11, a12, a21, a22 = (a.split() if spec is None
                                  else _split_on_device(a, spec))
        i_ = sharded_spin_inverse(a11, leaf_solver,
                                  _level + 1)             # I   = A11^-1
        with step_scope("II"):
            ii = a21.multiply(i_)                         # II  = A21 I
        with step_scope("III"):
            iii = i_.multiply(a12)                        # III = I A12
        with step_scope("schur"):
            iv = a21.multiply(iii)                        # IV  = A21 III
            v = iv.subtract(a22)                          # V   = IV - A22
        vi = sharded_spin_inverse(v, leaf_solver,
                                  _level + 1)             # VI  = V^-1
        with step_scope("C12"):
            c12 = iii.multiply(vi)
        with step_scope("C21"):
            c21 = vi.multiply(ii)
        with step_scope("C11"):
            vii = iii.multiply(c21)
            c11 = i_.subtract(vii)
        with step_scope("neg"):
            c22 = vi.neg()                                # scalarMul(VI, -1)
        with step_scope("arrange"):
            if spec is None:
                return ShardedBlockMatrix.arrange(c11, c12, c21, c22)
            return _arrange_on_device(c11, c12, c21, c22, spec)


def _apply_blocks_sharded(a: ShardedBlockMatrix, x: jax.Array) -> jax.Array:
    """A·X for the sharded grid and a row-sharded dense panel X."""
    from repro.core.solve import _apply_blocks

    return _constrain_panel(_apply_blocks(a.to_blockmatrix(), x),
                            "solve_apply", a.axes)


def _stack_panel_rows(x1: jax.Array, x2: jax.Array, op: str,
                      axes: tuple[str, str]) -> jax.Array:
    """[X1; X2] row stacking via dynamic_update_slice into an anchored panel.

    Concatenate along the row axis is exactly the partially-replicated
    sharded-dim case the XLA partitioner mis-lowers (panels are P(data,
    None), leaving `model` free) — see core.blockmatrix.assemble_quadrants.
    """
    rows = x1.shape[0] + x2.shape[0]
    out = jnp.zeros((rows,) + x1.shape[1:], x1.dtype)
    mesh = compat.get_abstract_mesh()
    spec = None
    if mesh is not None and mesh.shape:
        spec = panel_spec(rows, mesh, axes)
        out = jax.lax.with_sharding_constraint(out, spec)
    out = jax.lax.dynamic_update_slice(out, x1, (0, 0))
    out = jax.lax.dynamic_update_slice(out, x2, (x1.shape[0], 0))
    _record(op, "panel", out.shape, spec, axes,
            mesh if spec is not None else None)
    return out


def _sharded_solve(a: ShardedBlockMatrix, b: jax.Array,
                   leaf_solver: str) -> jax.Array:
    """Inverse-free Schur recursion with row-sharded panels (core.solve
    `_solve`, with every panel pinned to the `data` axis between levels)."""
    from repro.core.solve import _accum_dtype, _leaf_solve

    if a.grid == 1:
        return _constrain_panel(_leaf_solve(a.blocks[0, 0], b, leaf_solver),
                                "leaf_solve", a.axes)

    bs = a.block_size
    a11, a12, a21, a22 = a.split()
    half = a11.n
    b1, b2 = b[:half], b[half:]

    # One recursive solve covers both III (= A11⁻¹A12) and Y1 (= A11⁻¹B1).
    # Column concatenation is safe ONLY because both operands are first
    # pinned to row-only sharding (concat dim replicated); the row-stacking
    # cases below must go through _stack_panel_rows instead.
    z = _sharded_solve(
        a11,
        _constrain_panel(jnp.concatenate(
            [_constrain_panel(a12.to_dense(), "solve_rhs", a.axes),
             _constrain_panel(b1, "solve_rhs", a.axes)], axis=1),
            "solve_rhs", a.axes),
        leaf_solver)
    iii, y1 = z[:, :half], z[:, half:]

    v = _apply_blocks_sharded(a21, iii) - a22.to_dense()  # −Schur complement
    _bump("subtracts")
    rhs2 = _apply_blocks_sharded(a21, y1) - b2
    _bump("subtracts")
    x2 = _sharded_solve(
        ShardedBlockMatrix.from_dense(v, bs, a.axes),
        _constrain_panel(rhs2, "solve_rhs", a.axes), leaf_solver)

    acc = _accum_dtype(iii.dtype)
    _bump("solve_applies")                                # III·X2 panel GEMM
    x1 = y1 - jnp.matmul(iii, x2, preferred_element_type=acc,
                         precision=dot_precision(iii.dtype, x2.dtype)
                         ).astype(y1.dtype)
    _bump("subtracts")
    return _stack_panel_rows(x1, x2, "solve_panel", a.axes)


def sharded_spin_solve(a: ShardedBlockMatrix, b: jax.Array, *,
                       leaf_solver: str = "linalg") -> jax.Array:
    """Solve A X = B with the mesh-resident recursion; B (n, k) or (n,)."""
    grid = a.grid
    if grid & (grid - 1):
        raise ValueError(f"grid must be a power of two, got {grid}")
    if b.shape[0] != a.n:
        raise ValueError(f"rhs rows {b.shape[0]} != matrix dim {a.n}")
    vector = b.ndim == 1
    rhs = b[:, None] if vector else b
    rhs = _constrain_panel(rhs, "solve_rhs", a.axes)
    x = _sharded_solve(a, rhs, leaf_solver)
    return x[:, 0] if vector else x


# ---------------------------------------------------------------------------
# One-program (pjit) entry points. `mesh_fp` keys the jit cache on the
# ambient mesh: the constraints above read the mesh at TRACE time, so a
# cached executable traced under one mesh must never serve another.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("leaf_solver", "engine", "axes",
                                             "mesh_fp"))
def _inverse_program(blocks: jax.Array, leaf_solver: str,
                     engine: str | None, axes: tuple[str, str],
                     mesh_fp: str) -> jax.Array:
    ctx = multiply_engine(engine) if engine else contextlib.nullcontext()
    with ctx:
        with step_scope(LAYOUT):
            a = ShardedBlockMatrix(blocks, axes).constrain("input")
        return sharded_spin_inverse(a, leaf_solver).blocks


@functools.partial(jax.jit, static_argnames=("leaf_solver", "engine", "axes",
                                             "mesh_fp"))
def _solve_program(blocks: jax.Array, rhs: jax.Array, leaf_solver: str,
                   engine: str | None, axes: tuple[str, str],
                   mesh_fp: str) -> jax.Array:
    ctx = multiply_engine(engine) if engine else contextlib.nullcontext()
    with ctx:
        a = ShardedBlockMatrix(blocks, axes).constrain("input")
        return sharded_spin_solve(a, rhs, leaf_solver=leaf_solver)


def inverse_program(a: ShardedBlockMatrix, *, leaf_solver: str = "linalg",
                    engine: str | None = None) -> ShardedBlockMatrix:
    """The whole recursion as ONE jitted program; blocks stay device-resident.

    engine=None resolves the ambient `multiply_engine` HERE (static jit
    argument), so programs traced under different engines never share an
    executable.
    """
    out = _inverse_program(a.blocks, leaf_solver, engine or current_engine(),
                           a.axes, mesh_fingerprint())
    return ShardedBlockMatrix(out, a.axes)


def solve_program(a: ShardedBlockMatrix, b: jax.Array, *,
                  leaf_solver: str = "linalg",
                  engine: str | None = None) -> jax.Array:
    """Mesh-resident multi-RHS solve as ONE jitted program."""
    vector = b.ndim == 1
    rhs = b[:, None] if vector else b
    x = _solve_program(a.blocks, rhs, leaf_solver, engine or current_engine(),
                       a.axes, mesh_fingerprint())
    return x[:, 0] if vector else x
