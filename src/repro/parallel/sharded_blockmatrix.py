"""ShardedBlockMatrix: the mesh-resident distributed SPIN data structure.

A BlockMatrix between recursion levels is a plain unconstrained array:
under pjit the SPMD partitioner is free to replicate every intermediate, so
nothing larger than one device's HBM can be inverted and the 6 multiplies
per level pay full-replication traffic — exactly the between-stage
movement Gittens et al. blame for Spark's gap vs MPI.

`ShardedBlockMatrix` closes that gap. It is a BlockMatrix whose placement
hooks pin the (b, b, bs, bs) block grid to the mesh: every producing
operation — quadrant views, the 6 multiplies, subtracts, scalarMul,
arrange, and leaf inversions — re-asserts the grid-over-mesh rule of
`repro.core.placement` (`PartitionSpec(data, model, None, None)` while the
grid divides the mesh, replicated along an axis it no longer divides), so
the one Algorithm-2 recursion (`repro.core.recursion`) lowers to ONE pjit
program in which no inter-level gather-to-dense exists. Dense solve panels
shard their row axis over `data` under the same rule. Once a node's
quadrants no longer divide the mesh, its products run replicated: every
device computes them whole (at grid 32 on a (2, 2) mesh, the 16 nodes of
depth 4 with 96 bs×bs GEMMs), and so does every leaf inversion (all 32
there). What is replicated is small, never the matrix, but it is repeated
work, booked as `OpCounts.replicated_block_gemms` and `replicated_leaves`.

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

The mesh keeps the recursion's two Schur steps unfused — a multiply, then
a subtract, each re-anchored (`fused_schur = False`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.blockmatrix import BlockMatrix, _bump
from repro.core.multiply import current_engine, multiply_engine
from repro.core.placement import (DEFAULT_AXES, SpecRecord, active_mesh,
                                  assert_mesh_resident, constrain_grid,
                                  constrain_panel, grid_spec,
                                  mesh_fingerprint, panel_spec, record_specs)
from repro.core.recursion import invert, solve
from repro.obs.trace import LAYOUT, step_scope

__all__ = [
    "ShardedBlockMatrix", "SpecRecord", "record_specs",
    "assert_mesh_resident", "grid_spec", "panel_spec", "mesh_fingerprint",
    "sharded_spin_inverse", "sharded_spin_solve",
    "inverse_program", "solve_program", "lower_inverse_program",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ShardedBlockMatrix(BlockMatrix):
    """A BlockMatrix whose grid carries (and re-asserts) a mesh sharding.

    Every producing method ends in a grid-over-mesh sharding constraint so
    intermediates never silently replicate. Outside any mesh context the
    constraints are skipped and the ops are bit-identical to BlockMatrix's.
    """

    axes: tuple[str, str] = DEFAULT_AXES

    # Unfused on the mesh until measured there: a multiply, then a
    # subtract, each re-anchored.
    fused_schur = False

    # -- pytree protocol (axes are static structure) ------------------------
    def tree_flatten(self):
        return (self.blocks,), self.axes

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)

    # -- placement hooks ------------------------------------------------------
    def placed(self, blocks: jax.Array, op: str) -> "ShardedBlockMatrix":
        return ShardedBlockMatrix(constrain_grid(blocks, op, self.axes),
                                  self.axes)

    def node_split(self):
        spec = _on_device_spec(self)
        if spec is None:
            return super().node_split()
        return (_split_on_device(self, spec),
                functools.partial(_arrange_on_device, spec=spec))

    def place_panel(self, x: jax.Array, op: str) -> jax.Array:
        return constrain_panel(x, op, self.axes)

    def stack_rows(self, x1: jax.Array, x2: jax.Array) -> jax.Array:
        """[X1; X2] via dynamic_update_slice into an anchored panel.

        Concatenate along the row axis is exactly the partially-replicated
        sharded-dim case the XLA partitioner mis-lowers (panels are P(data,
        None), leaving `model` free) — see core.blockmatrix.assemble_quadrants.
        """
        out = self.place_panel(jnp.zeros((x1.shape[0] + x2.shape[0],)
                                         + x1.shape[1:], x1.dtype),
                               "solve_panel")
        out = jax.lax.dynamic_update_slice(out, x1, (0, 0))
        return jax.lax.dynamic_update_slice(out, x2, (x1.shape[0], 0))

    def leaf_inverse(self, solver: str = "linalg") -> "ShardedBlockMatrix":
        if active_mesh() is not None:
            _bump("replicated_leaves")        # every device inverts it
        return super().leaf_inverse(solver)

    def constrain(self, op: str = "input") -> "ShardedBlockMatrix":
        """Re-assert this matrix's own grid sharding (entry-point anchor)."""
        return self.placed(self.blocks, op)

    # -- conversions ----------------------------------------------------------
    @classmethod
    def from_dense(cls, dense: jax.Array, block_size: int,
                   axes: tuple[str, str] = DEFAULT_AXES
                   ) -> "ShardedBlockMatrix":
        bm = BlockMatrix.from_dense(dense, block_size)
        return cls(bm.blocks, axes).constrain("from_dense")

    @classmethod
    def from_blockmatrix(cls, bm: BlockMatrix,
                         axes: tuple[str, str] = DEFAULT_AXES
                         ) -> "ShardedBlockMatrix":
        return cls(bm.blocks, axes).constrain("from_blockmatrix")

    def to_blockmatrix(self) -> BlockMatrix:
        return BlockMatrix(self.blocks)


# ---------------------------------------------------------------------------
# Interleaved quadrants: split and arrange on the device that holds them
# ---------------------------------------------------------------------------


def _on_device_spec(a: ShardedBlockMatrix) -> P | None:
    """The node's grid spec when its interleaved split stays on device.

    That needs both grid axes sharded over equal mesh axes (rows and
    columns then partition alike, a symmetric permutation) and an even
    local extent; None sends the node down the contiguous `split`.
    """
    mesh = active_mesh()
    if mesh is None:
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
    return tuple(a.placed(q, "split") for q in quads)


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
    return c11.placed(out, "arrange")


# ---------------------------------------------------------------------------
# The mesh entries into the one recursion of each algorithm
# ---------------------------------------------------------------------------


def sharded_spin_inverse(a: ShardedBlockMatrix, leaf_solver: str = "linalg"
                         ) -> ShardedBlockMatrix:
    """Algorithm-2 inversion with every intermediate pinned to the mesh."""
    return invert(a, leaf_solver)


def sharded_spin_solve(a: ShardedBlockMatrix, b: jax.Array, *,
                       leaf_solver: str = "linalg") -> jax.Array:
    """Solve A X = B with the mesh-resident recursion; B (n, k) or (n,)."""
    return solve(a, b, leaf_solver)


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


def lower_inverse_program(n: int, block_size: int, leaf_solver: str,
                          engine: str | None, mesh) -> jax.stages.Lowered:
    """`_inverse_program` lowered from shapes, for an (n, n) float32 operand
    whose blocks lie on `mesh` under the grid rule."""
    grid = n // block_size
    sharding = NamedSharding(mesh, grid_spec(grid, grid, mesh, DEFAULT_AXES))
    blocks = jax.ShapeDtypeStruct((grid, grid, block_size, block_size),
                                  jnp.float32, sharding=sharding)
    with compat.set_mesh(mesh):
        return _inverse_program.lower(blocks, leaf_solver, engine,
                                      DEFAULT_AXES, mesh_fingerprint())
