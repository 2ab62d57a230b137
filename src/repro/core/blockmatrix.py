"""BlockMatrix: the distributed block data structure from SPIN (§3.2), on JAX.

The paper stores an n×n matrix as a Spark RDD of ((rowIndex, colIndex), block)
tuples. On a TPU mesh the natural analogue is a single array of shape
``(b, b, bs, bs)`` — a b×b grid of bs×bs blocks — whose *grid* axes are
sharded over the device mesh (``PartitionSpec('data', 'model')``). Every
method of the paper's BlockMatrix API (breakMat/xy/multiply/subtract/
scalarMul/arrange) maps to a pure function here; breakMat/xy/arrange become
trace-time slicing (free on TPU — no tagging/shuffle pass), which is recorded
as a structural win in DESIGN.md §2.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .leaf import LEAF_SOLVERS

__all__ = [
    "BlockMatrix",
    "OpCounts",
    "count_ops",
    "current_counts",
    "block_sharding",
    "assemble_quadrants",
]


# ---------------------------------------------------------------------------
# Operation accounting (used by tests to assert the paper's op counts and by
# benchmarks to report the Table-1 style breakdown).
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class OpCounts:
    multiplies: int = 0          # BlockMatrix-level multiply() calls
    block_gemms: int = 0         # bs×bs GEMMs implied by those multiplies
    subtracts: int = 0
    scalar_muls: int = 0
    leaf_inversions: int = 0
    leaf_lu: int = 0
    leaf_solves: int = 0         # grid==1 systems solved by spin_solve
    solve_applies: int = 0       # BlockMatrix × dense-panel products (solve)
    smw_updates: int = 0         # Woodbury rank-k inverse revisions (update)
    arranges: int = 0
    splits: int = 0
    # Strassen-engine internals (engine="strassen" only; the engine-blind
    # counters above still book each Strassen product as ONE multiply):
    strassen_base_multiplies: int = 0   # classical leaves of the recursion
    strassen_adds: int = 0              # quadrant add/sub passes (18/level)
    # Pallas GEMM grid steps, (m/bm)·(n/bn)·(k/bk) summed over the
    # kernels.matmul calls (engine="pallas" and its solve panels only):
    pallas_grid_steps: int = 0
    # On a mesh (zero off one): bytes each device receives from the SUMMA
    # gathers, bs×bs GEMMs of products every device repeats (the grid no
    # longer divides the mesh), and leaf inversions (every device repeats
    # each one).
    gather_bytes: int = 0
    # The part of gather_bytes that arrives while a GEMM step of the same
    # Pallas SUMMA product runs: every step's bytes but the first.
    pipelined_gather_bytes: int = 0
    replicated_block_gemms: int = 0
    replicated_leaves: int = 0
    # Nodes of the mesh recursion split and arranged in interleaved
    # quadrants on the device that holds them (also booked as splits and
    # arranges; parallel/sharded_blockmatrix.py):
    local_splits: int = 0
    local_arranges: int = 0

    def as_dict(self) -> dict[str, int]:
        return dataclasses.asdict(self)


_COUNTS: contextvars.ContextVar[OpCounts | None] = contextvars.ContextVar(
    "blockmatrix_op_counts", default=None
)


@contextlib.contextmanager
def count_ops() -> Iterator[OpCounts]:
    """Context manager that records BlockMatrix op counts (trace-time)."""
    counts = OpCounts()
    token = _COUNTS.set(counts)
    try:
        yield counts
    finally:
        _COUNTS.reset(token)


def current_counts() -> OpCounts | None:
    return _COUNTS.get()


def _bump(field: str, by: int = 1) -> None:
    counts = _COUNTS.get()
    if counts is not None:
        setattr(counts, field, getattr(counts, field) + by)


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------


def block_sharding(mesh, grid_axes=("data", "model")) -> NamedSharding:
    """Sharding that puts the block *grid* over the mesh, blocks replicated."""
    return NamedSharding(mesh, P(*grid_axes, None, None))


def assemble_quadrants(c11: jax.Array, c12: jax.Array, c21: jax.Array,
                       c22: jax.Array, into: jax.Array) -> jax.Array:
    """Four (h, h, bs, bs) quadrant grids -> one (2h, 2h, bs, bs) grid.

    Deliberately zeros + dynamic_update_slice, NOT jnp.concatenate: the XLA
    SPMD partitioner (0.4.x line, CPU at least) mis-lowers concatenate along
    a sharded dimension when an operand is partially replicated (one mesh
    axis free), silently corrupting values. dynamic_update_slice assembly
    lowers correctly for every operand sharding the recursion produces, and
    is bitwise-identical pure data movement wherever concatenate was right.

    `into` is the (2h, 2h, bs, bs) zero buffer written into; a
    sharding-aware caller anchors it first (e.g. with_sharding_constraint)
    so the updates inherit the intended output sharding.
    """
    h = c11.shape[0]
    for (i, j), quad in zip(((0, 0), (0, 1), (1, 0), (1, 1)),
                            (c11, c12, c21, c22)):
        into = jax.lax.dynamic_update_slice(into, quad, (i * h, j * h, 0, 0))
    return into


# ---------------------------------------------------------------------------
# BlockMatrix
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BlockMatrix:
    """A b×b grid of bs×bs blocks, stored as one (b, b, bs, bs) array.

    The methods below are the node operations both SPIN recursions
    (`core.recursion`) walk. A subclass changes where the blocks live by
    overriding the placement hooks — `placed`, `node_split`,
    `place_panel`, `stack_rows`, `leaf_inverse` and `fused_schur` — which
    on a BlockMatrix leave every block where XLA puts it
    (`repro.parallel.ShardedBlockMatrix` pins them to a mesh).
    """

    blocks: jax.Array

    # The recursion's two Schur steps run as one fused update
    # (`multiply_subtract`, `subtract_multiply`), not a multiply then a
    # subtract.
    fused_schur = True

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.blocks,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])

    # -- shape accessors ----------------------------------------------------
    @property
    def grid(self) -> int:
        """Number of block rows (= block cols); the paper's ``b``."""
        return self.blocks.shape[0]

    @property
    def block_size(self) -> int:
        """Side of one block; the paper's ``n / b``."""
        return self.blocks.shape[2]

    @property
    def n(self) -> int:
        return self.grid * self.block_size

    @property
    def dtype(self):
        return self.blocks.dtype

    # -- placement hooks ------------------------------------------------------
    def placed(self, blocks: jax.Array, op: str) -> "BlockMatrix":
        """`blocks`, produced by step `op`, in this matrix's placement."""
        return BlockMatrix(blocks)

    def node_split(self) -> tuple[tuple["BlockMatrix", ...], Callable]:
        """The four quadrants of a recursion node, and the arrange that
        puts four results back in their places."""
        return self.split(), type(self).arrange

    def place_panel(self, x: jax.Array, op: str) -> jax.Array:
        """A dense (rows, k) solve panel, produced by step `op`, in this
        matrix's placement."""
        return x

    def stack_rows(self, x1: jax.Array, x2: jax.Array) -> jax.Array:
        """[X1; X2]: the solve's two row panels as one."""
        return jnp.concatenate([x1, x2], axis=0)

    def leaf_inverse(self, solver: str = "linalg") -> "BlockMatrix":
        """Paper Algorithm 2 `if` branch: grid==1, invert the block in place.

        The paper deliberately does NOT collect the block to the driver
        ("we do a map which takes the only block of the RDD") — likewise we
        invert in situ on whichever device holds the block; no reshard is
        issued.
        """
        if self.grid != 1:
            raise ValueError(f"leaf_inverse expects grid==1, got {self.grid}")
        _bump("leaf_inversions")
        inv = LEAF_SOLVERS[solver](self.blocks[0, 0])
        return self.placed(inv[None, None], "leaf_inverse")

    # -- conversions ----------------------------------------------------------
    @classmethod
    def from_dense(cls, dense: jax.Array, block_size: int) -> "BlockMatrix":
        n = dense.shape[0]
        if dense.ndim != 2 or dense.shape[0] != dense.shape[1]:
            raise ValueError(f"expected square matrix, got {dense.shape}")
        if n % block_size:
            raise ValueError(f"n={n} not divisible by block_size={block_size}")
        b = n // block_size
        blocks = dense.reshape(b, block_size, b, block_size).transpose(0, 2, 1, 3)
        return cls(blocks)

    def to_dense(self) -> jax.Array:
        b, _, bs, _ = self.blocks.shape
        return self.blocks.transpose(0, 2, 1, 3).reshape(b * bs, b * bs)

    # -- paper methods (breakMat / xy fused into one trace-time split) ------
    def split(self) -> tuple["BlockMatrix", "BlockMatrix", "BlockMatrix", "BlockMatrix"]:
        """breakMat + _11/_12/_21/_22 of the paper, at trace time.

        Spark needs a tag+filter shuffle pass; on an already-sharded array
        this is pure indexing that XLA folds into the consumers.
        """
        b = self.grid
        if b % 2:
            raise ValueError(f"cannot split odd grid b={b}")
        h = b // 2
        _bump("splits")
        blk = self.blocks
        return (
            self.placed(blk[:h, :h], "split"),
            self.placed(blk[:h, h:], "split"),
            self.placed(blk[h:, :h], "split"),
            self.placed(blk[h:, h:], "split"),
        )

    @staticmethod
    def arrange(
        c11: "BlockMatrix", c12: "BlockMatrix", c21: "BlockMatrix", c22: "BlockMatrix"
    ) -> "BlockMatrix":
        """The paper's arrange: four quadrants -> one matrix (Algorithm 6).

        The quadrants are written into a zero grid placed FIRST (see
        `assemble_quadrants` on why not concatenate); the writes inherit
        its placement, so the result needs no second one.
        """
        _bump("arranges")
        h = c11.grid
        into = c11.placed(jnp.zeros((2 * h, 2 * h) + c11.blocks.shape[2:],
                                    c11.dtype), "arrange")
        return dataclasses.replace(c11, blocks=assemble_quadrants(
            c11.blocks, c12.blocks, c21.blocks, c22.blocks, into=into.blocks))

    # -- arithmetic ----------------------------------------------------------
    def subtract(self, other: "BlockMatrix") -> "BlockMatrix":
        _bump("subtracts")
        return self.placed(self.blocks - other.blocks, "subtract")

    def add(self, other: "BlockMatrix") -> "BlockMatrix":
        _bump("subtracts")  # same cost class as subtract in the paper's model
        return self.placed(self.blocks + other.blocks, "add")

    def scalar_mul(self, scalar) -> "BlockMatrix":
        _bump("scalar_muls")
        return self.placed(self.blocks * scalar, "scalar_mul")

    def neg(self) -> "BlockMatrix":
        return self.scalar_mul(-1.0)

    def transpose(self) -> "BlockMatrix":
        return BlockMatrix(self.blocks.transpose(1, 0, 3, 2))

    @classmethod
    def identity(cls, grid: int, block_size: int, dtype=jnp.float32) -> "BlockMatrix":
        eye_block = jnp.eye(block_size, dtype=dtype)
        grid_eye = jnp.eye(grid, dtype=dtype)[:, :, None, None]
        return cls(grid_eye * eye_block[None, None])

    @classmethod
    def zeros(cls, grid: int, block_size: int, dtype=jnp.float32) -> "BlockMatrix":
        return cls(jnp.zeros((grid, grid, block_size, block_size), dtype=dtype))
