"""Distributed BlockMatrix multiply — the paper's dominant cost (§5.4).

The paper's Spark `multiply` replicates blocks with a cogroup so each output
block's operands land on one node. On a TPU mesh we provide three engines:

  * ``einsum``    — one `jnp.einsum` over the block grid; under pjit the XLA
                    SPMD partitioner inserts the collectives. This is the
                    paper-faithful baseline engine (declarative multiply, the
                    system chooses the shuffle — like Spark's cogroup).
  * ``allgather`` — shard_map SUMMA: all-gather A's k-panels along `model`
                    and B's k-panels along `data`, then one local grid GEMM.
                    Each block moves exactly (axis−1)/axis of its bytes —
                    strictly less traffic than cogroup replication.
  * ``ring``      — shard_map SUMMA with the B-panel gather unrolled into a
                    `lax.ppermute` ring, double-buffered so the step-(t+1)
                    transfer is in flight during the step-t GEMM
                    (compute/comm overlap; beyond-paper optimization).
  * ``strassen``  — the Stark 7-multiply engine (core/strassen.py): the
                    grid product is computed by Strassen's recursion —
                    7 sub-multiplies + 18 add passes per split level,
                    n^log2(7) asymptotics — down to a crossover cutoff,
                    where the classical leaves dispatch through the SUMMA
                    or Pallas paths (kernels/strassen). Mesh-resident:
                    every Strassen intermediate is re-anchored through the
                    spec ledger.
  * ``pallas``    — the fused-kernel engine: local grid contractions run as
                    ONE tiled Pallas GEMM (`kernels/matmul`) with the whole
                    k-sum in f32 VMEM scratch, and the Schur updates of
                    Algorithm 2 (`V = A21·III − A22`, `C11 = I − III·C21`)
                    fuse the trailing subtract into the same kernel
                    (`schur_update_blocks`), so the intermediate product
                    never round-trips through HBM. Under a mesh its SUMMA
                    is a k-chunked pipeline (`pallas_matmul_panels`): the
                    local k extent goes in s steps, the largest divisor of
                    its block count up to 4 where both mesh axes hold two
                    devices, and step t+1's A and B chunks are swapped
                    with the neighbours while step t's kernels run, the
                    k-sum in f32 throughout. At one local k block (or on
                    another mesh) s = 1: two all-gathers and one kernel,
                    as before. Off-TPU the kernels run in interpret mode
                    (tests/CI).

All engines accumulate in f32 (`preferred_element_type`) so bf16 inputs hit
the MXU with f32 accumulation — the TPU analogue of JBlas dgemm — and f32
operands multiply at f32 precision (`precision.dot_precision`), not in the
single bf16 pass a TPU gives an f32 dot by default.

Grid-to-mesh contract for the shard_map engines:
    A grid (i, k): i over 'data', k over 'model'
    B grid (k, j): k over 'data', j over 'model'
    C grid (i, j): i over 'data', j over 'model'

Every SUMMA all-gather (and the ring's and the pipeline's ppermutes) runs
under the `gather` step scope (repro.obs.trace) and books the bytes each
device receives as `OpCounts.gather_bytes`, the pipeline's steps after the
first also as `pipelined_gather_bytes`; a product whose grid does not
divide the mesh runs on every device and books its bs×bs GEMMs as
`replicated_block_gemms`.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Iterator

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.obs.trace import step_scope

from .blockmatrix import BlockMatrix, _bump
from .placement import active_mesh, grid_spec, mesh_axes
from .precision import dot_precision

__all__ = ["multiply", "multiply_engine", "current_engine", "validate_engine",
           "multiply_blocks", "matmul_blocks_einsum", "matmul_blocks_pallas",
           "ring_matmul_panels", "allgather_matmul_panels",
           "pallas_matmul_panels", "schur_update_blocks",
           "multiply_subtract", "subtract_multiply"]

_ENGINE: contextvars.ContextVar[str] = contextvars.ContextVar(
    "blockmatrix_multiply_engine", default="einsum"
)

_ENGINES = ("einsum", "allgather", "ring", "pallas", "strassen")


def validate_engine(engine: str | None) -> str | None:
    """Boundary check for `engine=` arguments: raise a clear ValueError HERE.

    Entry points call this before any jit/trace work so an unknown engine
    string fails at the API boundary with the registry in the message,
    instead of surfacing as a deep dispatch error mid-trace. None (inherit
    the ambient engine) passes through.
    """
    if engine is not None and engine not in _ENGINES:
        raise ValueError(f"unknown multiply engine {engine!r}; want {_ENGINES}")
    return engine


@contextlib.contextmanager
def multiply_engine(name: str) -> Iterator[None]:
    """Select the multiply engine (one of `_ENGINES`)."""
    if name not in _ENGINES:
        raise ValueError(f"unknown multiply engine {name!r}; want {_ENGINES}")
    token = _ENGINE.set(name)
    try:
        yield
    finally:
        _ENGINE.reset(token)


def current_engine() -> str:
    """The ambient multiply engine name ('einsum' unless overridden).

    Entry points that jit a whole program must resolve this BEFORE the jit
    boundary and pass it as a static argument: the engine contextvar is read
    at trace time, so an executable cached under one engine would otherwise
    silently serve another.
    """
    return _ENGINE.get()


def _accum_dtype(dtype) -> jnp.dtype:
    return jnp.float32 if dtype in (jnp.bfloat16, jnp.float16, jnp.float32) else dtype


def matmul_blocks_einsum(a: jax.Array, b: jax.Array) -> jax.Array:
    """C[i,j] = sum_k A[i,k] @ B[k,j] over (bi,bk,bs,bs)×(bk,bj,bs,bs) grids."""
    acc = _accum_dtype(a.dtype)
    out = jnp.einsum("ikab,kjbc->ijac", a, b, preferred_element_type=acc,
                     precision=dot_precision(a.dtype, b.dtype))
    return out.astype(a.dtype)


# ---------------------------------------------------------------------------
# shard_map engines (run INSIDE shard_map; see grid-to-mesh contract above).
# ---------------------------------------------------------------------------


def matmul_blocks_pallas(a: jax.Array, b: jax.Array) -> jax.Array:
    """C[i,j] = sum_k A[i,k] @ B[k,j] as ONE fused Pallas GEMM (f32 accum)."""
    from repro.kernels.matmul import ops as mm_ops  # late: kernels optional

    return mm_ops.grid_matmul(a, b)


def _gather(panel: jax.Array, axis_name: str, axis: int) -> jax.Array:
    """One SUMMA panel all-gather (tiled), under the `gather` step scope;
    books the bytes this device receives as `gather_bytes` (trace time)."""
    with step_scope("gather"):
        full = jax.lax.all_gather(panel, axis_name, axis=axis, tiled=True)
    _bump("gather_bytes", (full.size - panel.size) * panel.dtype.itemsize)
    return full


def _gather_panels(a_loc: jax.Array, b_loc: jax.Array, *, model_axis: str,
                   data_axis: str) -> tuple[jax.Array, jax.Array]:
    """SUMMA's row and column broadcast: A's k-panels along `model`, B's
    along `data`."""
    return (_gather(a_loc, model_axis, 1), _gather(b_loc, data_axis, 0))


def allgather_matmul_panels(a_loc: jax.Array, b_loc: jax.Array, *,
                            model_axis: str, data_axis: str) -> jax.Array:
    """SUMMA row/column broadcast as two tiled all-gathers + one local GEMM."""
    return matmul_blocks_einsum(*_gather_panels(
        a_loc, b_loc, model_axis=model_axis, data_axis=data_axis))


# The Pallas SUMMA takes its local k extent in at most this many steps:
# enough that all but a quarter of the bytes move behind a GEMM, few enough
# that each step's GEMMs keep the kernel's large tiles.
_MAX_SUMMA_STEPS = 4
_SWAP = ((0, 1), (1, 0))


def _summa_steps(local_k: int, model_axis: str, data_axis: str) -> int:
    """Steps of the Pallas SUMMA product over a local k extent of `local_k`
    blocks: its largest divisor up to `_MAX_SUMMA_STEPS` where both mesh
    axes hold two devices (a swap then moves one step's pieces), else 1."""
    if compat.axis_size(model_axis) != 2 or compat.axis_size(data_axis) != 2:
        return 1
    return max(s for s in range(1, _MAX_SUMMA_STEPS + 1) if local_k % s == 0)


def _swap(piece: jax.Array, axis_name: str) -> jax.Array:
    """The other device's `piece` along a two-device axis: one step of a
    SUMMA gather, under the `gather` step scope; books the bytes this
    device receives as `gather_bytes` (trace time)."""
    with step_scope("gather"):
        got = jax.lax.ppermute(piece, axis_name, _SWAP)
    _bump("gather_bytes", piece.size * piece.dtype.itemsize)
    return got


def pallas_matmul_panels(a_loc: jax.Array, b_loc: jax.Array,
                         c_loc: jax.Array | None = None, *,
                         alpha: float = 1.0, beta: float = 1.0,
                         model_axis: str, data_axis: str) -> jax.Array:
    """SUMMA with the Pallas kernels: β·C + α·A·B on this device's shards,
    or A·B without `c_loc`, pipelined over `_summa_steps` k-chunks.

    In one step, A's k-panels are gathered along `model` and B's along
    `data`, and one kernel takes the gathered panels. In s > 1 steps, step t
    swaps chunk t of A's local k-columns with the `model` neighbour and
    chunk t of B's local k-rows with the `data` neighbour (on a two-device
    axis the swap is the all-gather, byte for byte), then runs two kernels:
    the own A chunk with the B chunk of the same k, the received A chunk
    with the other. The swaps of chunk t+1 are issued before step t's
    kernels and awaited after them: an optimization barrier ties their
    operands to chunk t's arrival and to step t−1's product, so XLA neither
    hoists every swap to the start nor merges them, and only chunk 0's
    transfer blocks. Which B chunk meets the own A chunk depends on the
    device: its own on the mesh diagonal (A's local k is B's), the
    received one off it. Chunks go dense before they are sent, so only the
    local panels are copied into the kernels' layout. The k-sum stays in
    the accumulator dtype (f32) across steps and is cast once at the end.
    """
    from repro.kernels.matmul import ops as mm_ops  # late: kernels optional

    steps = _summa_steps(a_loc.shape[1], model_axis, data_axis)
    if steps == 1:
        a_full, b_full = _gather_panels(a_loc, b_loc, model_axis=model_axis,
                                        data_axis=data_axis)
        if c_loc is None:
            return mm_ops.grid_matmul(a_full, b_full)
        return mm_ops.grid_schur_update(c_loc, a_full, b_full, alpha=alpha,
                                        beta=beta)
    dense = mm_ops.blocks_to_dense
    a_parts = [dense(x) for x in jnp.split(a_loc, steps, axis=1)]
    b_parts = [dense(x) for x in jnp.split(b_loc, steps, axis=0)]
    diagonal = (jax.lax.axis_index(data_axis)
                == jax.lax.axis_index(model_axis))
    acc_dtype = _accum_dtype(a_loc.dtype)
    out_dtype = a_loc.dtype if c_loc is None else c_loc.dtype
    acc = None if c_loc is None else dense(c_loc)

    def send(t: int) -> tuple[jax.Array, ...]:
        a, b = a_parts[t], b_parts[t]
        if t:
            _bump("pipelined_gather_bytes",
                  (a.size + b.size) * a.dtype.itemsize)
        return a, _swap(a, model_axis), b, _swap(b, data_axis)

    def gemm(acc, a, b, first: bool) -> jax.Array:
        if acc is None:
            return mm_ops.matmul(a, b, out_dtype=acc_dtype)
        return mm_ops.schur_update(acc, a, b, alpha=alpha,
                                   beta=beta if first else 1.0,
                                   out_dtype=acc_dtype)

    held = send(0)
    for t in range(steps):
        if t + 1 < steps:
            a_parts[t + 1], b_parts[t + 1], held, acc = (
                jax.lax.optimization_barrier(
                    (a_parts[t + 1], b_parts[t + 1], held, acc)))
            ahead = send(t + 1)
        a_own, a_got, b_own, b_got = held
        acc = gemm(acc, a_own, jnp.where(diagonal, b_own, b_got), t == 0)
        acc = gemm(acc, a_got, jnp.where(diagonal, b_got, b_own), False)
        if t + 1 < steps:
            held = ahead
    return mm_ops.dense_to_blocks(acc.astype(out_dtype), a_loc.shape[2])


def ring_matmul_panels(a_loc: jax.Array, b_loc: jax.Array, *, model_axis: str,
                       data_axis: str) -> jax.Array:
    """SUMMA with the B gather unrolled into a double-buffered ppermute ring.

    A's k-panels are gathered once along `model` (rows then own full k).
    B's k-panels circulate around the `data` ring: at step t each rank holds
    the panel that started at rank (d_idx − t), multiplies it against the
    matching k-columns of A, and forwards it. The forward ppermute is issued
    BEFORE the GEMM so XLA overlaps transfer with compute. Every step's
    forward counts in `gather_bytes`, the last one's too.
    """
    a_full = _gather(a_loc, model_axis, 1)
    n_data = compat.axis_size(data_axis)
    if n_data == 1:
        return matmul_blocks_einsum(a_full, b_loc)
    _bump("gather_bytes", n_data * b_loc.size * b_loc.dtype.itemsize)
    d_idx = jax.lax.axis_index(data_axis)
    bk_panel = b_loc.shape[0]                  # B's local k extent
    perm = [(i, (i + 1) % n_data) for i in range(n_data)]

    bi_loc, bj_loc, bs = a_loc.shape[0], b_loc.shape[1], a_loc.shape[2]
    acc0 = jnp.zeros((bi_loc, bj_loc, bs, bs), a_loc.dtype)
    # Mark the fresh accumulator as device-varying so it can live in a carry
    # next to the (varying) rotating panel.
    acc0 = compat.pvary(acc0, (data_axis, model_axis))

    def step(t, carry):
        acc, panel = carry
        with step_scope("gather"):                             # in flight…
            next_panel = jax.lax.ppermute(panel, data_axis, perm)
        src = (d_idx - t) % n_data                 # whose slab is this?
        a_cols = jax.lax.dynamic_slice_in_dim(
            a_full, src * bk_panel, bk_panel, axis=1)
        acc = acc + matmul_blocks_einsum(a_cols, panel)        # …during GEMM
        return acc, next_panel

    acc, _ = jax.lax.fori_loop(0, n_data, step, (acc0, b_loc))
    return acc


def _mesh_axes_for(mesh, *grids) -> tuple[str, str] | None:
    """(data_axis, model_axis) when every (rows, cols) grid divides the mesh.

    Deep recursion levels shrink the grid below the mesh; shard_map needs
    even divisibility, so those levels fall back to the SPMD partitioner,
    and with their grid undivided there every device computes the whole
    product: they run replicated on every chip (`_book_replicated`).
    Explicit SUMMA only pays off when the grid covers the mesh.
    """
    if mesh is None:
        return None
    axes = mesh_axes(mesh)
    for rows, cols in grids:
        spec = grid_spec(rows, cols, mesh, axes)
        if spec[0] is None or spec[1] is None:
            return None
    return axes


def _book_replicated(mesh, a: jax.Array, b: jax.Array) -> None:
    """Book, as `replicated_block_gemms`, the bs×bs GEMMs of a product that
    runs outside SUMMA on a mesh, so that every device repeats it."""
    if mesh is not None:
        _bump("replicated_block_gemms", a.shape[0] * a.shape[1] * b.shape[1])


def _local_matmul(engine: str):
    return matmul_blocks_pallas if engine == "pallas" else matmul_blocks_einsum


# A Pallas kernel inside shard_map runs with the vma check off: its VMEM
# scratch refs carry no manual-axis typing, so the kernel body's first
# `scratch += loaded_operand` fails the check though every value is
# per-shard by construction (each shard's kernel reads only its own panels).
def _shard_map_multiply(a: jax.Array, b: jax.Array, engine: str) -> jax.Array:
    mesh = active_mesh()
    axes = _mesh_axes_for(mesh, (a.shape[0], a.shape[1]),
                          (b.shape[0], b.shape[1]))
    if axes is None:
        _book_replicated(mesh, a, b)
        return _local_matmul(engine)(a, b)
    data_axis, model_axis = axes
    fn = {"ring": ring_matmul_panels,
          "pallas": pallas_matmul_panels}.get(engine, allgather_matmul_panels)
    local = functools.partial(fn, model_axis=model_axis, data_axis=data_axis)
    return compat.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(data_axis, model_axis, None, None),
                  P(data_axis, model_axis, None, None)),
        out_specs=P(data_axis, model_axis, None, None),
        check_vma=engine != "pallas",
    )(a, b)


def multiply_blocks(a: jax.Array, b: jax.Array,
                    engine: str | None = None) -> jax.Array:
    """Engine dispatch on raw (bi,bk,bs,bs)×(bk,bj,bs,bs) block grids.

    The mechanism under `multiply` for every block container, on one
    device or on a mesh; engine=None reads the ambient `multiply_engine`
    context.
    """
    engine = validate_engine(engine) or _ENGINE.get()
    if engine == "einsum":
        return matmul_blocks_einsum(a, b)
    if engine == "strassen":
        from .strassen import strassen_matmul_blocks  # late: recursion layer

        return strassen_matmul_blocks(a, b)
    return _shard_map_multiply(a, b, engine)


def schur_update_blocks(c: jax.Array, a: jax.Array, b: jax.Array, *,
                        negate_c: bool, engine: str | None = None
                        ) -> jax.Array:
    """Fused multiply+subtract on block grids: A·B − C (negate_c=True, the
    paper's `V = A21·III − A22`) or C − A·B (negate_c=False, `C11 = I − VII`).

    Under the ``pallas`` engine the subtract folds into the GEMM kernel's
    f32 accumulator (one kernel, no product round-trip through HBM); for
    SUMMA placements the gathers stay and the fused kernel runs on the
    local shard. Under ``strassen`` the product runs the 7-multiply
    recursion (fusing the subtract into the base kernel when the whole
    product is one classical leaf — the Algorithm-2 V/C11 Schur updates
    get the Strassen win directly). Every other engine composes
    `multiply_blocks` with the elementwise subtract in exactly the op
    order the unfused recursion used, so non-pallas results are bitwise
    identical to multiply-then-subtract.
    """
    engine = validate_engine(engine) or _ENGINE.get()
    if engine == "strassen":
        from .strassen import strassen_schur_update_blocks  # late import

        return strassen_schur_update_blocks(c, a, b, negate_c=negate_c)
    if engine == "pallas":
        from repro.kernels.matmul import ops as mm_ops  # late: optional layer

        alpha, beta = (1.0, -1.0) if negate_c else (-1.0, 1.0)
        mesh = active_mesh()
        axes = _mesh_axes_for(mesh, (a.shape[0], a.shape[1]),
                              (b.shape[0], b.shape[1]),
                              (c.shape[0], c.shape[1]))
        if axes is None:
            _book_replicated(mesh, a, b)
            return mm_ops.grid_schur_update(c, a, b, alpha=alpha, beta=beta)
        data_axis, model_axis = axes

        def local(c_loc, a_loc, b_loc):
            return pallas_matmul_panels(a_loc, b_loc, c_loc, alpha=alpha,
                                        beta=beta, model_axis=model_axis,
                                        data_axis=data_axis)

        spec = P(data_axis, model_axis, None, None)
        return compat.shard_map(local, mesh=mesh, in_specs=(spec,) * 3,
                                out_specs=spec, check_vma=False)(c, a, b)
    prod = multiply_blocks(a, b, engine)
    return prod - c if negate_c else c - prod


def multiply(a: BlockMatrix, b: BlockMatrix) -> BlockMatrix:
    """The paper's `multiply` (§3.3): C = A · B on the block grid."""
    if a.grid != b.grid or a.block_size != b.block_size:
        raise ValueError(
            f"grid mismatch: {a.blocks.shape} vs {b.blocks.shape}")
    _bump("multiplies")
    _bump("block_gemms", a.grid ** 3)
    return a.placed(multiply_blocks(a.blocks, b.blocks), "multiply")


def _fused_op_counts(grid: int) -> None:
    # A fused Schur update is one multiply + one subtract of the paper's
    # Algorithm 2 — the op-count oracle (6/2/1 per level) must not notice
    # whether the engine fused them.
    _bump("multiplies")
    _bump("block_gemms", grid ** 3)
    _bump("subtracts")


def multiply_subtract(a: BlockMatrix, b: BlockMatrix,
                      c: BlockMatrix) -> BlockMatrix:
    """A·B − C (the paper's `V = IV − A22` with IV = A21·III), fused where
    `a`'s container fuses Schur updates (`BlockMatrix.fused_schur`)."""
    if a.grid != b.grid or a.grid != c.grid:
        raise ValueError(f"grid mismatch: {a.blocks.shape} vs "
                         f"{b.blocks.shape} vs {c.blocks.shape}")
    if not a.fused_schur:
        return multiply(a, b).subtract(c)
    _fused_op_counts(a.grid)
    return a.placed(schur_update_blocks(c.blocks, a.blocks, b.blocks,
                                        negate_c=True), "schur")


def subtract_multiply(c: BlockMatrix, a: BlockMatrix,
                      b: BlockMatrix) -> BlockMatrix:
    """C − A·B (the paper's `C11 = I − VII` with VII = III·C21), fused where
    `c`'s container fuses Schur updates (`BlockMatrix.fused_schur`)."""
    if a.grid != b.grid or a.grid != c.grid:
        raise ValueError(f"grid mismatch: {a.blocks.shape} vs "
                         f"{b.blocks.shape} vs {c.blocks.shape}")
    if not c.fused_schur:
        return c.subtract(multiply(a, b))
    _fused_op_counts(a.grid)
    return c.placed(schur_update_blocks(c.blocks, a.blocks, b.blocks,
                                        negate_c=False), "schur")
