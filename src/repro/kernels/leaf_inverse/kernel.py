"""Pallas TPU kernel: in-VMEM Gauss-Jordan inversion of one leaf block.

The paper's `if` branch (Algorithm 2) inverts a single (bs, bs) block on one
node with "any approach (e.g., LU, QR, SVD)". On TPU the natural leaf is a
pivot-free Gauss-Jordan sweep over the augmented system [A | I] held entirely
in VMEM: at step k the pivot row is extracted with an iota row-mask (no
dynamic slicing — masked full-matrix vector ops keep the VPU busy and avoid
lane-dim dynamic addressing), normalized, and an outer-product update
eliminates column k from every other row.

Pivot-free is safe for the paper's matrix class (positive definite /
diagonally dominant ⇒ nonzero pivots at every step of unpivoted elimination).

Layout: input (batch, bs, bs); grid = (batch,); one program inverts one
block. SPIN's leaf has batch=1; the SPIN-Shampoo optimizer batches all layer
factors through the same kernel.

Two blocked variants ride alongside the scalar sweep (the `pallas` leaf
solver / leaf-solve path): `blocked_leaf_inverse_pallas` runs the same GJ
elimination panel-by-panel so all cross-panel work is rank-t MXU GEMMs, and
`triangular_solve_pallas` is a blocked substitution for triangular (or
packed-LU) systems — the multi-RHS leaf solve without materializing an
inverse. Panel rows are addressed by slicing the VMEM *refs* at a dynamic
sublane offset (`pl.ds`); Mosaic has no lowering for a dynamic slice of a
loaded value.

VMEM budget. A v5e TensorCore has 128 MiB of VMEM, but Mosaic scopes a
kernel to 16 MiB unless the kernel asks for more. These kernels hold a
whole block, so each reckons its bytes from `bs` (`*_vmem_bytes` below:
double-buffered in/out blocks, the f32 scratch, and the loop's largest
live temporaries), asks for that plus a quarter through
`vmem_limit_bytes`, and refuses a block size whose reckoning passes
`VMEM_CAP_BYTES`. For f32 blocks that leaves (MiB reckoned / asked):

    kernel                       bs=512        bs=1024       bs=2048
    leaf_inverse_pallas          18 / 22.5     72 / 90       288: refused
    blocked_leaf_inverse_pallas  10.3 / 12.8   32.5 / 40.6   113: refused
    triangular_solve (128 cols)  4.5 / 5.6     12.9 / 16.1   41.6 / 52.0

`max_block_size` turns the same reckoning into the largest power-of-two
block each kernel accepts — the bound the planner respects on a TPU
signature and `tests/test_tpu_compile.py` compiles at.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import VMEM_CAP_BYTES, vmem_compiler_params

__all__ = ["leaf_inverse_pallas", "blocked_leaf_inverse_pallas",
           "triangular_solve_pallas", "default_panel", "VMEM_CAP_BYTES",
           "leaf_inverse_vmem_bytes", "blocked_leaf_inverse_vmem_bytes",
           "triangular_solve_vmem_bytes", "max_block_size"]

_HI = jax.lax.Precision.HIGHEST     # every dot here is f32 (one-hot gathers
                                    # must not round their operand to bf16)

_ROW_CHUNK = 256                    # rows per rank-t update step


def leaf_inverse_vmem_bytes(bs: int, itemsize: int = 4) -> int:
    """Scalar GJ: in/out blocks ×2 buffers, the (bs, 2bs) f32 scratch, and
    the step's full-width temporaries (loaded m, two masked reductions'
    inputs, the update, two iota planes) — 7 (bs, 2bs) f32 planes."""
    return 4 * bs * bs * itemsize + 7 * (2 * bs * bs * 4)


def blocked_leaf_inverse_vmem_bytes(bs: int, itemsize: int = 4,
                                    panel: int = 64) -> int:
    """Blocked GJ: in/out blocks ×2 buffers, the (bs, 2bs) f32 scratch,
    the panel and its masks (4 × (t, 2bs)), the one-hot gather (2bs, t),
    and one row chunk's rank-t update (3 × (R, 2bs))."""
    r = min(bs, _ROW_CHUNK)
    return (4 * bs * bs * itemsize + 2 * bs * bs * 4
            + 4 * panel * 2 * bs * 4 + 2 * bs * panel * 4
            + 3 * r * 2 * bs * 4)


def triangular_solve_vmem_bytes(bs: int, itemsize: int = 4,
                                k_tile: int = 128, panel: int = 64) -> int:
    """Triangular solve: the T block ×2 buffers, rhs/out column tiles ×2,
    the (bs, k_tile) f32 scratch, the one-hot gather (bs, t), and one row
    chunk of T with its update (2 × (R, bs) + (R, k_tile))."""
    r = min(bs, _ROW_CHUNK)
    return (2 * bs * bs * itemsize + 4 * bs * k_tile * itemsize
            + bs * k_tile * 4 + bs * panel * 4
            + 2 * r * bs * 4 + r * k_tile * 4)


def max_block_size(kernel: str, itemsize: int = 4) -> int:
    """Largest power-of-two bs whose VMEM reckoning fits `VMEM_CAP_BYTES`.

    kernel: "gauss_jordan" (`leaf_inverse_pallas`), "pallas"
    (`blocked_leaf_inverse_pallas`) or "triangular_solve".
    """
    reckon = {"gauss_jordan": leaf_inverse_vmem_bytes,
              "pallas": blocked_leaf_inverse_vmem_bytes,
              "triangular_solve": triangular_solve_vmem_bytes}[kernel]
    bs = 8
    while reckon(2 * bs, itemsize) <= VMEM_CAP_BYTES:
        bs *= 2
    return bs


def _gauss_jordan_kernel(a_ref, out_ref, m_ref) -> None:
    bs = a_ref.shape[1]
    # augmented system [A | I] in VMEM scratch
    m_ref[:, :bs] = a_ref[0].astype(jnp.float32)
    m_ref[:, bs:] = (jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
                     == jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
                     ).astype(jnp.float32)

    rows_i = jax.lax.broadcasted_iota(jnp.int32, (bs, 2 * bs), 0)
    cols_i = jax.lax.broadcasted_iota(jnp.int32, (bs, 2 * bs), 1)

    def step(k, _):
        m = m_ref[...]
        # pivot row k via row mask (VPU-friendly; no dynamic lane addressing)
        row_k = jnp.sum(jnp.where(rows_i == k, m, 0.0), axis=0)        # (2bs,)
        pivot = jnp.sum(jnp.where(cols_i[0] == k, row_k, 0.0))          # scalar
        row_k_n = row_k / pivot
        # column k of every row; zero the pivot row so it isn't eliminated
        col_k = jnp.sum(jnp.where(cols_i == k, m, 0.0), axis=1)         # (bs,)
        row_sel = (jax.lax.broadcasted_iota(jnp.int32, (bs,), 0) == k)
        factors = jnp.where(row_sel, 0.0, col_k)
        m = m - factors[:, None] * row_k_n[None, :]
        # write the normalized pivot row back
        m_ref[...] = jnp.where(rows_i == k, row_k_n[None, :], m)
        return 0

    jax.lax.fori_loop(0, bs, step, 0)
    out_ref[0] = m_ref[:, bs:].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def leaf_inverse_pallas(blocks: jax.Array, interpret: bool = False,
                        out_dtype=None) -> jax.Array:
    """Invert a batch of square blocks: (batch, bs, bs) -> (batch, bs, bs).

    The GJ sweep runs in the f32 VMEM scratch regardless of input dtype;
    out_dtype (default: the blocks' dtype) is what the result is cast to on
    the final write — pass float32 to keep the sweep un-rounded out of
    low-precision operands, same contract as the matmul kernels.
    """
    if blocks.ndim != 3 or blocks.shape[1] != blocks.shape[2]:
        raise ValueError(f"expected (batch, bs, bs), got {blocks.shape}")
    batch, bs, _ = blocks.shape
    out_dtype = out_dtype or blocks.dtype
    itemsize = max(blocks.dtype.itemsize, jnp.dtype(out_dtype).itemsize)
    return pl.pallas_call(
        _gauss_jordan_kernel,
        grid=(batch,),
        in_specs=[pl.BlockSpec((1, bs, bs), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, bs, bs), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(blocks.shape, out_dtype),
        scratch_shapes=[pltpu.VMEM((bs, 2 * bs), jnp.float32)],
        compiler_params=vmem_compiler_params(
            leaf_inverse_vmem_bytes(bs, itemsize), ("parallel",)),
        interpret=interpret,
        name="leaf_inverse_pallas",
    )(blocks)


# ---------------------------------------------------------------------------
# Blocked Gauss-Jordan: panel-wise elimination with rank-t MXU updates.
# ---------------------------------------------------------------------------


def default_panel(bs: int, cap: int = 64) -> int:
    """Largest panel width ≤ cap dividing bs (power-of-two bs -> cap)."""
    t = min(bs, cap)
    while bs % t:
        t -= 1
    return t


def _panel_gj(d: jax.Array, rest: jax.Array, offset) -> tuple[jax.Array,
                                                                jax.Array]:
    """Unblocked GJ on the t rows of a panel [D | rest]: afterwards D's
    t×t block starting at column `offset` is I, and `rest` (t, k) has had
    the same row operations. Two operands instead of one concatenated
    panel: a lane-dim concatenate at an unaligned offset does not lower."""
    t = d.shape[0]
    drow = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
    dcol = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    rrow = jax.lax.broadcasted_iota(jnp.int32, rest.shape, 0)
    sel_i = jax.lax.broadcasted_iota(jnp.int32, (t,), 0)

    def mini(j, carry):
        d, rest = carry
        row_d = jnp.sum(jnp.where(drow == j, d, 0.0), axis=0)
        row_r = jnp.sum(jnp.where(rrow == j, rest, 0.0), axis=0)
        piv = jnp.sum(jnp.where(dcol[0] == offset + j, row_d, 0.0))
        row_d, row_r = row_d / piv, row_r / piv
        colv = jnp.sum(jnp.where(dcol == offset + j, d, 0.0), axis=1)
        factors = jnp.where(sel_i == j, 0.0, colv)[:, None]
        d = jnp.where(drow == j, row_d[None, :], d - factors * row_d[None, :])
        rest = jnp.where(rrow == j, row_r[None, :],
                         rest - factors * row_r[None, :])
        return d, rest

    return jax.lax.fori_loop(0, t, mini, (d, rest))


def _blocked_gauss_jordan_kernel(a_ref, out_ref, m_ref, *, panel: int) -> None:
    """Blocked GJ sweep over [A | I]: the scalar elimination of the unblocked
    kernel runs only INSIDE a t-row panel; everything outside the panel is
    eliminated with a rank-t update (`factors @ panel` — an MXU GEMM
    instead of t vector steps), one row chunk at a time. Panel rows are
    read and written through the scratch ref at a dynamic sublane offset;
    panel *columns* are gathered by multiplying with a one-hot selector
    E_p, so no lane-dim dynamic addressing exists.
    """
    bs = a_ref.shape[1]
    t = panel
    r = default_panel(bs, _ROW_CHUNK)
    m_ref[:, :bs] = a_ref[0].astype(jnp.float32)
    m_ref[:, bs:] = (jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
                     == jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
                     ).astype(jnp.float32)

    e_rows = jax.lax.broadcasted_iota(jnp.int32, (2 * bs, t), 0)
    e_cols = jax.lax.broadcasted_iota(jnp.int32, (2 * bs, t), 1)
    chunk_rows = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0)

    def panel_step(p, _):
        base = pl.multiple_of(p * t, t)
        left, right = _panel_gj(m_ref[pl.ds(base, t), :bs],
                                m_ref[pl.ds(base, t), bs:], base)
        # Rank-t elimination of columns [base, base+t) from every other
        # row, chunk by chunk. E_p gathers those columns by matmul.
        e = (e_rows == base + e_cols).astype(jnp.float32)

        def chunk(c, _):
            row0 = pl.multiple_of(c * r, r)
            rows = m_ref[pl.ds(row0, r), :]
            factors = jnp.dot(rows, e, precision=_HI,
                              preferred_element_type=jnp.float32)   # (r, t)
            ridx = row0 + chunk_rows
            factors = jnp.where((ridx >= base) & (ridx < base + t), 0.0,
                                factors)
            m_ref[pl.ds(row0, r), :bs] = rows[:, :bs] - jnp.dot(
                factors, left, precision=_HI,
                preferred_element_type=jnp.float32)
            m_ref[pl.ds(row0, r), bs:] = rows[:, bs:] - jnp.dot(
                factors, right, precision=_HI,
                preferred_element_type=jnp.float32)
            return 0

        jax.lax.fori_loop(0, bs // r, chunk, 0)
        m_ref[pl.ds(base, t), :bs] = left
        m_ref[pl.ds(base, t), bs:] = right
        return 0

    jax.lax.fori_loop(0, bs // t, panel_step, 0)
    out_ref[0] = m_ref[:, bs:].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("panel", "interpret", "out_dtype"))
def blocked_leaf_inverse_pallas(blocks: jax.Array, panel: int | None = None,
                                interpret: bool = False,
                                out_dtype=None) -> jax.Array:
    """Blocked-GJ inverse of a batch of blocks: (batch, bs, bs) -> same.

    out_dtype (default: the blocks' dtype) is what the f32 panel sweep is
    cast to on the final write, matching `leaf_inverse_pallas`.
    """
    if blocks.ndim != 3 or blocks.shape[1] != blocks.shape[2]:
        raise ValueError(f"expected (batch, bs, bs), got {blocks.shape}")
    batch, bs, _ = blocks.shape
    t = panel or default_panel(bs)
    if bs % t:
        raise ValueError(f"panel={t} must divide block size {bs}")
    out_dtype = out_dtype or blocks.dtype
    itemsize = max(blocks.dtype.itemsize, jnp.dtype(out_dtype).itemsize)
    return pl.pallas_call(
        functools.partial(_blocked_gauss_jordan_kernel, panel=t),
        grid=(batch,),
        in_specs=[pl.BlockSpec((1, bs, bs), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, bs, bs), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(blocks.shape, out_dtype),
        scratch_shapes=[pltpu.VMEM((bs, 2 * bs), jnp.float32)],
        compiler_params=vmem_compiler_params(
            blocked_leaf_inverse_vmem_bytes(bs, itemsize, t), ("parallel",)),
        interpret=interpret,
        name="blocked_leaf_inverse_pallas",
    )(blocks)


# ---------------------------------------------------------------------------
# Blocked triangular solve: panel substitution with rank-t MXU updates.
# ---------------------------------------------------------------------------


def _tri_solve_kernel(t_ref, b_ref, out_ref, w_ref, *, panel: int,
                      lower: bool, unit: bool) -> None:
    """Solve T X = B for triangular T, panel by panel: solve the t×t
    diagonal block with a mini GJ sweep, then clear its columns from every
    pending row with a rank-t GEMM, one row chunk at a time. The
    untargeted triangle of T is masked out (solve_triangular semantics),
    so a packed-LU matrix can be passed for both the L (unit lower) and U
    (upper) sweeps. One program handles one column tile of B: columns of
    a triangular solve are independent.
    """
    bs = t_ref.shape[1]
    t = panel
    r = default_panel(bs, _ROW_CHUNK)
    npan = bs // t
    w_ref[...] = b_ref[0].astype(jnp.float32)

    e_rows = jax.lax.broadcasted_iota(jnp.int32, (bs, t), 0)
    e_cols = jax.lax.broadcasted_iota(jnp.int32, (bs, t), 1)
    drow = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    dcol = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    chunk_rows = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0)
    if unit:
        keep = (drow > dcol) if lower else (drow < dcol)
    else:
        keep = (drow >= dcol) if lower else (drow <= dcol)

    def step(pi, _):
        p = pi if lower else npan - 1 - pi
        base = pl.multiple_of(p * t, t)
        e = (e_rows == base + e_cols).astype(jnp.float32)
        t_rows = t_ref[0, pl.ds(base, t), :].astype(jnp.float32)
        d = jnp.dot(t_rows, e, precision=_HI,
                    preferred_element_type=jnp.float32)             # (t, t)
        d = jnp.where(keep, d, 0.0)
        if unit:
            d = d + (drow == dcol).astype(jnp.float32)
        # x_p = D^{-1} rhs_p via a mini GJ sweep on [D | rhs_p].
        _, x_p = _panel_gj(d, w_ref[pl.ds(base, t), :], 0)

        # Substitute into every still-pending row, chunk by chunk.
        def chunk(c, _):
            row0 = pl.multiple_of(c * r, r)
            tcols = jnp.dot(t_ref[0, pl.ds(row0, r), :].astype(jnp.float32),
                            e, precision=_HI,
                            preferred_element_type=jnp.float32)     # (r, t)
            ridx = row0 + chunk_rows
            pending = (ridx >= base + t) if lower else (ridx < base)
            tcols = jnp.where(pending, tcols, 0.0)
            w_ref[pl.ds(row0, r), :] = w_ref[pl.ds(row0, r), :] - jnp.dot(
                tcols, x_p, precision=_HI,
                preferred_element_type=jnp.float32)
            return 0

        jax.lax.fori_loop(0, bs // r, chunk, 0)
        w_ref[pl.ds(base, t), :] = x_p
        return 0

    jax.lax.fori_loop(0, npan, step, 0)
    out_ref[0] = w_ref[...].astype(out_ref.dtype)


def _column_tile(k: int) -> int:
    """RHS columns per program: 128 lanes when k allows, else all of k
    (the wrapper pads a wide k to a multiple of 128 first)."""
    return 128 if k % 128 == 0 else k


@functools.partial(jax.jit,
                   static_argnames=("panel", "lower", "unit_diagonal",
                                    "interpret"))
def triangular_solve_pallas(t: jax.Array, b: jax.Array,
                            panel: int | None = None, *,
                            lower: bool = True, unit_diagonal: bool = False,
                            interpret: bool = False) -> jax.Array:
    """Solve T X = B for a batch of triangular systems.

    t: (batch, bs, bs) triangular (the other triangle is ignored, so packed
    LU factors work); b: (batch, bs, k). Returns X with b's shape/dtype.
    The grid is (batch, column tiles of B): a wide RHS (the recursion's
    leaf sees up to ~n columns) is padded to whole 128-column tiles, so
    VMEM holds one tile of it at a time, never the whole panel.
    """
    if t.ndim != 3 or t.shape[1] != t.shape[2]:
        raise ValueError(f"expected (batch, bs, bs), got {t.shape}")
    if b.ndim != 3 or b.shape[:2] != t.shape[:2]:
        raise ValueError(f"rhs {b.shape} incompatible with {t.shape}")
    batch, bs, _ = t.shape
    k = b.shape[2]
    tp = panel or default_panel(bs)
    if bs % tp:
        raise ValueError(f"panel={tp} must divide block size {bs}")
    kp = k if k <= 128 else -(-k // 128) * 128
    bp = b if kp == k else jnp.pad(b, ((0, 0), (0, 0), (0, kp - k)))
    kt = _column_tile(kp)
    itemsize = max(t.dtype.itemsize, b.dtype.itemsize)
    kernel = functools.partial(_tri_solve_kernel, panel=tp, lower=lower,
                               unit=unit_diagonal)
    x = pl.pallas_call(
        kernel,
        grid=(batch, kp // kt),
        in_specs=[pl.BlockSpec((1, bs, bs), lambda i, j: (i, 0, 0)),
                  pl.BlockSpec((1, bs, kt), lambda i, j: (i, 0, j))],
        out_specs=pl.BlockSpec((1, bs, kt), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct(bp.shape, b.dtype),
        scratch_shapes=[pltpu.VMEM((bs, kt), jnp.float32)],
        compiler_params=vmem_compiler_params(
            triangular_solve_vmem_bytes(bs, itemsize, kt, tp),
            ("parallel", "parallel")),
        interpret=interpret,
        name="triangular_solve_pallas",
    )(t, bp)
    return x if kp == k else x[:, :, :k]
