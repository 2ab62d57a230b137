"""Pallas TPU kernels: tiled MXU matmul + fused Schur update, f32 accumulation.

The per-device GEMM under every distributed BlockMatrix multiply — the
compute hot-spot the paper identifies ("the primary bottleneck of inversion
algorithm is matrix multiplications", §6) — plus the fused Schur-complement
update of Algorithm 2: `V = A21·III − A22` and `C11 = I − III·C21` are a
multiply immediately followed by a subtract, so `schur_update_pallas`
computes `β·C + α·(A@B)` in ONE kernel. The C tile is read into the f32
accumulator at k-step 0 and the result flushed once — the intermediate
product never round-trips through HBM and the separate subtract pass
disappears.

Tiling: grid (m/bm, n/bn, k/bk); A tiles (bm, bk) and B tiles (bk, bn) are
staged HBM→VMEM by BlockSpec; the MXU sees (bm, bk)·(bk, bn) with bm/bn/bk
multiples of 128 (systolic-array aligned) or whole dims. The k axis is the
innermost, sequential grid dim: an (bm, bn) f32 VMEM scratch accumulator is
revisited across k steps and cast to the output dtype on the last one. The
C tile's index map ignores the k index, so it is fetched once and stays
VMEM-resident across the whole k sweep.

Tile sizes come from the call's shape and itemsizes alone (`auto_tiles`):
the Mosaic-legal tiling up to `TILE_MAX` with the fewest grid steps whose
VMEM reckoning (`gemm_vmem_bytes`) fits `TILE_VMEM_BUDGET`. Each step
costs a fixed overhead and re-reads its A and B tiles from HBM, so the
product's time follows its step count until the tiles are large: at 128³
tiles an 8192² f32 product takes 262,144 steps and runs about 3.5× slower
than XLA's HIGHEST dot on a TPU v5e. Every call asks Mosaic for its
reckoned VMEM through `vmem_limit_bytes` (`kernels.vmem_compiler_params`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import dot_precision

from .. import vmem_compiler_params

__all__ = ["matmul_pallas", "schur_update_pallas", "auto_tiles",
           "call_tiles", "gemm_vmem_bytes", "TILE_MAX", "TILE_VMEM_BUDGET"]

# The largest (bm, bn, bk) the rule considers (the chip sweep in PERF.md),
# and the VMEM a tiling's reckoning may take. XLA fuses a kernel's
# output into a consumer such as the recursion's in-place arrange, and a
# fused kernel is held to the default 16 MiB of scoped VMEM whatever its
# `vmem_limit_bytes` asks, so the budget is that default.
TILE_MAX = (1024, 1024, 1024)
TILE_VMEM_BUDGET = 16 * 2**20


def _legal(dim: int, cap: int) -> list[int]:
    """Mosaic-legal tiles of `dim` up to `cap`, largest first: the
    multiples of 128 that divide it, else the full dim."""
    return [t for t in range(min(cap, dim) // 128 * 128, 0, -128)
            if dim % t == 0] or [dim]


def gemm_vmem_bytes(bm: int, bn: int, bk: int, a_itemsize: int,
                    b_itemsize: int, out_itemsize: int,
                    c_itemsize: int = 0) -> int:
    """VMEM a (bm, bn, bk) tiling needs, at most.

    The pipeline's buffers: double-buffered A and B tiles (and, for the
    Schur update, C tiles), the double-buffered output tile and the f32
    accumulator. Then what the TPU compiler adds on its stack: the dot's
    f32 product tile, 512 KiB of its own scratch, and, where an operand is
    f32 (HIGHEST, six bf16 passes), the operands' split, bounded by four
    f32 copies of the A tile. The bound holds for every tiling measured
    by compiling for a v5e at shrinking `vmem_limit_bytes` (PERF.md).
    """
    buffers = (2 * (bm * bk * a_itemsize + bk * bn * b_itemsize)
               + 2 * bm * bn * (c_itemsize + out_itemsize) + 4 * bm * bn)
    split = 16 * bm * bk if max(a_itemsize, b_itemsize) >= 4 else 0
    return buffers + 4 * bm * bn + split + 2**19


def auto_tiles(m: int, n: int, k: int, *, a_itemsize: int = 4,
               b_itemsize: int = 4, out_itemsize: int = 4,
               c_itemsize: int = 0) -> tuple[int, int, int]:
    """The (bm, bn, bk) tiles for an (m, k) × (k, n) product.

    Per dim the candidates are Mosaic-legal (`_legal`) and at most
    `TILE_MAX`; of those whose `gemm_vmem_bytes` fits `TILE_VMEM_BUDGET`,
    the tiling with the fewest grid steps wins, ties going to the larger
    output tile (fewer re-reads of A and B). Where no candidate fits
    (a large dim with no 128-multiple divisor), the least VMEM wins.

    Compiled TPU lowering requires each block dim to be 128-aligned (lane)
    / 8-aligned (sublane) or equal to the full array dim — an arbitrary
    divisor like 96 of 192 lowers in interpret mode but fails Mosaic, so
    awkward dims fall back to whole-dimension blocks rather than to the
    biggest divisor. The block-grid entry points flatten (b, b, bs, bs)
    grids into dense operands whose dims are multiples of bs but not
    necessarily of 128; this keeps them legal everywhere.
    """
    def vmem(t):
        return gemm_vmem_bytes(*t, a_itemsize, b_itemsize, out_itemsize,
                               c_itemsize)

    cands = [(bm, bn, bk) for bm in _legal(m, TILE_MAX[0])
             for bn in _legal(n, TILE_MAX[1]) for bk in _legal(k, TILE_MAX[2])]
    fits = [t for t in cands if vmem(t) <= TILE_VMEM_BUDGET]
    if not fits:
        return min(cands, key=vmem)
    return min(fits, key=lambda t: ((m // t[0]) * (n // t[1]) * (k // t[2]),
                                    -t[0] * t[1]))


def call_tiles(a: jax.Array, b: jax.Array, out_dtype,
               c: jax.Array | None = None,
               tiles: tuple[int, int, int] | None = None
               ) -> tuple[int, int, int]:
    """The tiles an A @ B (or, with `c`, Schur-update) call runs with:
    `tiles` clamped to the dims, else `auto_tiles` from the operands'
    shapes and itemsizes."""
    m, k = a.shape
    n = b.shape[-1]
    bm, bn, bk = tiles or auto_tiles(
        m, n, k, a_itemsize=a.dtype.itemsize, b_itemsize=b.dtype.itemsize,
        out_itemsize=jnp.dtype(out_dtype).itemsize,
        c_itemsize=0 if c is None else c.dtype.itemsize)
    return min(bm, m), min(bn, n), min(bk, k)


def _matmul_kernel(a_ref, b_ref, out_ref, acc_ref, *, k_steps: int) -> None:
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
        precision=dot_precision(a_ref.dtype, b_ref.dtype))

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("tiles", "interpret", "out_dtype"))
def matmul_pallas(a: jax.Array, b: jax.Array,
                  tiles: tuple[int, int, int] | None = None,
                  interpret: bool = False, out_dtype=None) -> jax.Array:
    """C = A @ B for (m, k) × (k, n); dims must divide the chosen tiles.

    out_dtype (default: a's dtype) is what the f32 VMEM accumulator is cast
    to on the final flush — pass float32 to keep full accumulation
    precision out of low-precision operands (the solve panels do).
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} x {b.shape}")
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    bm, bn, bk = call_tiles(a, b, out_dtype, tiles=tiles)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"dims ({m},{n},{k}) must divide tiles ({bm},{bn},{bk})")
    k_steps = k // bk
    vmem = gemm_vmem_bytes(bm, bn, bk, a.dtype.itemsize, b.dtype.itemsize,
                           out_dtype.itemsize)

    kernel = functools.partial(_matmul_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=vmem_compiler_params(
            vmem, ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="matmul_pallas",
    )(a, b)


def _schur_update_kernel(c_ref, a_ref, b_ref, out_ref, acc_ref, *,
                         k_steps: int, alpha: float, beta: float) -> None:
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = beta * c_ref[...].astype(jnp.float32)

    acc_ref[...] += alpha * jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
        precision=dot_precision(a_ref.dtype, b_ref.dtype))

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("alpha", "beta", "tiles", "interpret",
                                    "out_dtype"))
def schur_update_pallas(c: jax.Array, a: jax.Array, b: jax.Array, *,
                        alpha: float = 1.0, beta: float = -1.0,
                        tiles: tuple[int, int, int] | None = None,
                        interpret: bool = False, out_dtype=None) -> jax.Array:
    """Fused `β·C + α·(A@B)` for (m, n) C, (m, k) A, (k, n) B.

    α=1, β=−1 is the paper's `V = A21·III − A22`; α=−1, β=1 is
    `C11 = I − III·C21`. Accumulation is f32 regardless of input dtype; the
    result is cast to `out_dtype` (default: C's dtype — pass float32 to
    keep the accumulator un-rounded out of low-precision operands, same
    contract as `matmul_pallas`). Tile shapes default to `auto_tiles`
    (Mosaic-legal: a multiple-of-128 divisor per dim, else the full dim —
    arbitrary divisors only lower in interpret mode).
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} x {b.shape}")
    if c.shape != (m, n):
        raise ValueError(f"update operand {c.shape} != product shape {(m, n)}")
    out_dtype = jnp.dtype(out_dtype or c.dtype)
    bm, bn, bk = call_tiles(a, b, out_dtype, c, tiles)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"dims ({m},{n},{k}) must divide tiles ({bm},{bn},{bk})")
    k_steps = k // bk
    vmem = gemm_vmem_bytes(bm, bn, bk, a.dtype.itemsize, b.dtype.itemsize,
                           out_dtype.itemsize, c.dtype.itemsize)

    kernel = functools.partial(_schur_update_kernel, k_steps=k_steps,
                               alpha=float(alpha), beta=float(beta))
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),   # C: k-invariant
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=vmem_compiler_params(
            vmem, ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="schur_update_pallas",
    )(c, a, b)
