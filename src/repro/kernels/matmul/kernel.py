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
multiples of 128 (systolic-array aligned). The k axis is the innermost,
sequential grid dim: an (bm, bn) f32 VMEM scratch accumulator is revisited
across k steps and cast to the output dtype on the last one. The C tile's
index map ignores the k index, so it is fetched once and stays VMEM-resident
across the whole k sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import dot_precision

__all__ = ["matmul_pallas", "schur_update_pallas", "auto_tiles",
           "DEFAULT_TILES"]

DEFAULT_TILES = (128, 128, 128)  # (bm, bn, bk) — MXU-aligned


def auto_tiles(m: int, n: int, k: int, cap: int = 128) -> tuple[int, int, int]:
    """Mosaic-legal default tiles: per dim, the largest multiple of 128
    ≤ cap that divides it, else the FULL dim (untiled along that axis).

    Compiled TPU lowering requires each block dim to be 128-aligned (lane)
    / 8-aligned (sublane) or equal to the full array dim — an arbitrary
    divisor like 96 of 192 lowers in interpret mode but fails Mosaic, so
    awkward dims fall back to whole-dimension blocks rather than to the
    biggest divisor. The block-grid entry points flatten (b, b, bs, bs)
    grids into dense operands whose dims are multiples of bs but not
    necessarily of 128; this keeps them legal everywhere.
    """

    def best(dim: int) -> int:
        t = min(cap, dim) // 128 * 128
        while t >= 128:
            if dim % t == 0:
                return t
            t -= 128
        return dim

    return best(m), best(n), best(k)


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
    bm, bn, bk = tiles or DEFAULT_TILES
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"dims ({m},{n},{k}) must divide tiles ({bm},{bn},{bk})")
    k_steps = k // bk

    kernel = functools.partial(_matmul_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype or a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
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
    bm, bn, bk = tiles or auto_tiles(m, n, k)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"dims ({m},{n},{k}) must divide tiles ({bm},{bn},{bk})")
    k_steps = k // bk

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
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype or c.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="schur_update_pallas",
    )(c, a, b)
