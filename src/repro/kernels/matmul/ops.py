"""Jit'd public wrappers for the Pallas tiled matmul + fused Schur update.

Interpret mode is resolved through the package-wide policy
(`repro.kernels.pallas_interpret_default`): compiled on TPU, interpreted
elsewhere, overridable with ``SPIN_PALLAS_INTERPRET=1`` — so the same call
sites run on CPU (tests, CI) and TPU (production).

`block_gemm` is the vmapped form used by BlockMatrix multiplies; the
`grid_*` entry points are the multiply-engine mechanism: they flatten a
whole (bi, bk, bs, bs) block grid into its dense equivalent and contract it
with ONE Pallas kernel (k-accumulation in f32 VMEM scratch), instead of one
kernel per output block.

`matmul` and `schur_update` resolve each call's tiles (`auto_tiles` from
the shape and itemsizes, unless given) and book the call's grid steps as
`pallas_grid_steps` in an active `count_ops` context (trace time only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.blockmatrix import _bump

from .. import mesh_safe, pallas_interpret_default
from .kernel import call_tiles, matmul_pallas, schur_update_pallas

__all__ = ["matmul", "schur_update", "block_gemm", "grid_matmul",
           "grid_schur_update", "blocks_to_dense", "dense_to_blocks"]


def _booked(tiles, a, b, out_dtype, c=None) -> tuple[int, int, int]:
    """`kernel.call_tiles`, its grid steps booked as `pallas_grid_steps`."""
    bm, bn, bk = call_tiles(a, b, out_dtype or (a if c is None else c).dtype,
                            c, tiles)
    m, k = a.shape
    _bump("pallas_grid_steps", (m // bm) * (b.shape[-1] // bn) * (k // bk))
    return bm, bn, bk


def matmul(a: jax.Array, b: jax.Array,
           tiles: tuple[int, int, int] | None = None,
           out_dtype=None) -> jax.Array:
    """C = A @ B via the Pallas kernel (auto tile + interpret selection).

    out_dtype=float32 keeps the f32 accumulator un-rounded on the flush
    even for low-precision operands (see matmul_pallas).
    """
    return mesh_safe(functools.partial(
        matmul_pallas, tiles=_booked(tiles, a, b, out_dtype),
        interpret=pallas_interpret_default(), out_dtype=out_dtype))(a, b)


def schur_update(c: jax.Array, a: jax.Array, b: jax.Array, *,
                 alpha: float = 1.0, beta: float = -1.0,
                 tiles: tuple[int, int, int] | None = None,
                 out_dtype=None) -> jax.Array:
    """Fused β·C + α·(A@B) (see kernel.schur_update_pallas).

    out_dtype=float32 keeps the f32 accumulator un-rounded on the flush
    even for low-precision operands, matching `matmul`.
    """
    return mesh_safe(functools.partial(
        schur_update_pallas, alpha=alpha, beta=beta,
        tiles=_booked(tiles, a, b, out_dtype, c),
        interpret=pallas_interpret_default(), out_dtype=out_dtype))(c, a, b)


def blocks_to_dense(blocks: jax.Array) -> jax.Array:
    """(bi, bj, bs, bs) block grid -> dense (bi*bs, bj*bs) view."""
    bi, bj, bs, _ = blocks.shape
    return blocks.transpose(0, 2, 1, 3).reshape(bi * bs, bj * bs)


def dense_to_blocks(dense: jax.Array, bs: int) -> jax.Array:
    """Dense (bi*bs, bj*bs) -> (bi, bj, bs, bs) block grid."""
    m, n = dense.shape
    return dense.reshape(m // bs, bs, n // bs, bs).transpose(0, 2, 1, 3)


def grid_matmul(a_blocks: jax.Array, b_blocks: jax.Array) -> jax.Array:
    """C[i,j] = Σ_k A[i,k]·B[k,j] over block grids, as ONE Pallas GEMM.

    The grid contraction IS the dense product of the flattened operands, so
    the whole k-sum accumulates in the kernel's f32 VMEM scratch — no
    per-block partial products ever reach HBM (unlike `block_gemm`'s
    scan-of-kernels formulation).
    """
    bs = a_blocks.shape[2]
    out = matmul(blocks_to_dense(a_blocks), blocks_to_dense(b_blocks))
    return dense_to_blocks(out, bs)


def grid_schur_update(c_blocks: jax.Array, a_blocks: jax.Array,
                      b_blocks: jax.Array, *, alpha: float = 1.0,
                      beta: float = -1.0, out_dtype=None) -> jax.Array:
    """Fused β·C + α·(A@B) on (b, b, bs, bs) block grids, one kernel."""
    bs = c_blocks.shape[2]
    out = schur_update(blocks_to_dense(c_blocks), blocks_to_dense(a_blocks),
                       blocks_to_dense(b_blocks), alpha=alpha, beta=beta,
                       out_dtype=out_dtype)
    return dense_to_blocks(out, bs)


@functools.partial(jax.jit, static_argnames=("tiles",))
def block_gemm(a_blocks: jax.Array, b_blocks: jax.Array,
               tiles: tuple[int, int, int] | None = None) -> jax.Array:
    """Grid contraction C[i,j] = Σ_k A[i,k]·B[k,j] with Pallas inner GEMMs.

    a_blocks: (bi, bk, bs, bs); b_blocks: (bk, bj, bs, bs).
    The k-sum stays in f32 regardless of input dtype. Kept as the
    one-kernel-per-block formulation (vmap × scan); `grid_matmul` is the
    fused single-kernel engine path.
    """
    bi, bk, bs, _ = a_blocks.shape
    _, bj, _, _ = b_blocks.shape
    mm = functools.partial(matmul_pallas, tiles=tiles,
                           interpret=pallas_interpret_default())

    # vmap over (i, j); lax.map over k to bound trace size, accumulate f32.
    def one_pair(a_row, b_col):  # (bk, bs, bs), (bk, bs, bs)
        def step(carry, ab):
            a_blk, b_blk = ab
            return carry + mm(a_blk, b_blk).astype(jnp.float32), None
        init = jnp.zeros((bs, bs), jnp.float32)
        out, _ = jax.lax.scan(step, init, (a_row, b_col))
        return out.astype(a_blocks.dtype)

    pairwise = jax.vmap(jax.vmap(one_pair, in_axes=(None, 1)), in_axes=(0, None))
    return pairwise(a_blocks, b_blocks)
