"""Strassen 7-multiply block-grid multiply — the Stark engine (engine="strassen").

Stark (the SPIN authors' follow-up, PAPERS.md) replaces one classical block
multiply with Strassen's scheme at the grid level: split both operands into
quadrants, form 7 sub-products from quadrant sums/differences, and combine —
7 multiplies + 18 add/sub passes per level instead of 8 multiplies, giving
n^log2(7) asymptotics. We run the same recursion over the (g, g, bs, bs)
block grids the SPIN recursion already uses:

    m1 = (A11 + A22)(B11 + B22)     C11 = m1 + m4 − m5 + m7
    m2 = (A21 + A22) B11            C12 = m3 + m5
    m3 = A11 (B12 − B22)            C21 = m2 + m4
    m4 = A22 (B21 − B11)            C22 = m1 − m2 + m3 + m6
    m5 = (A11 + A12) B22
    m6 = (A21 − A11)(B11 + B12)
    m7 = (A12 − A22)(B21 + B22)

Three variants share this one recursion:

  * dense  — `strassen_matmul` on raw (n, n) operands (odd n pads to n+1).
  * grid   — `strassen_matmul_blocks` on (g, g, bs, bs) BlockMatrix grids;
             an odd grid pads to g+1 block rows/cols of zeros. ALL assembly
             (padding buffers and the quadrant combine) goes through zeros +
             dynamic_update_slice (`assemble_quadrants`) — never
             jnp.concatenate, which the XLA SPMD partitioner mis-lowers
             along sharded dimensions (see blockmatrix.assemble_quadrants).
  * mesh-resident — the same grid recursion under an active mesh: every
             intermediate (quadrant sums, the seven m_i, padding buffers,
             the combined output) is re-anchored with the grid-over-mesh
             rule and recorded in the spec ledger (core.placement:
             `constrain_grid`, `record_specs`), so no Strassen
             level gathers to dense. Base-case multiplies dispatch through
             `multiply_blocks`, whose shard_map SUMMA path is the fallback
             wherever the (halved, possibly padded) grid no longer splits
             evenly over the mesh.

The recursion stops (crossover cutoff) when the operand dimension
n = g·bs drops to `strassen_cutoff()` — below that the 18 add passes cost
more than the saved eighth multiply — and hands the leaf to the classical
base case (`kernels.strassen.ops`), which routes to the Pallas fused
kernels where they are compiled (TPU) or forced (SPIN_PALLAS_INTERPRET=1)
and Mosaic-legal, else to XLA einsum / SUMMA.

Like the multiply-engine contextvar, the cutoff env override is a
PROCESS-START switch for the jitted entry points: it is read at trace
time, so already-compiled executables keep the cutoff they were traced
with. Tests that vary the cutoff pass `cutoff=` explicitly or run the
eager (non-jitted) paths.

Op accounting: each split level bumps `strassen_adds` by 18 and each
classical leaf bumps `strassen_base_multiplies` by 1, so the op-count
oracle (verify.expected_strassen_counts) can check the exact 7/18 shape;
the BlockMatrix-level counters (multiplies/subtracts/...) stay engine-blind.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.obs.trace import TRACER as _TRACER

from .blockmatrix import _bump, assemble_quadrants
from .costmodel import STRASSEN_CUTOFF
from .placement import constrain_grid

__all__ = ["STRASSEN_CUTOFF_ENV", "strassen_cutoff", "strassen_matmul",
           "strassen_matmul_blocks", "strassen_schur_update_blocks"]

STRASSEN_CUTOFF_ENV = "SPIN_STRASSEN_CUTOFF"


def strassen_cutoff() -> int:
    """Operand dimension at/below which the recursion goes classical.

    Defaults to `costmodel.STRASSEN_CUTOFF` (the same constant the planner
    prices with, so the modeled and executed recursions agree); the
    SPIN_STRASSEN_CUTOFF env var overrides it — subject to the trace-time
    caveat in the module docstring.
    """
    from repro import envconfig

    raw = envconfig.env_int(STRASSEN_CUTOFF_ENV)
    return STRASSEN_CUTOFF if raw is None else max(raw, 0)


def _pad_grid(x: jax.Array, op: str) -> jax.Array:
    """Zero-pad an odd (g, g, ...) grid to (g+1, g+1, ...) for an even split.

    Zeros + dynamic_update_slice, not concatenate (sharded-concat XLA bug);
    the zero row/column is annihilated by the matching zero column/row of
    the other operand, so slicing the product back to g×g is exact.
    """
    g = x.shape[0]
    buf = constrain_grid(jnp.zeros((g + 1, g + 1) + x.shape[2:], x.dtype), op)
    return constrain_grid(jax.lax.dynamic_update_slice(
        buf, x, (0,) * x.ndim), op)


def _quads(x: jax.Array):
    h = x.shape[0] // 2
    return x[:h, :h], x[:h, h:], x[h:, :h], x[h:, h:]


# ---------------------------------------------------------------------------
# Grid variant (the engine mechanism under multiply_blocks)
# ---------------------------------------------------------------------------


def _default_base_blocks(a: jax.Array, b: jax.Array) -> jax.Array:
    from repro.kernels.strassen import ops as st_ops  # late: optional layer

    return st_ops.base_matmul_blocks(a, b)


def strassen_matmul_blocks(a: jax.Array, b: jax.Array, *,
                           cutoff: int | None = None,
                           base: Callable[[jax.Array, jax.Array], jax.Array]
                           | None = None) -> jax.Array:
    """C = A·B over (g, g, bs, bs) block grids via Strassen's recursion.

    cutoff=None reads `strassen_cutoff()`; base=None dispatches leaves
    through kernels.strassen.ops (Pallas-composed where legal).
    """
    if a.ndim != 4 or a.shape != b.shape or a.shape[0] != a.shape[1]:
        raise ValueError(
            f"expected matching square (g, g, bs, bs) grids, got {a.shape} "
            f"vs {b.shape}")
    if cutoff is None:
        cutoff = strassen_cutoff()
    g, bs = a.shape[0], a.shape[2]
    if g == 1 or g * bs <= cutoff:
        _bump("strassen_base_multiplies")
        if _TRACER.enabled:
            _TRACER.event("strassen.base", "strassen_level", grid=g,
                          block_size=bs, n=g * bs, op="classical_leaf")
        return (base or _default_base_blocks)(a, b)
    if _TRACER.enabled:
        _TRACER.event("strassen.split", "strassen_level", grid=g,
                      block_size=bs, n=g * bs, cutoff=cutoff,
                      op="seven_multiply_split")
    if g % 2:
        ap = _pad_grid(a, "strassen_pad")
        bp = _pad_grid(b, "strassen_pad")
        out = strassen_matmul_blocks(ap, bp, cutoff=cutoff, base=base)
        return constrain_grid(out[:g, :g], "strassen_unpad")

    a11, a12, a21, a22 = _quads(a)
    b11, b12, b21, b22 = _quads(b)

    def add(x, y):
        return constrain_grid(x + y, "strassen_add")

    def sub(x, y):
        return constrain_grid(x - y, "strassen_add")

    rec = functools.partial(strassen_matmul_blocks, cutoff=cutoff, base=base)
    m1 = rec(add(a11, a22), add(b11, b22))
    m2 = rec(add(a21, a22), b11)
    m3 = rec(a11, sub(b12, b22))
    m4 = rec(a22, sub(b21, b11))
    m5 = rec(add(a11, a12), b22)
    m6 = rec(sub(a21, a11), add(b11, b12))
    m7 = rec(sub(a12, a22), add(b21, b22))
    c11 = add(sub(add(m1, m4), m5), m7)
    c12 = add(m3, m5)
    c21 = add(m2, m4)
    c22 = add(sub(add(m1, m3), m2), m6)
    # 10 operand-side + 8 output-side elementwise passes per split level.
    _bump("strassen_adds", 18)
    into = constrain_grid(jnp.zeros((g, g) + a.shape[2:], a.dtype),
                          "strassen_combine")
    out = assemble_quadrants(c11, c12, c21, c22, into=into)
    return constrain_grid(out, "strassen_combine")


def strassen_schur_update_blocks(c: jax.Array, a: jax.Array, b: jax.Array, *,
                                 negate_c: bool,
                                 cutoff: int | None = None) -> jax.Array:
    """Strassen route for the fused Schur updates: A·B − C or C − A·B.

    When the whole product is one classical leaf (at/below the cutoff) the
    subtract fuses into the base kernel (`base_schur_update`: one Pallas
    kernel where legal). Above the cutoff the product is computed by the
    Strassen recursion and the subtract applied in the same multiply-then-
    subtract order as the unfused path, so XLA base cases stay bitwise
    identical to `multiply_blocks` + subtract.
    """
    if cutoff is None:
        cutoff = strassen_cutoff()
    g, bs = a.shape[0], a.shape[2]
    if g == 1 or g * bs <= cutoff:
        from repro.kernels.strassen import ops as st_ops

        _bump("strassen_base_multiplies")
        return st_ops.base_schur_update(c, a, b, negate_c=negate_c)
    prod = strassen_matmul_blocks(a, b, cutoff=cutoff)
    out = prod - c if negate_c else c - prod
    return constrain_grid(out, "strassen_schur")


# ---------------------------------------------------------------------------
# Dense variant (raw (n, n) operands — benchmarks, crossover measurement)
# ---------------------------------------------------------------------------


def _default_base_dense(a: jax.Array, b: jax.Array) -> jax.Array:
    from repro.kernels.strassen import ops as st_ops

    return st_ops.base_matmul(a, b)


def strassen_matmul(a: jax.Array, b: jax.Array, *,
                    cutoff: int | None = None,
                    base: Callable[[jax.Array, jax.Array], jax.Array]
                    | None = None) -> jax.Array:
    """C = A @ B on dense square (n, n) operands via Strassen's recursion.

    Odd n pads both operands to n+1 (zeros + dynamic_update_slice) for the
    even split and slices the product back — exact, since the padded row
    and column multiply to zero.
    """
    if a.ndim != 2 or a.shape != b.shape or a.shape[0] != a.shape[1]:
        raise ValueError(
            f"expected matching square (n, n) operands, got {a.shape} "
            f"vs {b.shape}")
    if cutoff is None:
        cutoff = strassen_cutoff()
    n = a.shape[0]
    if n <= max(cutoff, 1):
        _bump("strassen_base_multiplies")
        return (base or _default_base_dense)(a, b)
    if n % 2:
        pad = jnp.zeros((n + 1, n + 1), a.dtype)
        ap = jax.lax.dynamic_update_slice(pad, a, (0, 0))
        bp = jax.lax.dynamic_update_slice(pad, b, (0, 0))
        return strassen_matmul(ap, bp, cutoff=cutoff, base=base)[:n, :n]
    h = n // 2
    a11, a12, a21, a22 = a[:h, :h], a[:h, h:], a[h:, :h], a[h:, h:]
    b11, b12, b21, b22 = b[:h, :h], b[:h, h:], b[h:, :h], b[h:, h:]
    rec = functools.partial(strassen_matmul, cutoff=cutoff, base=base)
    m1 = rec(a11 + a22, b11 + b22)
    m2 = rec(a21 + a22, b11)
    m3 = rec(a11, b12 - b22)
    m4 = rec(a22, b21 - b11)
    m5 = rec(a11 + a12, b22)
    m6 = rec(a21 - a11, b11 + b12)
    m7 = rec(a12 - a22, b21 + b22)
    _bump("strassen_adds", 18)
    out = jnp.zeros((n, n), a.dtype)
    for (i, j), quad in zip(((0, 0), (0, 1), (1, 0), (1, 1)),
                            (m1 + m4 - m5 + m7, m3 + m5,
                             m2 + m4, m1 - m2 + m3 + m6)):
        out = jax.lax.dynamic_update_slice(out, quad, (i * h, j * h))
    return out
