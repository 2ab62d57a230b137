"""The plain f32 reference: what decides `correct`, and the controls.

Nothing here imports the program. An inverse X of A is judged by the
residual ‖A X − I‖_F/√n and a solve answer x of A x = b by ‖A x − b‖_F /
‖b‖_F, each product formed at HIGHEST precision from the benchmark's own
copy of A. The limit is the f32 bound the configuration states.

The controls put a plain solver in the program's place at a lower
precision: Newton–Schulz for an inverse, conjugate gradients for a solve.
`precision` is "highest" or "high" (XLA's HIGHEST, six bf16 passes on the
MXU, and HIGH, three), or "bf16": operands cast to bfloat16 with f32
accumulation, which is one MXU pass on the chip and the same arithmetic on
a CPU (where XLA computes f32 exactly at any `precision`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def matmul(a, b, precision: str = "highest"):
    if precision == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    lax_precision = {"highest": jax.lax.Precision.HIGHEST,
                     "high": jax.lax.Precision.HIGH}[precision]
    return jnp.matmul(a, b, precision=lax_precision)


@jax.jit
def inverse_residual(a, x):
    """‖A X − I‖_F / √n, the product at HIGHEST precision."""
    r = matmul(a, x) - jnp.eye(a.shape[0], dtype=a.dtype)
    return jnp.linalg.norm(r) / math.sqrt(a.shape[0])


@jax.jit
def solve_residual(a, x, b):
    """‖A x − b‖_F / ‖b‖_F, the product at HIGHEST precision."""
    return jnp.linalg.norm(matmul(a, x) - b) / jnp.linalg.norm(b)


@functools.partial(jax.jit, donate_argnums=0)
def add_update(a, u):
    """A + u uᵀ: the benchmark's own copy of a rank-k update."""
    return a + matmul(u, u.T)


@functools.partial(jax.jit, static_argnames=("precision", "iterations"))
def newton_schulz_inverse(a, precision: str, iterations: int = 10):
    """X ← X(2I − A X) from X₀ = I/3. For a spectrum in [1, 5] the error
    contracts from 2/3 and then squares at each step; ten steps leave the
    fixed point that `precision` allows."""
    x = jnp.eye(a.shape[0], dtype=jnp.float32) / 3.0
    for _ in range(iterations):
        x = 2.0 * x - matmul(x, matmul(a, x, precision), precision)
    return x


@functools.partial(jax.jit, static_argnames=("precision", "iterations"))
def cg_solve(a, b, precision: str, iterations: int = 40):
    """Conjugate gradients on every column of b at once (A SPD)."""
    x = jnp.zeros_like(b)
    r = b
    p = r
    rr = jnp.sum(r * r, axis=0)
    for _ in range(iterations):
        ap = matmul(a, p, precision)
        alpha = rr / jnp.maximum(jnp.sum(p * ap, axis=0), 1e-30)
        x = x + alpha * p
        r = r - alpha * ap
        rr_new = jnp.sum(r * r, axis=0)
        p = r + (rr_new / jnp.maximum(rr, 1e-30)) * p
        rr = rr_new
    return x


def replay_residuals(n: int, matrix, history: dict, answers: list,
                     panels, factors, solver=None) -> list[float]:
    """Residual of every kept service answer against its matrix version.

    `matrix(t)` makes tenant t's starting matrix; `history[t]` lists the
    factor-pool items of its updates in the order they were sent, so
    version v is the start plus the first v updates. `answers` holds
    (tenant, version, panel item, x); with `solver(a, b)` given, x is
    ignored and the solver answers in the program's place (a control).
    """
    out = []
    for t in sorted({ans[0] for ans in answers}):
        mine = sorted((ans for ans in answers if ans[0] == t),
                      key=lambda ans: ans[1])
        a = matrix(t)
        version = 0
        for _, v, item, x in mine:
            while version < v:
                a = add_update(a, factors[history[t][version]])
                version += 1
            b = panels[item]
            if solver is not None:
                x = solver(a, b)
            out.append(float(solve_residual(a, x, b)))
        del a
    return out
