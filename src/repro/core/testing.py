"""Test-matrix generators shared by tests and benchmarks.

The paper evaluates on random matrices; Strassen inversion needs invertible
leading principal blocks, which SPD guarantees — and the paper's stated class
is "square positive definite and invertible matrices".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["make_spd", "make_diag_dominant", "make_ill_conditioned_spd",
           "make_block_banded_spd", "make_spd_batch", "MATRIX_FAMILIES"]

# Generators build in f32 at full precision, so a seed gives the same matrix
# on every backend (a TPU's default f32 dot is one bf16 pass).
_HI = jax.lax.Precision.HIGHEST


def make_spd(n: int, key: jax.Array, dtype=jnp.float32,
             cond_boost: float = 1.0) -> jax.Array:
    """Well-conditioned SPD: B Bᵀ/n + boost·I (condition ~ O(10)/boost)."""
    b = jax.random.normal(key, (n, n), dtype=jnp.float32)
    a = (jnp.matmul(b, b.T, precision=_HI) / n
         + cond_boost * jnp.eye(n, dtype=jnp.float32))
    return a.astype(dtype)


def make_diag_dominant(n: int, key: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Strictly diagonally dominant (invertible, unpivoted-LU safe)."""
    m = jax.random.uniform(key, (n, n), minval=-1.0, maxval=1.0)
    d = jnp.sum(jnp.abs(m), axis=1) + 1.0
    return (m + jnp.diag(d)).astype(dtype)


def make_ill_conditioned_spd(n: int, key: jax.Array, dtype=jnp.float32,
                             cond: float = 1e6) -> jax.Array:
    """SPD with a prescribed condition number (log-spaced spectrum).

    Built as Q diag(λ) Qᵀ with λ log-spaced in [1/cond, 1] — the stress case
    for the recursion's leading-block inversions, where `make_spd`'s O(10)
    condition never exercises the error-growth term of the paper's analysis.
    """
    q, _ = jnp.linalg.qr(jax.random.normal(key, (n, n), dtype=jnp.float32))
    lam = jnp.logspace(-jnp.log10(cond), 0.0, n, dtype=jnp.float32)
    return jnp.matmul(q * lam[None, :], q.T, precision=_HI).astype(dtype)


def make_block_banded_spd(n: int, key: jax.Array, dtype=jnp.float32,
                          band: int = 32, bandwidth: int = 1) -> jax.Array:
    """Block-banded SPD: B Bᵀ of a block-banded factor + I.

    Zero blocks outside the band survive in the product's sparsity envelope
    (bandwidth doubles) — the structured class of the paper's Earth-science
    motivation, and a check that SPIN's quadrant recursion does not require
    dense quadrants.
    """
    if n % band:
        raise ValueError(f"n={n} not divisible by band={band}")
    nb = n // band
    f = jax.random.normal(key, (n, n), dtype=jnp.float32) / n ** 0.5
    i = jnp.arange(nb)
    mask = (jnp.abs(i[:, None] - i[None, :]) <= bandwidth).astype(jnp.float32)
    mask = jnp.kron(mask, jnp.ones((band, band), jnp.float32))
    f = f * mask
    return (jnp.matmul(f, f.T, precision=_HI)
            + jnp.eye(n, dtype=jnp.float32)).astype(dtype)


def make_spd_batch(batch: int, n: int, key: jax.Array,
                   dtype=jnp.float32, cond_boost: float = 1.0) -> jax.Array:
    """(batch, n, n) stack of independent SPD matrices (one key split each)."""
    keys = jax.random.split(key, batch)
    return jnp.stack([make_spd(n, k, dtype=dtype, cond_boost=cond_boost)
                      for k in keys])


# name -> generator(n, key, dtype=...) for the conformance matrix zoo.
# Batched families are exercised separately via `make_spd_batch` (they have a
# different arity); this table is the square single-matrix zoo.
MATRIX_FAMILIES = {
    "spd": make_spd,
    "diag_dominant": make_diag_dominant,
    "ill_conditioned_spd": make_ill_conditioned_spd,
    "block_banded_spd": make_block_banded_spd,
}
