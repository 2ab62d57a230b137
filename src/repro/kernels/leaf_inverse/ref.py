"""Pure-jnp oracles for the Gauss-Jordan leaf-inverse kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def leaf_inverse_ref(blocks: jax.Array) -> jax.Array:
    """LAPACK-semantics oracle: batched jnp.linalg.inv in f32."""
    inv = jnp.linalg.inv(blocks.astype(jnp.float32))
    return inv.astype(blocks.dtype)


def gauss_jordan_ref(blocks: jax.Array) -> jax.Array:
    """Step-exact oracle: the same pivot-free GJ sweep in pure jnp.

    Distinguishes kernel-implementation bugs (vs gauss_jordan_ref) from
    algorithmic error of unpivoted GJ itself (vs leaf_inverse_ref).
    """

    def one(a: jax.Array) -> jax.Array:
        bs = a.shape[0]
        m = jnp.concatenate(
            [a.astype(jnp.float32), jnp.eye(bs, dtype=jnp.float32)], axis=1)
        rows_i = jax.lax.broadcasted_iota(jnp.int32, (bs, 2 * bs), 0)
        cols_i = jax.lax.broadcasted_iota(jnp.int32, (bs, 2 * bs), 1)

        def step(k, m):
            row_k = jnp.sum(jnp.where(rows_i == k, m, 0.0), axis=0)
            pivot = jnp.sum(jnp.where(cols_i[0] == k, row_k, 0.0))
            row_k_n = row_k / pivot
            col_k = jnp.sum(jnp.where(cols_i == k, m, 0.0), axis=1)
            row_sel = (jnp.arange(bs) == k)
            factors = jnp.where(row_sel, 0.0, col_k)
            m = m - factors[:, None] * row_k_n[None, :]
            return jnp.where(rows_i == k, row_k_n[None, :], m)

        m = jax.lax.fori_loop(0, bs, step, m)
        return m[:, bs:].astype(a.dtype)

    return jax.vmap(one)(blocks)


def blocked_gauss_jordan_ref(blocks: jax.Array, panel: int) -> jax.Array:
    """Step-exact oracle for the BLOCKED GJ kernel, written as the textbook
    block sweep over the whole augmented [A | I]: per panel, D⁻¹ of its
    t×t diagonal block by the scalar sweep over [D | I_t], the normalised
    panel P = D⁻¹ · M[panel, :] with one step of refinement, and
    M[R, :] −= M[R, panel cols] · P for every other row. The kernel stores
    [A | I] in place and skips its identity and zero columns; the
    arithmetic on every entry it keeps is this one, so the two agree step
    by step."""
    def dot(x, y):
        return jnp.dot(x, y, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    def inv_diag(d: jax.Array) -> jax.Array:
        t = d.shape[0]
        m = jnp.concatenate([d, jnp.eye(t, dtype=jnp.float32)], axis=1)
        rows_i = jax.lax.broadcasted_iota(jnp.int32, (t, 2 * t), 0)

        def step(j, m):
            row_n = m[j] / m[j, j]
            factors = jnp.where(jnp.arange(t) == j, 0.0, m[:, j])
            m = m - factors[:, None] * row_n[None, :]
            return jnp.where(rows_i == j, row_n[None, :], m)

        return jax.lax.fori_loop(0, t, step, m)[:, t:]

    def one(a: jax.Array) -> jax.Array:
        bs = a.shape[0]
        t = panel
        m = jnp.concatenate(
            [a.astype(jnp.float32), jnp.eye(bs, dtype=jnp.float32)], axis=1)
        for b in range(0, bs, t):
            d = m[b:b + t, b:b + t]
            dinv = inv_diag(d)
            pan = dot(dinv, m[b:b + t])
            pan = pan + dot(dinv, m[b:b + t] - dot(d, pan))
            m = (m - dot(m[:, b:b + t], pan)).at[b:b + t].set(pan)
        return m[:, bs:].astype(a.dtype)

    return jax.vmap(one)(blocks)


def triangular_solve_ref(t: jax.Array, b: jax.Array, *, lower: bool = True,
                         unit_diagonal: bool = False) -> jax.Array:
    """LAPACK-semantics oracle (batched solve_triangular in f32)."""
    x = jax.vmap(lambda ti, bi: jax.scipy.linalg.solve_triangular(
        ti.astype(jnp.float32), bi.astype(jnp.float32), lower=lower,
        unit_diagonal=unit_diagonal))(t, b)
    return x.astype(b.dtype)
