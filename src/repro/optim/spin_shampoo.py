"""SPIN-Shampoo: Kronecker-factored second-order optimizer whose factor
inversions run through the paper's distributed Strassen solver.

For each matrix parameter W (d_in × d_out) with gradient G:

    L ← β L + (1−β) G Gᵀ          (d_in × d_in  Gram factor)
    R ← β R + (1−β) Gᵀ G          (d_out × d_out)
    every `update_every` steps:  L⁻¹, R⁻¹ ← SPIN((L,R) + λI)
    precondition:  P = L⁻¹ G R⁻¹   (K-FAC / full-matrix-AdaGrad exponent-1)

This makes large-matrix inversion a first-class training-loop operation —
the integration point of the paper's technique into the LM framework
(DESIGN.md §3). Factors of the big archs reach 6144² (granite-34b) and are
inverted as BlockMatrix grids on the training mesh; the block size is picked
so the grid is a power of two (SPIN's recursion requirement), falling back
to the Pallas Gauss-Jordan leaf for small/odd dims. Stacked-layer params
(L, d_in, d_out) vmap the factor update and invert factors batched.

Stale-inverse amortization (`update_every`) is the standard Shampoo trick;
between refreshes the cached inverses keep preconditioning.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import spin_inverse_batched, spin_inverse_dense
from repro.obs.trace import BATCH, step_scope
from .adamw import global_norm

__all__ = ["SpinShampooConfig", "spin_shampoo_init", "spin_shampoo_update",
           "damp", "invert_spd"]


@dataclasses.dataclass(frozen=True)
class SpinShampooConfig:
    lr: float = 1e-3
    beta: float = 0.95
    damping: float = 1e-3
    update_every: int = 10
    grad_clip: float = 1.0
    weight_decay: float = 0.0
    max_factor_dim: int = 8192      # fall back to diagonal beyond this
    grafting: bool = True           # graft step norm onto Adam's (stability)


@jax.jit
def damp(mat: jax.Array, damping) -> jax.Array:
    """mat + λ·tr/n·I in float32, per matrix of a stack, under the scope
    `spin.batch`.

    Damping is scaled by the mean eigenvalue (trace/n) so it is invariant to
    the gradient scale, the standard Shampoo/K-FAC choice. One program, so
    the damped copy is the only full-size array it writes."""
    with step_scope(BATCH):
        n = mat.shape[-1]
        lam = damping * (jnp.trace(mat, axis1=-2, axis2=-1) / n + 1e-12)
        damped = mat + lam[..., None, None] * jnp.eye(n, dtype=mat.dtype)
        return damped.astype(jnp.float32)


def invert_spd(mat: jax.Array, damping: float, *,
               block_size: int | None = None,
               leaf_solver: str | None = None,
               engine: str | None = None) -> jax.Array:
    """(mat + λ·tr/n·I)⁻¹ via SPIN, for one (n, n) factor or an (L, n, n)
    stack (`damp`, then the recursion).

    `block_size`, `leaf_solver` and `engine` are the plan the recursion
    runs, as `spin_inverse_dense` takes them. Those not given come from one
    plan of the planner's cost-model path (no live measurement — this runs
    inside `jax.lax.cond` branches at trace time), over the given block
    size where there is one, so each factor dimension lands at the bottom
    of its §4 U-curve instead of a hand-picked grid. Stacked-layer factors
    go through `spin_inverse_batched` — one compiled SPIN program for the
    whole stack instead of L unrolled copies, each slice bitwise the
    per-matrix inverse.
    """
    if None in (block_size, leaf_solver, engine):
        from repro.planner import planned_fields

        bs, leaf, eng = planned_fields(mat.shape[-1], jnp.float32,
                                       block_size=block_size)
        block_size = block_size or bs
        leaf_solver = leaf_solver or leaf
        engine = engine or eng
    damped = damp(mat, damping)
    invert = spin_inverse_dense if mat.ndim == 2 else spin_inverse_batched
    return invert(damped, block_size, leaf_solver,
                  engine=engine).astype(mat.dtype)


class _Factor(NamedTuple):
    l: jax.Array
    r: jax.Array
    linv: jax.Array
    rinv: jax.Array


class SpinShampooState(NamedTuple):
    """All fields are lists aligned with the flattened parameter leaves
    (Nones in `factors` mark non-matrix leaves that use the Adam fallback)."""
    step: jax.Array
    master: list
    factors: list
    m: list
    v: list


def _is_matrix(p: jax.Array, max_dim: int) -> bool:
    if p.ndim == 2:
        dims = p.shape
    elif p.ndim == 3:          # (layers, d_in, d_out) stacked
        dims = p.shape[1:]
    else:
        return False
    return all(16 <= d <= max_dim for d in dims)


def spin_shampoo_init(params, cfg: SpinShampooConfig) -> SpinShampooState:
    def factor(p):
        if not _is_matrix(p, cfg.max_factor_dim):
            return None
        lead = p.shape[:-2]
        din, dout = p.shape[-2:]
        eye_l = jnp.broadcast_to(jnp.eye(din, dtype=jnp.float32),
                                 (*lead, din, din))
        eye_r = jnp.broadcast_to(jnp.eye(dout, dtype=jnp.float32),
                                 (*lead, dout, dout))
        z = jnp.zeros_like
        return _Factor(z(eye_l), z(eye_r), eye_l, eye_r)

    leaves = jax.tree.leaves(params)
    return SpinShampooState(
        step=jnp.zeros((), jnp.int32),
        # copy=True: avoid master/param buffer aliasing (donation safety)
        master=[jnp.array(p, dtype=jnp.float32, copy=True) for p in leaves],
        factors=[factor(p) for p in leaves],
        m=[jnp.zeros(p.shape, jnp.float32) for p in leaves],
        v=[jnp.zeros(p.shape, jnp.float32) for p in leaves],
    )


def spin_shampoo_update(cfg: SpinShampooConfig, grads,
                        state: SpinShampooState, lr_scale=1.0):
    """Returns (new_params, new_state, grad_norm)."""
    step = state.step + 1
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9))
    refresh = (step % cfg.update_every == 1) | (step == 1)

    def upd(g, fac, m, v, master):
        g32 = g.astype(jnp.float32) * clip
        m_new = cfg.beta * m + (1 - cfg.beta) * g32
        v_new = cfg.beta * v + (1 - cfg.beta) * g32 * g32
        adam_dir = m_new / (jnp.sqrt(v_new) + 1e-8)
        if fac is None:
            direction = adam_dir
            fac_new = None
        else:
            gg_l = jnp.einsum("...ij,...kj->...ik", g32, g32)
            gg_r = jnp.einsum("...ji,...jk->...ik", g32, g32)
            l_new = cfg.beta * fac.l + (1 - cfg.beta) * gg_l
            r_new = cfg.beta * fac.r + (1 - cfg.beta) * gg_r
            linv = jax.lax.cond(refresh,
                                lambda: invert_spd(l_new, cfg.damping),
                                lambda: fac.linv)
            rinv = jax.lax.cond(refresh,
                                lambda: invert_spd(r_new, cfg.damping),
                                lambda: fac.rinv)
            pre = jnp.einsum("...ij,...jk,...kl->...il", linv, m_new, rinv)
            if cfg.grafting:    # graft Adam's per-tensor step size
                pre_n = jnp.linalg.norm(pre.reshape(-1))
                adam_n = jnp.linalg.norm(adam_dir.reshape(-1))
                pre = pre * (adam_n / jnp.maximum(pre_n, 1e-12))
            direction = pre
            fac_new = _Factor(l_new, r_new, linv, rinv)
        new_master = master - cfg.lr * lr_scale * (
            direction + cfg.weight_decay * master)
        return m_new, v_new, new_master, fac_new

    g_flat, treedef = jax.tree.flatten(grads)
    trip = [upd(g, fac, m, v, ma) for g, fac, m, v, ma in
            zip(g_flat, state.factors, state.m, state.v, state.master)]
    m = [t[0] for t in trip]
    v = [t[1] for t in trip]
    master = [t[2] for t in trip]
    factors = [t[3] for t in trip]
    new_params = treedef.unflatten(
        [ma.astype(g.dtype) for ma, g in zip(master, g_flat)])
    return new_params, SpinShampooState(step, master, factors, m, v), gnorm
