"""Sherman–Morrison–Woodbury low-rank updates of a maintained SPIN inverse.

SPIN gives a fast *offline* inversion; a serving system (DESIGN.md §9)
keeps the inverse alive under churn. When the matrix mutates by a rank-k
correction A' = A + U Vᵀ, re-running Algorithm 2 pays the full recursion
again; the Woodbury identity revises the maintained inverse in O(n²k):

    (A + U Vᵀ)⁻¹ = A⁻¹ − (A⁻¹U) (I_k + Vᵀ A⁻¹ U)⁻¹ (Vᵀ A⁻¹)

Only three n×k panel products and one k×k "capacitance" solve touch the
big operand. The same identity in solve form (`smw_update_solve`) answers
(A + U Vᵀ) x = b from the *base* inverse without ever materializing the
updated one — the transient-perturbation path.

Every entry point dispatches on the maintained-inverse representation:

  * dense (n, n) array — one fused jitted program;
  * `BlockMatrix` — the panel products run block-local (`ijab,jbk->iak`),
    the rank-k correction is scattered back per block, no densification;
  * `ShardedBlockMatrix` — same block path with every produced panel/grid
    re-anchored to the mesh (the PR-3 no-replication contract: the updated
    inverse never gathers to dense, and the constraints land in the spec
    ledger like every other sharded op).

Block row/column *replacement* — the churn unit of the straggler-robust
inverse-maintenance literature (PAPERS.md) — is expressed as a rank-2·bs
Woodbury update by `block_update_factors`: replacing symmetric block row r
and column r with delta W (bs × n, D = W's diagonal block) factors as

    Δ = E_r W + (Wᵀ − E_r D) E_rᵀ  =  [E_r | Wᵀ − E_r D] [Wᵀ | E_r]ᵀ

`DriftTracker` carries what the refactor policy (repro.planner.
refactor_policy) prices: accumulated update rank, update count, and a
cheap probe-based residual estimate bounded by the conformance harness's
dtype-aware tolerance (`core.verify.residual_tolerance`).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from .blockmatrix import BlockMatrix, _bump
from .placement import constrain_grid, constrain_panel, mesh_fingerprint
from .precision import dot_precision
from .verify import residual_tolerance

__all__ = [
    "smw_update_inverse", "smw_update_solve", "block_update_factors",
    "apply_inverse", "add_low_rank", "DriftTracker",
    "estimate_inverse_residual",
]


def _accum(dtype) -> jnp.dtype:
    return (jnp.float32 if dtype in (jnp.bfloat16, jnp.float16, jnp.float32)
            else dtype)


def _as_panel(x: jax.Array) -> tuple[jax.Array, bool]:
    return (x[:, None], True) if x.ndim == 1 else (x, False)


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """a @ b at the operands' own precision (f32 stays f32 on the MXU)."""
    return jnp.matmul(a, b, precision=dot_precision(a.dtype, b.dtype))


# ---------------------------------------------------------------------------
# Dense path
# ---------------------------------------------------------------------------


@jax.jit
def _smw_inverse_dense(inv: jax.Array, u: jax.Array, v: jax.Array
                       ) -> jax.Array:
    f32 = inv.astype(jnp.float32)
    u32, v32 = u.astype(jnp.float32), v.astype(jnp.float32)
    p = _mm(f32, u32)                               # A⁻¹ U          (n, k)
    q = _mm(f32.T, v32).T                           # Vᵀ A⁻¹         (k, n)
    cap = jnp.eye(u.shape[1], dtype=jnp.float32) + _mm(v32.T, p)
    return (f32 - _mm(p, jnp.linalg.solve(cap, q))).astype(inv.dtype)


@jax.jit
def _smw_solve_dense(inv: jax.Array, u: jax.Array, v: jax.Array,
                     rhs: jax.Array) -> jax.Array:
    f32 = inv.astype(jnp.float32)
    u32, v32 = u.astype(jnp.float32), v.astype(jnp.float32)
    r32 = rhs.astype(jnp.float32)
    x0 = _mm(f32, r32)                              # A⁻¹ b
    p = _mm(f32, u32)                               # A⁻¹ U
    cap = jnp.eye(u.shape[1], dtype=jnp.float32) + _mm(v32.T, p)
    return (x0 - _mm(p, jnp.linalg.solve(cap, _mm(v32.T, x0)))
            ).astype(rhs.dtype)


# ---------------------------------------------------------------------------
# Block path (BlockMatrix / ShardedBlockMatrix)
# ---------------------------------------------------------------------------


def _blocks_apply(blocks: jax.Array, x: jax.Array) -> jax.Array:
    """X·x for a (b, b, bs, bs) grid and an (n, k) panel, f32 accumulate."""
    b, _, bs, _ = blocks.shape
    out = jnp.einsum("ijab,jbk->iak", blocks.astype(jnp.float32),
                     x.astype(jnp.float32).reshape(b, bs, x.shape[-1]),
                     preferred_element_type=jnp.float32,
                     precision=dot_precision(jnp.float32))
    return out.reshape(b * bs, x.shape[-1])


def _blocks_apply_t(blocks: jax.Array, x: jax.Array) -> jax.Array:
    """Xᵀ·x without materializing the transpose (grid + intra-block swap)."""
    b, _, bs, _ = blocks.shape
    out = jnp.einsum("ijab,iak->jbk", blocks.astype(jnp.float32),
                     x.astype(jnp.float32).reshape(b, bs, x.shape[-1]),
                     preferred_element_type=jnp.float32,
                     precision=dot_precision(jnp.float32))
    return out.reshape(b * bs, x.shape[-1])


def _smw_correction_blocks(blocks: jax.Array, p: jax.Array, m: jax.Array
                           ) -> jax.Array:
    """blocks − P·M scattered onto the block grid (P: (n,k), M: (k,n))."""
    b, _, bs, _ = blocks.shape
    corr = jnp.einsum("iak,kjb->ijab", p.reshape(b, bs, p.shape[-1]),
                      m.reshape(m.shape[0], b, bs),
                      preferred_element_type=jnp.float32,
                      precision=dot_precision(p.dtype, m.dtype))
    return (blocks.astype(jnp.float32) - corr).astype(blocks.dtype)


def _smw_inverse_blocks(blocks: jax.Array, u: jax.Array, v: jax.Array,
                        constrain_panel=None) -> jax.Array:
    anchor = constrain_panel or (lambda x, op: x)
    p = anchor(_blocks_apply(blocks, u), "smw_panel")         # A⁻¹ U
    qt = anchor(_blocks_apply_t(blocks, v), "smw_panel")      # (Vᵀ A⁻¹)ᵀ
    cap = (jnp.eye(u.shape[1], dtype=jnp.float32)
           + _mm(v.astype(jnp.float32).T, p))
    m = jnp.linalg.solve(cap, qt.T)                           # (k, n)
    return _smw_correction_blocks(blocks, p, m)


# ---------------------------------------------------------------------------
# Public dispatchers
# ---------------------------------------------------------------------------


def _sharded_type():
    # Late import: the mesh container belongs to the parallel layer, which
    # imports core.
    from repro.parallel import ShardedBlockMatrix

    return ShardedBlockMatrix


@functools.partial(jax.jit, static_argnames=("axes", "mesh_fp"))
def _smw_inverse_sharded_program(blocks: jax.Array, u: jax.Array,
                                 v: jax.Array, axes: tuple[str, str],
                                 mesh_fp: str) -> jax.Array:
    anchored = constrain_grid(blocks, "smw_input", axes)

    def anchor(x, op):
        return constrain_panel(x, op, axes)

    out = _smw_inverse_blocks(anchored, u, v, constrain_panel=anchor)
    return constrain_grid(out, "smw_update", axes)


def smw_update_inverse(inv, u: jax.Array, v: jax.Array):
    """Woodbury-revise a maintained inverse of A for A' = A + U Vᵀ.

    `inv`: dense (n, n) array, `BlockMatrix`, or `ShardedBlockMatrix`
    holding A⁻¹; returns the same representation holding (A + U Vᵀ)⁻¹ in
    O(n²k). U, V: (n, k) (or (n,) vectors — classic Sherman–Morrison).
    The sharded path runs as one jitted program whose every produced panel
    and the output grid are re-anchored to the mesh (no gather-to-dense);
    off-mesh it is bitwise-identical to the BlockMatrix path.
    """
    u, _ = _as_panel(u)
    v, _ = _as_panel(v)
    if isinstance(inv, _sharded_type()):
        _bump("smw_updates")
        blocks = _smw_inverse_sharded_program(
            inv.blocks, u, v, inv.axes, mesh_fingerprint())
        return dataclasses.replace(inv, blocks=blocks)
    if isinstance(inv, BlockMatrix):
        _bump("smw_updates")
        return BlockMatrix(_jit_smw_inverse_blocks(inv.blocks, u, v))
    _bump("smw_updates")
    return _smw_inverse_dense(inv, u, v)


_jit_smw_inverse_blocks = jax.jit(_smw_inverse_blocks)


def smw_update_solve(inv, u: jax.Array, v: jax.Array, rhs: jax.Array
                     ) -> jax.Array:
    """Solve (A + U Vᵀ) x = b from the BASE inverse, never forming A'⁻¹.

    x = A⁻¹b − (A⁻¹U) (I + VᵀA⁻¹U)⁻¹ Vᵀ (A⁻¹b). Same `inv`
    representations as `smw_update_inverse`; `rhs` is (n, c) or (n,).
    """
    u, _ = _as_panel(u)
    v, _ = _as_panel(v)
    rhs2, vector = _as_panel(rhs)
    if isinstance(inv, BlockMatrix):
        x0 = apply_inverse(inv, rhs2)
        p = apply_inverse(inv, u)
        cap = (jnp.eye(u.shape[1], dtype=jnp.float32)
               + _mm(v.astype(jnp.float32).T, p.astype(jnp.float32)))
        x = (x0.astype(jnp.float32)
             - _mm(p.astype(jnp.float32),
                   jnp.linalg.solve(cap, _mm(v.astype(jnp.float32).T,
                                             x0.astype(jnp.float32))))
             ).astype(rhs.dtype)
    else:
        x = _smw_solve_dense(inv, u, v, rhs2)
    return x[:, 0] if vector else x


@jax.jit
def _apply_inverse_dense(inv: jax.Array, rhs: jax.Array) -> jax.Array:
    acc = _accum(inv.dtype)
    return jnp.matmul(inv.astype(acc), rhs.astype(acc),
                      preferred_element_type=acc,
                      precision=dot_precision(acc)).astype(rhs.dtype)


@functools.partial(jax.jit, static_argnames=("compute", "accum"))
def _apply_inverse_dense_lowp(inv: jax.Array, rhs: jax.Array,
                              compute: str, accum: str) -> jax.Array:
    # The low-precision serve GEMM: operands stay at `compute` (bf16 on the
    # MXU — the default path above would upcast a bf16 inverse to f32 and
    # forfeit the halved HBM traffic), accumulation at `accum` (the same
    # f32-accumulator contract the Pallas kernels keep in VMEM).
    c, a = jnp.dtype(compute), jnp.dtype(accum)
    return jnp.matmul(inv.astype(c), rhs.astype(c),
                      preferred_element_type=a,
                      precision=dot_precision(c)).astype(rhs.dtype)


@functools.partial(jax.jit, static_argnames=("axes", "mesh_fp"))
def _apply_sharded_program(blocks: jax.Array, rhs: jax.Array,
                           axes: tuple[str, str], mesh_fp: str) -> jax.Array:
    anchored = constrain_grid(blocks, "apply_input", axes)
    out = _blocks_apply(anchored, rhs).astype(rhs.dtype)
    return constrain_panel(out, "apply_inverse", axes)


def apply_inverse(inv, rhs: jax.Array, *, precision=None) -> jax.Array:
    """X·B for a maintained inverse in any representation; B (n, c) or (n,).

    The O(n²c) serving fast path: one panel GEMM against the resident
    inverse (row-anchored to the mesh for `ShardedBlockMatrix`).
    `precision` (PrecisionPolicy | preset string | None) selects the serve
    GEMM's compute/accumulate dtypes on the dense path — a bf16-stored
    inverse under the "bf16" policy multiplies at bf16 with f32
    accumulation instead of being upcast; the block representations already
    accumulate in f32 and are unaffected.
    """
    rhs2, vector = _as_panel(rhs)
    if isinstance(inv, _sharded_type()):
        _bump("solve_applies")
        x = _apply_sharded_program(inv.blocks, rhs2, inv.axes,
                                   mesh_fingerprint())
    elif isinstance(inv, BlockMatrix):
        _bump("solve_applies")
        x = _jit_blocks_apply(inv.blocks, rhs2).astype(rhs.dtype)
    else:
        policy = None
        if precision is not None:
            from .precision import resolve_precision

            policy = resolve_precision(precision)
        if policy is not None and not policy.is_exact:
            x = _apply_inverse_dense_lowp(
                inv, rhs2, compute=policy.resolve_compute(inv.dtype),
                accum=policy.accum_dtype)
        else:
            x = _apply_inverse_dense(inv, rhs2)
    return x[:, 0] if vector else x


_jit_blocks_apply = jax.jit(_blocks_apply)


@jax.jit
def _add_low_rank_dense(a: jax.Array, u: jax.Array, v: jax.Array
                        ) -> jax.Array:
    return (a.astype(jnp.float32)
            + _mm(u.astype(jnp.float32), v.astype(jnp.float32).T)
            ).astype(a.dtype)


@functools.partial(jax.jit, static_argnames=("axes", "mesh_fp"))
def _add_low_rank_sharded_program(blocks: jax.Array, u: jax.Array,
                                  v: jax.Array, axes: tuple[str, str],
                                  mesh_fp: str) -> jax.Array:
    anchored = constrain_grid(blocks, "add_input", axes)
    out = _smw_correction_blocks(anchored,
                                 -u.astype(jnp.float32),
                                 v.astype(jnp.float32).T)
    return constrain_grid(out, "add_low_rank", axes)


def add_low_rank(a, u: jax.Array, v: jax.Array):
    """A + U Vᵀ in the operand's own representation (the matrix-side twin
    of `smw_update_inverse`; the service maintains both sides)."""
    u, _ = _as_panel(u)
    v, _ = _as_panel(v)
    if isinstance(a, _sharded_type()):
        blocks = _add_low_rank_sharded_program(
            a.blocks, u, v, a.axes, mesh_fingerprint())
        return dataclasses.replace(a, blocks=blocks)
    if isinstance(a, BlockMatrix):
        return BlockMatrix(_jit_add_low_rank_blocks(a.blocks, u, v))
    return _add_low_rank_dense(a, u, v)


@jax.jit
def _jit_add_low_rank_blocks(blocks: jax.Array, u: jax.Array, v: jax.Array
                             ) -> jax.Array:
    return _smw_correction_blocks(blocks, -u.astype(jnp.float32),
                                  v.astype(jnp.float32).T)


# ---------------------------------------------------------------------------
# Block row/column replacement as a rank-2·bs Woodbury update
# ---------------------------------------------------------------------------


def block_update_factors(delta_row: jax.Array, index: int, n: int
                         ) -> tuple[jax.Array, jax.Array]:
    """Factor a symmetric block row+column replacement as (U, V), Δ = U Vᵀ.

    `delta_row` = new − old block row `index` (bs, n); the matching column
    delta is its transpose (the maintained matrix stays symmetric), and
    `delta_row[:, index·bs:(index+1)·bs]` — counted once — must itself be
    symmetric. Returns (n, 2bs) factors:

        Δ = E_r W + (Wᵀ − E_r D) E_rᵀ,  U = [E_r | Wᵀ − E_r D], V = [Wᵀ | E_r]
    """
    bs = delta_row.shape[0]
    if delta_row.shape != (bs, n):
        raise ValueError(f"delta_row must be (bs, n), got {delta_row.shape}")
    if not 0 <= index < n // bs:
        raise ValueError(f"block index {index} out of range for n={n}, "
                         f"bs={bs}")
    e = jnp.zeros((n, bs), delta_row.dtype)
    e = jax.lax.dynamic_update_slice(
        e, jnp.eye(bs, dtype=delta_row.dtype), (index * bs, 0))
    d = jax.lax.dynamic_slice(delta_row, (0, index * bs), (bs, bs))
    wt = delta_row.T
    u = jnp.concatenate([e, wt - _mm(e, d)], axis=1)
    v = jnp.concatenate([wt, e], axis=1)
    return u, v


# ---------------------------------------------------------------------------
# Drift tracking
# ---------------------------------------------------------------------------


def estimate_inverse_residual(apply_a, inv, key: jax.Array, n: int,
                              probes: int = 2, *, precision=None) -> float:
    """Probe estimate of ‖A X − I‖∞: max_z ‖A(Xz) − z‖∞ / ‖z‖∞, O(n²·probes).

    `apply_a(panel)` applies the CURRENT matrix A' (base + accumulated
    updates) to an (n, probes) panel; `inv` is the maintained inverse in any
    `apply_inverse` representation. A randomized lower bound on the true
    residual — cheap enough to run per update, and the drift signal the
    refactor policy compares against the dtype tolerance. `precision`
    forwards to `apply_inverse` so the probe measures the SAME GEMM the
    policy serves with — certifying a bf16 serve path with f32 probes
    would under-report the residual requests actually see.
    """
    z = jax.random.normal(key, (n, probes), jnp.float32)
    x = apply_inverse(inv, z, precision=precision)
    r = apply_a(x).astype(jnp.float32) - z
    return float(jnp.max(jnp.abs(r)) / jnp.max(jnp.abs(z)))


@dataclasses.dataclass
class DriftTracker:
    """Accumulated-churn state of one maintained inverse.

    `tolerance` defaults from the conformance harness's dtype-aware bound
    (`core.verify.residual_tolerance`); `exceeded` is the drift half of the
    refactor trigger (the cost half lives in the planner's refactor policy).
    """

    tolerance: float
    update_rank: int = 0
    updates: int = 0
    residual_est: float = 0.0

    @classmethod
    def for_dtype(cls, dtype, scale: float = 10.0) -> "DriftTracker":
        """Drift bound = `scale` × the dtype's conformance residual bound:
        a fresh factorization sits near the bound itself, so drift is only
        meaningful some way above it."""
        return cls(tolerance=scale * residual_tolerance(dtype))

    def note(self, rank: int) -> None:
        self.update_rank += int(rank)
        self.updates += 1

    @property
    def exceeded(self) -> bool:
        return self.residual_est > self.tolerance

    def reset(self) -> None:
        self.update_rank = 0
        self.updates = 0
        self.residual_est = 0.0
