"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes the kernel body on CPU)."""

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.testing import make_spd
from repro.kernels.leaf_inverse import ops as gj_ops, ref as gj_ref
from repro.kernels.matmul import kernel as mm_kernel, ops as mm_ops, ref as mm_ref


@pytest.mark.parametrize("m,k,n", [
    (128, 128, 128), (256, 128, 384), (64, 64, 64), (128, 256, 128),
    (384, 384, 128), (32, 32, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_sweep(m, k, n, dtype):
    ka, kb = jax.random.split(jax.random.PRNGKey(m * k + n))
    a = jax.random.normal(ka, (m, k), jnp.float32).astype(dtype)
    b = jax.random.normal(kb, (k, n), jnp.float32).astype(dtype)
    got = mm_ops.matmul(a, b)
    want = mm_ref.matmul_ref(a, b)
    assert got.dtype == want.dtype
    err = jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
    # bf16 storage rounds the f32 accumulator: the kernel's tiled-k partial
    # sums may land one output ulp away from the monolithic-dot oracle.
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-3
    assert float(err) < tol, float(err)


@settings(max_examples=10, deadline=None)
@given(st.sampled_from([64, 128, 192]), st.sampled_from([64, 128]),
       st.sampled_from([64, 128, 256]), st.integers(0, 2 ** 31 - 1))
def test_matmul_property(m, k, n, seed):
    key = jax.random.PRNGKey(seed)
    a = jax.random.normal(key, (m, k))
    b = jax.random.normal(jax.random.fold_in(key, 1), (k, n))
    got = mm_ops.matmul(a, b, tiles=(64, 64, 64))
    assert jnp.allclose(got, mm_ref.matmul_ref(a, b), atol=1e-3)


def test_matmul_rejects_bad_shapes():
    a = jnp.zeros((100, 64))
    b = jnp.zeros((64, 64))
    with pytest.raises(ValueError):
        mm_ops.matmul(a, b, tiles=(64, 64, 64))   # 100 % 64 != 0
    with pytest.raises(ValueError):
        mm_ops.matmul(jnp.zeros((64, 32)), b)     # contraction mismatch


def test_block_gemm_matches_einsum():
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (2, 3, 64, 64))
    b = jax.random.normal(jax.random.fold_in(key, 1), (3, 4, 64, 64))
    got = mm_ops.block_gemm(a, b)
    want = jnp.einsum("ikab,kjbc->ijac", a, b)
    assert jnp.allclose(got, want, atol=1e-3)


@pytest.mark.parametrize("bs", [16, 32, 64, 128, 256])
def test_gauss_jordan_sweep(bs):
    a = make_spd(bs, jax.random.PRNGKey(bs))
    got = gj_ops.leaf_inverse(a)
    want = gj_ref.leaf_inverse_ref(a[None])[0]
    rel = jnp.linalg.norm(got - want) / jnp.linalg.norm(want)
    assert float(rel) < 1e-4


def test_gauss_jordan_batched_and_step_exact():
    blocks = jnp.stack([make_spd(32, jax.random.PRNGKey(i)) for i in range(5)])
    got = gj_ops.batched_leaf_inverse(blocks)
    # step-exact against the pure-jnp twin of the same algorithm
    assert jnp.allclose(got, gj_ref.gauss_jordan_ref(blocks), atol=1e-5)
    # algorithmically correct vs LAPACK oracle
    want = gj_ref.leaf_inverse_ref(blocks)
    assert jnp.allclose(got, want, atol=1e-3)


@settings(max_examples=8, deadline=None)
@given(st.sampled_from([16, 32, 64]), st.integers(0, 2 ** 31 - 1))
def test_gauss_jordan_property(bs, seed):
    a = make_spd(bs, jax.random.PRNGKey(seed))
    inv = gj_ops.leaf_inverse(a)
    resid = jnp.linalg.norm(inv @ a - jnp.eye(bs)) / bs ** 0.5
    assert float(resid) < 1e-3


# ------------------------------------------------- fused Schur update


@pytest.mark.parametrize("alpha,beta", [(1.0, -1.0), (-1.0, 1.0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_schur_update_fused_matches_ref(alpha, beta, dtype):
    """β·C + α·(A@B) in one kernel — the paper's V and C11 updates."""
    ka, kb, kc = jax.random.split(jax.random.PRNGKey(3), 3)
    a = jax.random.normal(ka, (96, 64), jnp.float32).astype(dtype)
    b = jax.random.normal(kb, (64, 128), jnp.float32).astype(dtype)
    c = jax.random.normal(kc, (96, 128), jnp.float32).astype(dtype)
    got = mm_ops.schur_update(c, a, b, alpha=alpha, beta=beta)
    want = mm_ref.schur_update_ref(c, a, b, alpha, beta)
    assert got.dtype == want.dtype
    err = jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-3
    assert float(err) < tol, float(err)


def test_schur_update_multi_k_step_accumulates_in_f32():
    """Tiny tiles force k_steps > 1: the C tile must be folded in exactly
    once (at step 0), not once per k step."""
    key = jax.random.PRNGKey(4)
    a = jax.random.normal(key, (64, 64))
    b = jax.random.normal(jax.random.fold_in(key, 1), (64, 64))
    c = jax.random.normal(jax.random.fold_in(key, 2), (64, 64))
    got = mm_ops.schur_update(c, a, b, tiles=(32, 32, 16))
    assert jnp.allclose(got, mm_ref.schur_update_ref(c, a, b), atol=1e-3)


def test_schur_update_rejects_bad_shapes():
    with pytest.raises(ValueError):
        mm_ops.schur_update(jnp.zeros((64, 32)), jnp.zeros((64, 64)),
                            jnp.zeros((64, 64)))
    with pytest.raises(ValueError):
        mm_ops.schur_update(jnp.zeros((64, 64)), jnp.zeros((64, 32)),
                            jnp.zeros((64, 64)))


def test_grid_matmul_matches_einsum():
    key = jax.random.PRNGKey(5)
    a = jax.random.normal(key, (2, 3, 32, 32))
    b = jax.random.normal(jax.random.fold_in(key, 1), (3, 4, 32, 32))
    got = mm_ops.grid_matmul(a, b)
    want = jnp.einsum("ikab,kjbc->ijac", a, b)
    assert jnp.allclose(got, want, atol=1e-3)


# ------------------------------------------------- tile rule


_RULE_DIMS = (64, 192, 1024, 4096, 8192, 16384, 32768)


def _rule_tiles(m, n, k, dtype, kernel, out_itemsize):
    size = jnp.dtype(dtype).itemsize
    return mm_kernel.auto_tiles(
        m, n, k, a_itemsize=size, b_itemsize=size, out_itemsize=out_itemsize,
        c_itemsize=out_itemsize if kernel == "schur_update" else 0)


@pytest.mark.parametrize("m", _RULE_DIMS)
@pytest.mark.parametrize("kernel", ["matmul", "schur_update"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_auto_tiles_are_mosaic_legal_and_fit_vmem(m, kernel, dtype):
    """Every tile is a multiple of 128 dividing its dim, or the full dim,
    and the tiling's VMEM reckoning stays under the kernels' cap."""
    from repro.kernels import VMEM_CAP_BYTES

    size = jnp.dtype(dtype).itemsize
    for n in _RULE_DIMS:
        for k in _RULE_DIMS:
            for out in {size, 4}:
                tiles = _rule_tiles(m, n, k, dtype, kernel, out)
                for t, dim in zip(tiles, (m, n, k)):
                    assert dim % t == 0, (m, n, k, tiles)
                    assert t == dim or t % 128 == 0, (m, n, k, tiles)
                c = out if kernel == "schur_update" else 0
                assert mm_kernel.gemm_vmem_bytes(
                    *tiles, size, size, out, c) <= VMEM_CAP_BYTES


@pytest.mark.parametrize("kernel", ["matmul", "schur_update"])
def test_auto_tiles_take_an_8192_product_in_few_grid_steps(kernel):
    n = 8192
    bm, bn, bk = _rule_tiles(n, n, n, jnp.float32, kernel, 4)
    assert (n // bm) * (n // bn) * (n // bk) <= 4096


@pytest.mark.parametrize("kernel", ["matmul", "schur_update"])
def test_rule_tiles_above_128_accumulate_over_k_steps(kernel):
    """At a shape where the rule picks tiles above 128 and several k steps,
    the default-tiled kernels match the oracle."""
    m = n = 256
    k = 2 * mm_kernel.TILE_MAX[2]
    bm, bn, bk = _rule_tiles(m, n, k, jnp.float32, kernel, 4)
    assert min(bm, bn, bk) > 128 and k // bk > 1
    key = jax.random.PRNGKey(6)
    a = jax.random.normal(key, (m, k))
    b = jax.random.normal(jax.random.fold_in(key, 1), (k, n))
    if kernel == "matmul":
        got, want = mm_ops.matmul(a, b), mm_ref.matmul_ref(a, b)
    else:
        c = jax.random.normal(jax.random.fold_in(key, 2), (m, n))
        got = mm_ops.schur_update(c, a, b)
        want = mm_ref.schur_update_ref(c, a, b)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-3


def test_pallas_grid_steps_count_the_rule_over_a_traced_inverse():
    """`pallas_grid_steps` books (m/bm)·(n/bn)·(k/bk) for every product of
    the recursion: per node at half-dim d, four products and two Schur
    updates of d³."""
    from repro.core import BlockMatrix, count_ops, spin_inverse
    from repro.core.multiply import multiply_engine

    n, grid = 2048, 16
    bs = n // grid
    with count_ops() as counts, multiply_engine("pallas"):
        jax.eval_shape(
            lambda x: spin_inverse(BlockMatrix.from_dense(x, bs),
                                   leaf_solver="pallas").blocks,
            jax.ShapeDtypeStruct((n, n), jnp.float32))

    def steps(d, kernel):
        bm, bn, bk = _rule_tiles(d, d, d, jnp.float32, kernel, 4)
        return (d // bm) * (d // bn) * (d // bk)

    want, nodes, d = 0, 1, n // 2
    while d >= bs:
        want += nodes * (4 * steps(d, "matmul") + 2 * steps(d, "schur_update"))
        nodes, d = nodes * 2, d // 2
    assert counts.pallas_grid_steps == want


# ------------------------------------------------- blocked Gauss-Jordan


@pytest.mark.parametrize("bs,panel", [(32, 8), (64, 16), (64, 64), (96, 32),
                                      (128, 32)])
def test_blocked_gauss_jordan_sweep(bs, panel):
    a = make_spd(bs, jax.random.PRNGKey(bs + panel))
    got = gj_ops.blocked_leaf_inverse(a, panel=panel)
    want = gj_ref.leaf_inverse_ref(a[None])[0]
    rel = jnp.linalg.norm(got - want) / jnp.linalg.norm(want)
    assert float(rel) < 1e-4
    # step-exact against the pure-jnp twin of the same blocked elimination
    twin = gj_ref.blocked_gauss_jordan_ref(a[None], panel)[0]
    assert jnp.allclose(got, twin, atol=1e-6)


def test_blocked_gauss_jordan_batched_and_panel_validation():
    blocks = jnp.stack([make_spd(32, jax.random.PRNGKey(i)) for i in range(4)])
    got = gj_ops.batched_blocked_leaf_inverse(blocks, panel=8)
    want = gj_ref.leaf_inverse_ref(blocks)
    assert jnp.allclose(got, want, atol=1e-3)
    with pytest.raises(ValueError):
        gj_ops.blocked_leaf_inverse(blocks[0], panel=7)   # 32 % 7 != 0


# ------------------------------------------------- blocked triangular solve


@pytest.mark.parametrize("lower", [True, False])
@pytest.mark.parametrize("unit", [True, False])
def test_triangular_solve_matches_scipy(lower, unit):
    key = jax.random.PRNGKey(11)
    # Off-diagonals scaled down: a unit-diagonal substitution amplifies
    # N(0,1) off-diagonals exponentially, which only tests overflow, not
    # the kernel. Compare with a relative tolerance for the same reason.
    full = jax.random.normal(key, (64, 64)) / 8 + 5 * jnp.eye(64)
    # pass the FULL matrix: the kernel must ignore the untargeted triangle
    # (solve_triangular semantics), which is what lets packed LU work.
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (64, 8))
    got = gj_ops.triangular_solve(full, rhs, lower=lower, unit_diagonal=unit,
                                  panel=16)
    want = gj_ref.triangular_solve_ref(full[None], rhs[None], lower=lower,
                                       unit_diagonal=unit)[0]
    rel = jnp.linalg.norm(got - want) / jnp.linalg.norm(want)
    assert float(rel) < 1e-5, float(rel)


def test_triangular_solve_lu_round_trip():
    """Packed-LU usage: L then U substitution solves the original system."""
    a = make_spd(64, jax.random.PRNGKey(12))
    rhs = jax.random.normal(jax.random.PRNGKey(13), (64, 4))
    lu, _, perm = jax.lax.linalg.lu(a)
    y = gj_ops.triangular_solve(lu, rhs[perm], lower=True, unit_diagonal=True)
    x = gj_ops.triangular_solve(lu, y, lower=False)
    assert jnp.allclose(x, jnp.linalg.solve(a, rhs), atol=1e-4)
