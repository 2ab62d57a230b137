"""The K-FAC factor refresh through the optimizer's own entry,
`optim.spin_shampoo.invert_spd`, on seeded damped Gram stacks: against a
plain reference, bitwise against the per-matrix inverse, with the plan it
is given or one plan of the planner, and with its scope and counter."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import count_ops, spin_inverse_dense
from repro.core.solve import batched_op_scopes, spin_inverse_batched
from repro.obs.trace import BATCH, LAYOUT
from repro.optim import spin_shampoo
from repro.optim.spin_shampoo import damp, invert_spd

N, RANK, DEPTH, BS, DAMPING = 256, 128, 3, 64, 1e-3


def gram_stack(key, depth=DEPTH, n=N, rank=RANK):
    """Gradient-like Gram factors B Bᵀ/rank, B standard normal (n, rank):
    rank n/2, so the damped factor's condition number is about 5.8e3."""
    b = jax.random.normal(key, (depth, n, rank), jnp.float32)
    return jnp.einsum("lik,ljk->lij", b, b,
                      precision=jax.lax.Precision.HIGHEST) / rank


def plain_inverse(stack, damping):
    """(G + λ·tr(G)/n·I)⁻¹ per slice, in plain f32 `jax.numpy`."""
    n = stack.shape[-1]
    with jax.default_matmul_precision("highest"):
        lam = damping * jnp.trace(stack, axis1=-2, axis2=-1) / n
        a = stack + lam[:, None, None] * jnp.eye(n, dtype=stack.dtype)
        return jnp.stack([jnp.linalg.inv(s) for s in a])


def rel_error(x, ref):
    return float(jnp.max(jnp.linalg.norm(x - ref, axis=(1, 2))
                         / jnp.linalg.norm(ref, axis=(1, 2))))


# An inverse's forward error is about κ·ε·(growth) relative: at κ ≈ 5.7e3
# and f32's ε = 6e-8 that is 3.4e-4 before growth; the block recursion's
# Schur complements grow it about thirtyfold (8.6e-3 to 1.04e-2 read here on
# PRNGKey seeds 0-3, either plan), so 3e-2 passes f32 with threefold room.
# One bf16 pass (ε = 4e-3) puts κ·ε far above 1: the recursion then reads
# 18 to 96, and must fail.
TOL = 3e-2


@pytest.mark.parametrize("leaf,engine", [("linalg", "einsum"),
                                         ("pallas", "pallas")])
def test_invert_spd_matches_the_plain_reference(leaf, engine):
    stack = gram_stack(jax.random.PRNGKey(0))
    ref = plain_inverse(stack, DAMPING)
    x = invert_spd(stack, DAMPING, block_size=BS, leaf_solver=leaf,
                   engine=engine)
    assert x.shape == stack.shape and x.dtype == stack.dtype
    assert rel_error(x, ref) < TOL


def test_the_comparison_fails_in_bf16():
    stack = gram_stack(jax.random.PRNGKey(0))
    ref = plain_inverse(stack, DAMPING)
    low = spin_inverse_batched(damp(stack, DAMPING), BS, "linalg",
                               engine="einsum", precision="bf16")
    assert not rel_error(low.astype(jnp.float32), ref) < TOL


@pytest.mark.parametrize("leaf,engine", [("linalg", "einsum"),
                                         ("pallas", "pallas")])
def test_each_slice_is_bitwise_the_per_matrix_inverse(leaf, engine):
    stack = gram_stack(jax.random.PRNGKey(1))
    plan = {"block_size": BS, "leaf_solver": leaf, "engine": engine}
    x = invert_spd(stack, DAMPING, **plan)
    damped = damp(stack, DAMPING)
    for i in range(DEPTH):
        one = spin_inverse_dense(damped[i], BS, leaf, engine=engine)
        assert jnp.array_equal(x[i], one)
        assert jnp.array_equal(x[i], invert_spd(stack[i], DAMPING, **plan))


def _spy(monkeypatch):
    seen = []

    def fake(batch, block_size, leaf_solver, *, engine):
        seen.append((block_size, leaf_solver, engine))
        return batch

    monkeypatch.setattr(spin_shampoo, "spin_inverse_batched", fake)
    return seen


def test_without_a_plan_it_runs_one_plan_of_the_planner(monkeypatch):
    import repro.planner

    asked = []

    def planned(n, dtype, kind="inverse", block_size=None):
        asked.append((n, block_size))
        return 32, "gauss_jordan", "strassen"

    monkeypatch.setattr(repro.planner, "planned_fields", planned)
    seen = _spy(monkeypatch)
    stack = gram_stack(jax.random.PRNGKey(2))
    invert_spd(stack, DAMPING)
    invert_spd(stack, DAMPING, block_size=BS)
    invert_spd(stack, DAMPING, block_size=BS, leaf_solver="linalg",
               engine="einsum")
    assert asked == [(N, None), (N, BS)]
    assert seen == [(32, "gauss_jordan", "strassen"),
                    (BS, "gauss_jordan", "strassen"),
                    (BS, "linalg", "einsum")]


def test_the_default_plan_is_the_planners(monkeypatch):
    from repro.planner import planned_fields

    seen = _spy(monkeypatch)
    invert_spd(gram_stack(jax.random.PRNGKey(2)), DAMPING)
    assert seen == [planned_fields(N, jnp.float32)]


def test_batched_matrices_counts_the_stack():
    # Shapes no other test traces, so the counts come from a fresh trace.
    stack = gram_stack(jax.random.PRNGKey(3), depth=5, n=96, rank=48)
    with count_ops() as counts:
        invert_spd(stack, DAMPING, block_size=48, leaf_solver="linalg",
                   engine="einsum")
    assert counts.batched_matrices == 5
    assert counts.leaf_inversions == 2          # one slice's recursion
    with count_ops() as counts:
        spin_inverse_dense(damp(stack[0], DAMPING), 24, "linalg",
                           engine="einsum")
    assert counts.batched_matrices == 0 and counts.leaf_inversions == 4


def test_the_batched_programs_have_a_scope_map():
    scopes = batched_op_scopes(DEPTH, N, BS, "linalg", "einsum")
    assert len(scopes) == 2                   # the damping and the scan
    (damping,) = [ops for mod, ops in scopes.items() if "damp" in mod]
    assert set(damping.values()) == {(None, BATCH)}
    (scan,) = [ops for mod, ops in scopes.items() if "damp" not in mod]
    found = set(scan.values())
    assert {(None, BATCH), (None, LAYOUT), (2, "leaf")} <= found
    assert {lv for lv, _ in found} == {None, 0, 1, 2}
