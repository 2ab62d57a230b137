"""Pallas-engine parity suite (ISSUE 4): the fused-kernel engine vs the XLA
engine across the matrix zoo, on every entry point (dense, solve, batched,
sharded), plus the planner integration — enumeration gating, cost-model
pricing, and engine="pallas" plans round-tripping the schema-v2 cache with
the mesh/placement key respected."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import (count_ops, spin_inverse_dense, spin_inverse_sharded,
                        spin_inverse_batched, spin_solve_dense,
                        spin_solve_sharded)
from repro.core.multiply import multiply_engine
from repro.core.testing import MATRIX_FAMILIES, make_spd, make_spd_batch
from repro.kernels import PALLAS_INTERPRET_ENV, pallas_interpret_default
from repro.planner import (Plan, PlanCache, enumerate_plans, get_plan,
                           predict_cost, signature_for)

N, BS = 64, 16          # grid 4 — two recursion levels, small enough for
                        # interpret-mode kernels to stay fast on CPU


def _tol(dtype):
    return 5e-2 if dtype == jnp.bfloat16 else 2e-4


def _relerr(got, want):
    g = got.astype(jnp.float32)
    w = want.astype(jnp.float32)
    return float(jnp.linalg.norm(g - w) / (jnp.linalg.norm(w) + 1e-30))


# ------------------------------------------------------------- dense parity


@pytest.mark.parametrize("family", sorted(MATRIX_FAMILIES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_inverse_parity_across_matrix_zoo(family, dtype):
    """engine="pallas" must agree with the XLA engine on every zoo family
    (same recursion, same f32 accumulation — only the GEMM kernel differs),
    within dtype-aware tolerances.

    Well-conditioned families compare the inverses directly. The
    ill-conditioned family compares RESIDUAL QUALITY instead: κ≈1e6
    amplifies last-ulp GEMM rounding differences into O(1) relative
    differences between any two correct inverses (two block sizes of the
    same engine diverge as much), so "parity" there means the fused engine
    solves the problem as well as the XLA engine, not that it rounds
    identically.
    """
    if family == "ill_conditioned_spd" and dtype == jnp.bfloat16:
        pytest.skip("κ≈1e6 exceeds bf16's 8-bit mantissa: both engines "
                    "produce unusable inverses, so no parity statement "
                    "exists to pin (f32 covers the family)")
    make = MATRIX_FAMILIES[family]
    kwargs = {"band": BS} if family == "block_banded_spd" else {}
    # seed from the family NAME deterministically — hash() is salted per
    # process and would make this test input (and any failure) unreproducible
    seed = sum(ord(c) for c in family)
    a = make(N, jax.random.PRNGKey(seed), dtype=dtype, **kwargs)
    x_xla = spin_inverse_dense(a, BS, engine="einsum")
    x_pal = spin_inverse_dense(a, BS, engine="pallas")
    assert x_pal.dtype == x_xla.dtype
    if family == "ill_conditioned_spd":
        a32 = a.astype(jnp.float32)
        eye = jnp.eye(N, dtype=jnp.float32)
        r_xla = float(jnp.linalg.norm(a32 @ x_xla.astype(jnp.float32) - eye))
        r_pal = float(jnp.linalg.norm(a32 @ x_pal.astype(jnp.float32) - eye))
        assert r_pal < 10 * max(r_xla, 1e-6), (r_pal, r_xla)
    else:
        assert _relerr(x_pal, x_xla) < _tol(dtype), family


@pytest.mark.parametrize("leaf", ["pallas", "gauss_jordan"])
def test_pallas_leaf_solver_in_recursion(leaf):
    a = make_spd(128, jax.random.PRNGKey(7))
    got = spin_inverse_dense(a, 32, leaf_solver=leaf, engine="pallas")
    assert _relerr(got, jnp.linalg.inv(a)) < 1e-4


def test_solve_parity_and_pallas_leaf():
    a = make_spd(N, jax.random.PRNGKey(0))
    b = jax.random.normal(jax.random.PRNGKey(1), (N, 8))
    x_xla = spin_solve_dense(a, b, BS, engine="einsum")
    x_pal = spin_solve_dense(a, b, BS, engine="pallas")
    assert _relerr(x_pal, x_xla) < 2e-4
    # the inverse-free pallas leaf path: LU factor + two Pallas triangular
    # substitution sweeps
    x_tri = spin_solve_dense(a, b, BS, leaf_solver="pallas", engine="pallas")
    resid = jnp.linalg.norm(a @ x_tri - b) / jnp.linalg.norm(b)
    assert float(resid) < 1e-4


def test_pallas_engine_is_a_static_jit_argument():
    """Same contract as the XLA engines (PR 2): switching to the pallas
    engine must retrace, not serve the cached einsum executable."""
    a = make_spd(80, jax.random.PRNGKey(2))    # shape unique to this test:
    spin_inverse_dense(a, 20, engine="einsum")  # a jit-cache hit from an
    with count_ops() as cached:                 # earlier test would mask
        spin_inverse_dense(a, 20, engine="einsum")   # the retrace signal
    assert cached.multiplies == 0
    with count_ops() as retraced:
        spin_inverse_dense(a, 20, engine="pallas")
    assert retraced.multiplies > 0, "changed engine must retrace"


def test_engine_context_accepts_pallas():
    a = make_spd(N, jax.random.PRNGKey(3))
    with multiply_engine("pallas"):
        got = spin_inverse_dense(a, BS, engine="pallas")
    assert _relerr(got, jnp.linalg.inv(a)) < 1e-3
    with pytest.raises(ValueError):
        multiply_engine("fused").__enter__()


# ------------------------------------------------------- batched + sharded


def test_batched_engine_bitwise_matches_per_matrix():
    """spin_inverse_batched(engine=...) scans the SAME traced computation as
    the dense entry point, so each slice is bitwise-equal to the per-matrix
    call — engine included."""
    batch = make_spd_batch(3, N, jax.random.PRNGKey(4))
    got = spin_inverse_batched(batch, BS, engine="pallas")
    per = jnp.stack([spin_inverse_dense(batch[i], BS, engine="pallas")
                     for i in range(batch.shape[0])])
    assert jnp.array_equal(got, per)


def test_sharded_entry_points_accept_pallas_off_mesh():
    """Off-mesh the sharded recursion with engine="pallas" must agree with
    the dense pallas path (allclose, not bitwise: the dense path fuses the
    Schur updates into one kernel, the sharded one composes them)."""
    a = make_spd(N, jax.random.PRNGKey(5))
    want = spin_inverse_dense(a, BS, engine="pallas")
    got = spin_inverse_sharded(a, BS, engine="pallas")
    assert _relerr(got, want) < 2e-4
    b = jax.random.normal(jax.random.PRNGKey(6), (N, 4))
    xs = spin_solve_sharded(a, b, BS, engine="pallas")
    assert _relerr(xs, spin_solve_dense(a, b, BS, engine="pallas")) < 2e-4


# ------------------------------------------------------------ interpret env


def test_interpret_env_flag_forces_interpret(monkeypatch):
    """The backend alone decides: interpret off-TPU whatever the flag says,
    compiled on TPU — where a set flag is refused, never obeyed."""
    for value in ("1", "0", None):
        if value is None:
            monkeypatch.delenv(PALLAS_INTERPRET_ENV, raising=False)
        else:
            monkeypatch.setenv(PALLAS_INTERPRET_ENV, value)
        assert pallas_interpret_default() is True     # CPU test runner
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for value in ("1", "true", "0"):
        monkeypatch.setenv(PALLAS_INTERPRET_ENV, value)
        with pytest.raises(RuntimeError, match=PALLAS_INTERPRET_ENV):
            pallas_interpret_default()
    monkeypatch.delenv(PALLAS_INTERPRET_ENV)
    assert pallas_interpret_default() is False
    monkeypatch.undo()
    # and the kernels still produce correct results in interpret mode
    from repro.kernels.matmul import ops as mm_ops

    a = jax.random.normal(jax.random.PRNGKey(8), (32, 32))
    want = jnp.matmul(a, a, precision=jax.lax.Precision.HIGHEST)
    assert jnp.allclose(mm_ops.matmul(a, a), want, atol=1e-4)


def test_ci_interpret_job_env_is_inherited(monkeypatch):
    """The CI job's exported flag forces the optional Pallas routes
    off-TPU; on a TPU backend the same environment is an error."""
    from repro.kernels import pallas_routes_forced

    monkeypatch.setenv(PALLAS_INTERPRET_ENV, "1")
    assert pallas_routes_forced() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="compiled on TPU"):
        pallas_routes_forced()


# ------------------------------------------------------------ planner wiring


def test_pallas_enumeration_gated_by_backend():
    """pallas is enumerated by default on TPU signatures, opt-in elsewhere
    (interpret mode must never be auto-measured on CPU sweeps)."""
    tpu = signature_for("inverse", 256, jnp.float32, backend="tpu", device_kind="TPU v5 lite",
                        device_count=1, cores=1)
    assert "pallas" in {p.multiply_engine for p in enumerate_plans(tpu)}
    cpu = signature_for("inverse", 256, jnp.float32, backend="cpu",
                        device_count=1, cores=8)
    assert "pallas" not in {p.multiply_engine for p in enumerate_plans(cpu)}
    forced = enumerate_plans(cpu, engines=("pallas",))
    assert forced and all(p.multiply_engine == "pallas" for p in forced)


def test_predict_cost_prices_pallas_out_on_cpu():
    sig = signature_for("inverse", 256, jnp.float32, backend="cpu",
                        device_count=1, cores=8)
    pallas = predict_cost(sig, Plan(block_size=64, multiply_engine="pallas"))
    einsum = predict_cost(sig, Plan(block_size=64, multiply_engine="einsum"))
    assert pallas > 10 * einsum, "interpret-mode engine must be priced out"


def test_predict_cost_credits_fused_update_on_tpu():
    """The roofline charges XLA engines the Schur-update subtract traffic;
    the fused kernel is exempt, so pallas must model strictly cheaper for
    b > 1 and identical at b = 1 (no multiplies to fuse)."""
    sig = signature_for("inverse", 1 << 14, jnp.float32, backend="tpu", device_kind="TPU v5 lite",
                        device_count=16, cores=16)
    n = sig.n
    pal = predict_cost(sig, Plan(block_size=n // 8, multiply_engine="pallas"))
    xla = predict_cost(sig, Plan(block_size=n // 8, multiply_engine="einsum"))
    assert pal < xla
    pal1 = predict_cost(sig, Plan(block_size=n, multiply_engine="pallas"))
    xla1 = predict_cost(sig, Plan(block_size=n, multiply_engine="einsum"))
    assert pal1 == pytest.approx(xla1)


def test_pallas_plan_round_trips_schema_v2_cache(tmp_path):
    """A planned engine="pallas" plan must persist and recall through the
    schema-v2 cache: same execution key from a fresh cache object, no
    re-enumeration drift, and the mesh/placement signature dimensions keep
    it from leaking into other contexts."""
    path = str(tmp_path / "plans.json")
    plan1 = get_plan("inverse", 128, jnp.float32, measure=False,
                     cache=PlanCache(path), engines=("pallas",),
                     leaf_solvers=("linalg",))
    assert plan1.multiply_engine == "pallas"
    plan2 = get_plan("inverse", 128, jnp.float32, measure=False,
                     cache=PlanCache(path), engines=("pallas",),
                     leaf_solvers=("linalg",))
    assert plan2.execution_key() == plan1.execution_key()

    # the raw cache entry honors mesh/placement keying (schema v2)
    sig = signature_for("inverse", 128, jnp.float32,
                        constraint="engines=pallas;leaf_solvers=linalg")
    cache = PlanCache(path)
    assert cache.get(sig) is not None
    meshed = signature_for("inverse", 128, jnp.float32, mesh="data4:model2",
                           constraint="engines=pallas;leaf_solvers=linalg")
    sharded = signature_for("inverse", 128, jnp.float32, mesh="data4:model2",
                            placement="sharded",
                            constraint="engines=pallas;leaf_solvers=linalg")
    assert cache.get(meshed) is None
    assert cache.get(sharded) is None


def test_pallas_plan_executes_through_dispatch(tmp_path):
    """execute_inverse must run a pallas plan on its fused path and agree
    with the explicit entry point bitwise (same static arguments)."""
    from repro.planner import execute_inverse

    a = make_spd(N, jax.random.PRNGKey(9))
    plan = Plan(block_size=BS, multiply_engine="pallas")
    got = execute_inverse(plan, a)
    want = spin_inverse_dense(a, BS, engine="pallas")
    assert jnp.array_equal(got, want)
