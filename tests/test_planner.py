"""Planner subsystem tests: enumeration, cost-model shape, plan cache
persistence, auto=True equivalence, and the within-25%-of-exhaustive
acceptance bound (ISSUE 2)."""

import json

import jax
import jax.numpy as jnp
import pytest

from repro.core import spin_inverse_dense, spin_solve_dense
from repro.core.testing import make_spd
from repro.planner import (Plan, PlanCache, candidate_grids, enumerate_plans,
                           execute_inverse, get_plan, measure_plans,
                           plan_inverse, plan_solve, planned_block_size,
                           predict_cost, rank_plans, signature_for)


# ----------------------------------------------------------- enumeration

def test_candidate_grids_power_of_two_and_divisible():
    assert candidate_grids(256) == [1, 2, 4, 8, 16, 32]
    assert candidate_grids(50) == [1, 2]          # 4 does not divide 50
    assert candidate_grids(8) == [1]              # blocks must stay >= 8
    assert candidate_grids(1 << 14, max_grid=64)[-1] == 64


def test_enumerate_plans_single_device_has_no_summa_engines():
    sig = signature_for("inverse", 256, jnp.float32, device_count=1)
    engines = {p.multiply_engine for p in enumerate_plans(sig)}
    assert engines == {"einsum"}


def test_enumerate_plans_multi_device_and_refinement():
    sig = signature_for("inverse", 256, jnp.float32, backend="tpu", device_kind="TPU v5 lite",
                        device_count=4, cores=4)
    plans = enumerate_plans(sig)
    assert {p.multiply_engine for p in plans} == {"einsum", "allgather",
                                                 "ring", "pallas"}
    refined = [p for p in plans if p.refine_sweeps]
    assert refined and all(p.compute_dtype == "bfloat16" for p in refined)
    # refinement is an explicit opt-in elsewhere
    cpu_sig = signature_for("inverse", 256, jnp.float32, backend="cpu",
                            device_count=1, cores=8)
    assert not any(p.refine_sweeps for p in enumerate_plans(cpu_sig))


def test_enumerate_plans_fixed_block_size():
    sig = signature_for("inverse", 256, jnp.float32)
    plans = enumerate_plans(sig, block_sizes=(64,))
    assert plans and all(p.block_size == 64 for p in plans)


def test_tpu_plans_offer_no_leaf_kernel_that_cannot_compile():
    """On a TPU signature a kernel-backed leaf solver is offered only up to
    the block size its kernel compiles at (tests/test_tpu_compile.py);
    the XLA leaves are offered at every size, and off-TPU (interpret mode,
    no VMEM) nothing is gated."""
    from repro.kernels.leaf_inverse.kernel import max_block_size

    n = 1 << 14
    kernels = {"inverse": {"pallas": "pallas", "gauss_jordan": "gauss_jordan"},
               "solve": {"pallas": "triangular_solve",
                         "gauss_jordan": "gauss_jordan"}}
    for kind, leaf_kernel in kernels.items():
        sig = signature_for(kind, n, jnp.float32, backend="tpu",
                            device_kind="TPU v5 lite", device_count=1,
                            cores=1)
        plans = enumerate_plans(sig)
        for p in plans:
            if p.leaf_solver in leaf_kernel:
                assert p.block_size <= max_block_size(
                    leaf_kernel[p.leaf_solver]), (kind, p)
        offered = {(p.block_size, p.leaf_solver) for p in plans}
        assert (4096, "linalg") in offered
        assert (4096, "gauss_jordan") not in offered
        assert (1024, "gauss_jordan") in offered
    cpu = signature_for("inverse", n, jnp.float32, backend="cpu",
                        device_count=1, cores=8)
    assert (4096, "pallas") in {(p.block_size, p.leaf_solver)
                                for p in enumerate_plans(cpu)}


def test_tpu_pricing_needs_a_known_chip_kind():
    sig = signature_for("inverse", 4096, jnp.float32, backend="tpu",
                        device_kind="TPU v99", device_count=1, cores=1)
    with pytest.raises(ValueError, match="no published peaks"):
        predict_cost(sig, Plan(block_size=1024))


# ----------------------------------------------------------- cost model

def test_cost_model_u_curve_interior_beats_endpoints():
    """For large n both U-curve endpoints (b=1, b=n/8) must lose to some
    interior grid — the paper's central Fig. 3 shape, as scored by the
    planner."""
    n = 1 << 14
    sig = signature_for("inverse", n, jnp.float32, backend="cpu",
                        device_count=1, cores=8)
    cost = {b: predict_cost(sig, Plan(block_size=n // b))
            for b in [2 ** k for k in range(0, 12)]}   # b = 1 .. n/8
    interior = min(cost[b] for b in cost if 1 < b < n // 8)
    assert interior < cost[1], "b=1 endpoint should be beatable"
    assert interior < cost[n // 8], "b=n/8 endpoint should be beatable"


def test_rank_plans_penalizes_interpreted_gauss_jordan_on_cpu():
    sig = signature_for("inverse", 256, jnp.float32, backend="cpu",
                        device_count=1, cores=8)
    ranked = rank_plans(sig, enumerate_plans(sig))
    assert ranked[0].leaf_solver != "gauss_jordan"
    worst = [p.leaf_solver for p in ranked[-3:]]
    assert "gauss_jordan" in worst


def test_tpu_ranking_recurses_instead_of_single_leaf():
    """Regression: the roofline credits all flops with chips-parallelism,
    but leaf inversions serialize on one chip — without re-pricing them,
    b=1 (one whole-matrix serial inversion) ranks first at every n and
    auto=True never recurses on TPU."""
    for n in (1 << 13, 1 << 15):
        sig = signature_for("inverse", n, jnp.float32, backend="tpu", device_kind="TPU v5 lite",
                            device_count=256, cores=256)
        best = rank_plans(sig, enumerate_plans(sig, max_grid=256))[0]
        assert best.grid(n) > 1, f"n={n} planned a single serial leaf"


def test_solve_plans_never_enumerate_refinement():
    """Newton-Schulz polishes an inverse; execute_solve has no refinement
    stage, so enumerating refined solve plans would cache plans describing
    an execution that never happens."""
    sig = signature_for("solve", 4096, jnp.float32, backend="tpu", device_kind="TPU v5 lite",
                        device_count=256, cores=256)
    assert not any(p.refine_sweeps for p in
                   enumerate_plans(sig, include_refinement=True))


def test_predict_cost_tpu_ring_overlap_wins_at_scale():
    sig = signature_for("inverse", 1 << 15, jnp.float32, backend="tpu", device_kind="TPU v5 lite",
                        device_count=256, cores=256)
    ring = predict_cost(sig, Plan(block_size=(1 << 15) // 16,
                                  multiply_engine="ring"))
    gather = predict_cost(sig, Plan(block_size=(1 << 15) // 16,
                                    multiply_engine="allgather"))
    assert ring <= gather


# ----------------------------------------------------------- plan cache

def test_plan_cache_round_trip(tmp_path):
    cache = PlanCache(str(tmp_path / "plans.json"))
    sig = signature_for("inverse", 128, jnp.float32)
    plan = Plan(block_size=32, leaf_solver="linalg", predicted_s=1e-3,
                measured_s=2e-3, source="measured")
    cache.put(sig, plan)

    reloaded = PlanCache(str(tmp_path / "plans.json"))   # "new process"
    got = reloaded.get(sig)
    assert got == plan                                   # field-for-field
    assert got.execution_key() == plan.execution_key()


def test_plan_cache_survives_process_restart(tmp_path):
    """End-to-end: plan with measurement, then re-plan from a fresh cache
    object on the same file — the second call must hit, not re-measure."""
    path = str(tmp_path / "plans.json")
    plan1 = get_plan("inverse", 64, jnp.float32, measure=True,
                     top_k=None, cache=PlanCache(path),
                     leaf_solvers=("linalg",))
    assert plan1.source == "measured"

    calls = []
    import repro.planner.autotune as at
    orig = at.measure_plans
    at.measure_plans = lambda *a, **k: calls.append(1) or orig(*a, **k)
    try:
        plan2 = get_plan("inverse", 64, jnp.float32, measure=True,
                         top_k=None, cache=PlanCache(path),
                         leaf_solvers=("linalg",))
    finally:
        at.measure_plans = orig
    assert not calls, "cache hit must not re-measure"
    assert plan2.execution_key() == plan1.execution_key()


def test_plan_cache_version_mismatch_invalidates(tmp_path):
    path = tmp_path / "plans.json"
    sig = signature_for("inverse", 128, jnp.float32)
    cache = PlanCache(str(path))
    cache.put(sig, Plan(block_size=32))
    raw = json.loads(path.read_text())
    raw["version"] = -1
    path.write_text(json.dumps(raw))
    assert PlanCache(str(path)).get(sig) is None


def test_plan_cache_schema_v1_files_are_discarded(tmp_path):
    """ISSUE 3 fix: v1 cache files predate the mesh/placement signature
    dimensions — a v1 plan tuned on 1 device could silently serve an
    8-device mesh, so the whole file must be invalidated, not reused."""
    from repro.planner import PLAN_CACHE_VERSION

    assert PLAN_CACHE_VERSION >= 2
    path = tmp_path / "plans.json"
    sig = signature_for("inverse", 128, jnp.float32)
    # a v1-era file: same layout, old version, key without mesh/placement
    old_key = (f"{sig.kind}/n{sig.n}/{sig.dtype}/{sig.backend}"
               f"/d{sig.device_count}/c{sig.cores}")
    path.write_text(json.dumps({
        "version": 1,
        "plans": {old_key: {"sig": {}, "plan": Plan(block_size=8).to_dict()}},
        "calibration": {},
    }))
    assert PlanCache(str(path)).get(sig) is None


def test_signature_keys_on_mesh_and_placement(tmp_path):
    """Signatures differing only in mesh topology or engine placement must
    never share cache entries."""
    base = signature_for("inverse", 256, jnp.float32)
    meshed = signature_for("inverse", 256, jnp.float32, mesh="data4:model2")
    sharded = signature_for("inverse", 256, jnp.float32, mesh="data4:model2",
                            placement="sharded")
    assert base.mesh == ""                 # no ambient mesh in this process
    assert base.placement == "dense"
    assert len({base.key(), meshed.key(), sharded.key()}) == 3
    cache = PlanCache(str(tmp_path / "plans.json"))
    cache.put(base, Plan(block_size=32))
    assert cache.get(meshed) is None
    assert cache.get(sharded) is None
    assert cache.get(base).block_size == 32
    with pytest.raises(ValueError):
        signature_for("inverse", 256, jnp.float32, placement="replicated")


def test_signature_mesh_defaults_to_ambient_mesh():
    from repro.compat import AxisType, make_mesh, set_mesh
    from repro.planner import mesh_descriptor

    assert mesh_descriptor() == ""
    mesh = make_mesh((1, 1), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
    with set_mesh(mesh):
        assert mesh_descriptor() == "data1:model1"
        sig = signature_for("inverse", 128, jnp.float32)
        assert sig.mesh == "data1:model1"
    assert signature_for("inverse", 128, jnp.float32).mesh == ""


def test_planned_block_size_memo_keys_on_mesh(tmp_path, monkeypatch):
    """The trace-safe memo must observe a changed ambient mesh rather than
    serving a block size memoized under the previous topology."""
    from repro.compat import AxisType, make_mesh, set_mesh
    from repro.planner import dispatch

    monkeypatch.setenv("SPIN_PLAN_CACHE", str(tmp_path / "plans.json"))
    dispatch._planned_fields.cache_clear()
    bs_out = planned_block_size(256)
    misses_before = dispatch._planned_fields.cache_info().misses
    mesh = make_mesh((1, 1), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
    with set_mesh(mesh):
        bs_in = planned_block_size(256)
    assert dispatch._planned_fields.cache_info().misses == misses_before + 1
    assert 256 % bs_out == 0 and 256 % bs_in == 0
    # and repeating either context is a memo hit, not a re-plan
    hits_before = dispatch._planned_fields.cache_info().hits
    planned_block_size(256)
    assert dispatch._planned_fields.cache_info().hits == hits_before + 1


def test_plan_cache_signature_mismatch_misses(tmp_path):
    cache = PlanCache(str(tmp_path / "plans.json"))
    sig = signature_for("inverse", 128, jnp.float32)
    cache.put(sig, Plan(block_size=32))
    other = signature_for("inverse", 128, jnp.bfloat16)
    assert cache.get(other) is None


def test_plan_cache_corrupt_file_degrades_to_empty(tmp_path):
    path = tmp_path / "plans.json"
    path.write_text("{not json")
    cache = PlanCache(str(path))
    sig = signature_for("inverse", 128, jnp.float32)
    assert cache.get(sig) is None
    cache.put(sig, Plan(block_size=64))       # and it can still write
    assert PlanCache(str(path)).get(sig).block_size == 64


def test_plan_cache_concurrent_writers_merge(tmp_path):
    """A put() must not clobber entries another process wrote after our
    load: writes merge per key instead of dumping the stale snapshot."""
    path = str(tmp_path / "plans.json")
    sig_a = signature_for("inverse", 64, jnp.float32)
    sig_b = signature_for("inverse", 1024, jnp.float32)
    a, b = PlanCache(path), PlanCache(path)
    a.get(sig_a)                       # force both snapshots to load now
    b.get(sig_b)
    b.put(sig_b, Plan(block_size=128))
    a.put(sig_a, Plan(block_size=16))  # a's snapshot predates b's write
    fresh = PlanCache(path)
    assert fresh.get(sig_a).block_size == 16
    assert fresh.get(sig_b).block_size == 128


def test_costmodel_plan_upgraded_by_measurement(tmp_path):
    path = str(tmp_path / "plans.json")
    p1 = get_plan("inverse", 64, jnp.float32, measure=False,
                  cache=PlanCache(path))
    assert p1.source == "costmodel"
    p2 = get_plan("inverse", 64, jnp.float32, measure=True, top_k=2,
                  cache=PlanCache(path))
    assert p2.source == "measured" and p2.measured_s is not None


# ----------------------------------------------------------- auto path

def test_auto_inverse_bitwise_matches_explicit_plan(tmp_path):
    a = make_spd(128, jax.random.PRNGKey(0))
    cache = PlanCache(str(tmp_path / "plans.json"))
    x_auto, plan = plan_inverse(a, cache=cache, return_plan=True)
    x_explicit = spin_inverse_dense(a, plan.block_size, plan.leaf_solver)
    assert jnp.array_equal(x_auto, x_explicit)
    # and the spin_inverse_dense(auto=True) spelling agrees with the same
    # plan re-executed from the cache
    x_again = execute_inverse(plan, a)
    assert jnp.array_equal(x_auto, x_again)


def test_auto_solve_bitwise_matches_explicit_plan(tmp_path):
    a = make_spd(128, jax.random.PRNGKey(1))
    b = jax.random.normal(jax.random.PRNGKey(2), (128, 4))
    cache = PlanCache(str(tmp_path / "plans.json"))
    x_auto, plan = plan_solve(a, b, cache=cache, return_plan=True)
    x_explicit = spin_solve_dense(a, b, plan.block_size, plan.leaf_solver)
    assert jnp.array_equal(x_auto, x_explicit)


def test_planned_block_size_is_trace_safe():
    """The shampoo hook must be consultable while JAX is tracing."""
    @jax.jit
    def f(x):
        bs = planned_block_size(x.shape[0], x.dtype)
        return spin_inverse_dense(x, bs)

    a = make_spd(64, jax.random.PRNGKey(3))
    inv = f(a)
    resid = jnp.linalg.norm(inv @ a - jnp.eye(64)) / 8.0
    assert float(resid) < 1e-3


def test_planned_block_size_divides_n_and_grid_is_pow2():
    for n in (50, 64, 96, 256, 6144):
        bs = planned_block_size(n)
        assert n % bs == 0
        g = n // bs
        assert g & (g - 1) == 0


def test_multiply_engine_is_a_static_jit_argument():
    """Two plans differing only in multiply engine must not share a compiled
    executable: the engine is resolved at trace time, so a changed engine
    has to retrace. Op counts only bump during tracing, which makes the
    retrace observable."""
    from repro.core import count_ops

    a = make_spd(64, jax.random.PRNGKey(7))
    spin_inverse_dense(a, 16, engine="einsum")          # compile once
    with count_ops() as cached:
        spin_inverse_dense(a, 16, engine="einsum")      # cache hit: no trace
    assert cached.multiplies == 0
    with count_ops() as retraced:
        x_ring = spin_inverse_dense(a, 16, engine="ring")
    assert retraced.multiplies > 0, "changed engine must retrace"
    # single-device: SUMMA engines fall back to einsum, results agree
    assert jnp.allclose(x_ring, spin_inverse_dense(a, 16, engine="einsum"))


# ------------------------------------------- newton-schulz refinement stage

def test_refined_plan_executes_and_polishes():
    """A plan selecting the bf16 + Newton–Schulz refinement stage must beat
    the unrefined bf16 recursion's accuracy at f32 output."""
    a = make_spd(64, jax.random.PRNGKey(4))
    raw = spin_inverse_dense(a.astype(jnp.bfloat16), 16).astype(jnp.float32)
    plan = Plan(block_size=16, compute_dtype="bfloat16", refine_sweeps=2)
    polished = execute_inverse(plan, a)
    eye = jnp.eye(64)
    r_raw = float(jnp.linalg.norm(raw @ a - eye))
    r_pol = float(jnp.linalg.norm(polished @ a - eye))
    assert polished.dtype == a.dtype
    assert r_pol < r_raw * 0.1


# ------------------------------------------- acceptance: within 25% of best

@pytest.mark.parametrize("n", [64, 128, 256])
def test_planner_within_25pct_of_exhaustive_sweep(tmp_path, n):
    """ISSUE 2 acceptance: on CPU test sizes the planner's grid must come
    within 25% of the best grid found by exhaustive sweep.

    The sweep and the planner's pick are measured in ONE round-robin table
    (min-of-k, interleaved), so both sides see the same system noise. On a
    loaded host a single measurement pass can still invert sub-millisecond
    orderings, so the planner gets a bounded number of fresh re-plans
    (force_replan) before the assertion is final.
    """
    sig = signature_for("inverse", n, jnp.float32)
    grids = candidate_grids(n)
    attempts = []
    for attempt in range(3):
        cache = PlanCache(str(tmp_path / f"plans{n}_{attempt}.json"))
        plan = get_plan("inverse", n, jnp.float32, measure=True, top_k=None,
                        cache=cache, leaf_solvers=("linalg",))
        sweep = dict(zip(grids, measure_plans(
            sig, [Plan(block_size=n // b) for b in grids], iters=5)))
        t_best, t_plan = min(sweep.values()), sweep[plan.grid(n)]
        attempts.append((plan.grid(n), t_plan, t_best, sweep))
        if t_plan <= 1.25 * t_best:
            return
    raise AssertionError(
        f"planner never landed within 25% of the sweep best: {attempts}")
