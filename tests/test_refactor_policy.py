"""Refactor-policy + update_rank signature-axis tests (planner side of the
online inverse service)."""

import jax.numpy as jnp
import pytest

from repro.planner import (PlanCache, RefactorPolicy, get_plan,
                           signature_for, smw_update_cost)


def test_signature_update_rank_axis():
    base = signature_for("inverse", 256, jnp.float32, cores=4)
    churned = signature_for("inverse", 256, jnp.float32, cores=4,
                            update_rank=16)
    assert base.update_rank == 0
    # rank 0 leaves every pre-existing key byte-identical
    assert "/u" not in base.key()
    assert churned.key() == base.key() + "/u16"
    with pytest.raises(ValueError):
        signature_for("inverse", 256, jnp.float32, update_rank=-1)


def test_update_rank_plans_roundtrip_schema_v2_cache(tmp_path):
    """A churned-signature plan caches under its own key and round-trips."""
    cache = PlanCache(str(tmp_path / "plans.json"))
    plan = get_plan("inverse", 256, jnp.float32, measure=False, cache=cache,
                    update_rank=16)
    sig = signature_for("inverse", 256, jnp.float32, update_rank=16)
    recalled = cache.get(sig)
    assert recalled is not None
    assert recalled.execution_key() == plan.execution_key()
    # the offline (rank-0) key is a MISS — the axis isolates the entries
    assert cache.get(signature_for("inverse", 256, jnp.float32)) is None
    # and a reloaded cache file (fresh process) still round-trips
    assert PlanCache(str(tmp_path / "plans.json")).get(sig) is not None


def test_smw_update_cost_scales_linearly_in_rank():
    sig = signature_for("inverse", 512, jnp.float32, cores=4)
    c1, c8 = smw_update_cost(sig, 1), smw_update_cost(sig, 8)
    assert c1 > 0
    assert c8 == pytest.approx(8 * c1, rel=0.05)   # k³ term is negligible
    # TPU pricing exists and is roofline-positive too
    tpu = signature_for("inverse", 512, jnp.float32, backend="tpu", device_kind="TPU v5 lite",
                        device_count=4, cores=4)
    assert smw_update_cost(tpu, 8) > 0


def test_decide_crossover_is_rent_or_buy(tmp_path):
    """No churn spend → SMW; spend at the modeled re-inversion price →
    refactor. The boundary is the policy's slack × predicted cost."""
    cache = PlanCache(str(tmp_path / "plans.json"))
    pol = RefactorPolicy(cache=cache)
    fresh = pol.decide(256, jnp.float32, new_rank=4)
    assert not fresh.refactor and fresh.reason == "smw"
    assert fresh.cumulative_s == pytest.approx(fresh.smw_cost_s)
    spent = pol.decide(256, jnp.float32, new_rank=4,
                       pending_rank=16,
                       cumulative_s=fresh.refactor_cost_s)
    assert spent.refactor and spent.reason == "crossover"
    # slack defers the crossover
    lax_pol = RefactorPolicy(slack=1e6, cache=cache)
    assert not lax_pol.decide(256, jnp.float32, new_rank=4, pending_rank=16,
                              cumulative_s=fresh.refactor_cost_s).refactor


def test_decide_drift_and_rank_bounds_override_cost(tmp_path):
    pol = RefactorPolicy(cache=PlanCache(str(tmp_path / "plans.json")))
    drift = pol.decide(256, jnp.float32, new_rank=4,
                       residual_est=1.0, drift_tolerance=1e-2)
    assert drift.refactor and drift.reason == "drift"
    rank = pol.decide(256, jnp.float32, new_rank=4, pending_rank=124)
    assert rank.refactor and rank.reason == "rank"


def test_crossover_rank_monotone_in_n(tmp_path):
    """Bigger problems amortize more SMW spend before re-inverting: the
    crossover rank must not shrink with n (O(n³) rebuild vs O(n²k) rent)."""
    pol = RefactorPolicy(cache=PlanCache(str(tmp_path / "plans.json")))
    r256 = pol.crossover_rank(256, jnp.float32, step_rank=8)
    r1024 = pol.crossover_rank(1024, jnp.float32, step_rank=8)
    assert 8 <= r256 <= 256
    assert r1024 >= r256


def test_policy_validates_slack():
    with pytest.raises(ValueError):
        RefactorPolicy(slack=0.0)


def test_decide_buckets_rank_axis_to_powers_of_two(tmp_path):
    """A rank-1 update stream must not mint one plan-cache entry per
    accumulated-rank value: decide() quantizes the lookup to the next
    power of two, bounding distinct keys at log2(n)."""
    import json

    path = tmp_path / "plans.json"
    pol = RefactorPolicy(cache=PlanCache(str(path)))
    cumulative, rank = 0.0, 0
    for _ in range(9):
        d = pol.decide(256, jnp.float32, new_rank=1, pending_rank=rank,
                       cumulative_s=cumulative)
        rank += 1
        cumulative = d.cumulative_s
    with open(path) as f:
        keys = [k for k in json.load(f)["plans"] if "/u" in k]
    # ranks 1..9 -> buckets {1, 2, 4, 8, 16} only
    assert len(keys) <= 5, keys
    assert all(int(k.split("/u")[1].split("/")[0]) in (1, 2, 4, 8, 16)
               for k in keys), keys
