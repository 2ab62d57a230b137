"""repro.planner — cost-model-driven autotuning for SPIN (DESIGN.md §5).

Turns the paper's §4 cost model from an offline plotting aid into the
system's execution policy: enumerate candidate (block grid, leaf solver,
multiply engine, dtype, refinement) plans, score them with the per-level
Lemma 4.1 sums (CPU/GPU) or the TPU roofline, optionally refine the top-k
with live microbenchmarks, and persist the winner in a JSON plan cache
shared across processes. `spin_inverse(..., auto=True)` and friends route
through here.
"""

from .plan import (STRASSEN_MIN_N, Plan, ProblemSignature, candidate_grids,
                   enumerate_plans, mesh_descriptor, signature_for)
# NB: the `autotune` *function* is deliberately not re-exported — it would
# shadow the `repro.planner.autotune` submodule attribute. Use
# `repro.planner.autotune.autotune` (or just `get_plan`).
from .autotune import (ENGINE_RATE, LEAF_SOLVER_RATE, measure_plan,
                       measure_plans, predict_cost, rank_plans)
from .cache import PLAN_CACHE_VERSION, PlanCache, default_cache, \
    default_cache_path
from .dispatch import (MEASURE_MAX_N, execute_inverse, execute_solve,
                       get_plan, plan_inverse, plan_solve,
                       planned_block_size, planned_fields,
                       planned_leaf_solver)
from .refactor_policy import (RefactorDecision, RefactorPolicy,
                              smw_update_cost)

__all__ = [
    "Plan", "ProblemSignature", "signature_for", "enumerate_plans",
    "candidate_grids", "mesh_descriptor", "STRASSEN_MIN_N",
    "predict_cost", "rank_plans", "measure_plan", "measure_plans",
    "LEAF_SOLVER_RATE", "ENGINE_RATE",
    "PlanCache", "default_cache", "default_cache_path", "PLAN_CACHE_VERSION",
    "get_plan", "plan_inverse", "plan_solve", "planned_block_size",
    "planned_fields", "planned_leaf_solver", "execute_inverse", "execute_solve",
    "MEASURE_MAX_N",
    "RefactorDecision", "RefactorPolicy", "smw_update_cost",
]
