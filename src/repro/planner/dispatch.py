"""Planned execution: `plan_inverse` / `plan_solve` and the `auto=True` path.

`get_plan` is the policy pipeline: cache lookup → candidate enumeration
(`plan.enumerate_plans`) → cost-model ranking, optionally refined by live
microbenchmarks (`autotune.autotune`) → cache write-back. `execute_inverse`
/ `execute_solve` are the mechanism: run one concrete plan, including the
Newton–Schulz low-precision refinement stage when the plan selects it.

Trace-time safety: `planned_block_size` (the hook `optim/spin_shampoo.py`
uses inside `jax.lax.cond` branches) never measures and memoizes per
process, so consulting the planner while JAX is tracing costs a dict lookup
and issues no computation.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from repro.core.blockmatrix import BlockMatrix
from repro.core.multiply import multiply_engine
from repro.core.newton_schulz import newton_schulz_polish
from repro.obs.trace import TRACER as _TRACER

from .autotune import autotune as _autotune_plans
from .cache import PlanCache, default_cache
from .plan import Plan, enumerate_plans, signature_for

__all__ = ["get_plan", "plan_inverse", "plan_solve", "planned_block_size",
           "planned_leaf_solver", "execute_inverse", "execute_solve",
           "MEASURE_MAX_N"]

# `measure="auto"` microbenchmarks only problems at or below this size; above
# it the cost model (calibrated, when a previous tune ran) decides alone, so
# a first planned 2^17 inversion never pays a sweep of giant warmup runs.
MEASURE_MAX_N = 512


def _resolve_measure(measure, n: int) -> bool:
    if measure == "auto":
        return n <= MEASURE_MAX_N
    return bool(measure)


def get_plan(kind: str, n: int, dtype=jnp.float32, *,
             measure: bool | str = "auto",
             top_k: int | None = 4,
             cache: PlanCache | None = None,
             force_replan: bool = False,
             placement: str = "dense",
             update_rank: int = 0,
             precision=None,
             **enumerate_kw) -> Plan:
    """Select (or recall) the plan for one (kind, n, dtype) problem.

    measure: True / False / "auto" (measure iff n <= MEASURE_MAX_N).
    A cached cost-model-only plan is upgraded the first time the same
    problem is planned with measurement enabled. The signature additionally
    keys on the ambient mesh topology and `placement` ("dense" | "sharded"
    executors), so a plan tuned without a mesh is never recalled inside one.
    `update_rank` is the online-service axis (accumulated SMW churn a
    re-factorization plan is priced under, see planner.refactor_policy) —
    zero for ordinary offline problems, leaving their cache keys unchanged.
    `precision` (PrecisionPolicy | preset string | None) puts the policy on
    the signature: candidates gain store-dtype variants and are priced for
    serving (autotune.SERVE_HORIZON_COLS); an exact policy leaves the
    signature — and thus every pre-existing cache key — unchanged.
    """
    if kind not in ("inverse", "solve"):
        raise ValueError(f"unknown plan kind {kind!r}")
    from repro.core.precision import resolve_precision

    policy = resolve_precision(precision)
    sig = signature_for(kind, n, dtype, placement=placement,
                        update_rank=update_rank,
                        precision="" if policy.is_exact
                        else policy.descriptor(),
                        constraint=_constraint_key(enumerate_kw))
    cache = cache or default_cache()
    do_measure = _resolve_measure(measure, n)

    cached = cache.get(sig)
    if cached is not None and not force_replan:
        if not (do_measure and cached.source == "costmodel"):
            if _TRACER.enabled:
                _TRACER.event("planner.plan", "planner_decision",
                              sig=sig.key(), decision="cache_hit",
                              plan=cached.to_dict())
            return cached

    candidates = enumerate_plans(sig, **enumerate_kw)
    if not candidates:
        raise ValueError(f"no feasible plans for {sig.key()} "
                         f"(constraints: {enumerate_kw})")
    plan, calib = _autotune_plans(
        sig, candidates, measure=do_measure, top_k=top_k,
        calibration=cache.get_calibration(sig))
    cache.put(sig, plan)
    if calib:
        cache.put_calibration(sig, calib)
    if _TRACER.enabled:
        _TRACER.event("planner.plan", "planner_decision", sig=sig.key(),
                      decision="autotuned", measured=do_measure,
                      candidates=len(candidates), plan=plan.to_dict(),
                      calibrated=calib is not None)
    return plan


def _constraint_key(enumerate_kw: dict) -> str:
    """Cache-key suffix for constrained enumerations.

    EVERY non-default enumeration knob must appear here: a plan chosen from
    a restricted candidate space cached under the unconstrained key would
    poison every later unconstrained `auto=True` lookup.
    """
    if not enumerate_kw:
        return ""
    parts = []
    for k in sorted(enumerate_kw):
        v = enumerate_kw[k]
        if isinstance(v, (tuple, list)):
            v = "+".join(str(x) for x in v)
        parts.append(f"{k}={v}")
    return ";".join(parts)


# ---------------------------------------------------------------------------
# Executing a plan
# ---------------------------------------------------------------------------


def _refined_inverse(plan: Plan, dense: jax.Array) -> jax.Array:
    """Low-precision recursion + Newton–Schulz polish back to full precision."""
    from repro.core.spin import spin_inverse_dense

    approx = spin_inverse_dense(
        dense.astype(plan.compute_dtype), plan.block_size, plan.leaf_solver,
        engine=plan.multiply_engine).astype(dense.dtype)
    a = BlockMatrix.from_dense(dense, plan.block_size)
    x0 = BlockMatrix.from_dense(approx, plan.block_size)
    with multiply_engine(plan.multiply_engine):   # eager polish multiplies
        return newton_schulz_polish(a, x0,
                                    sweeps=plan.refine_sweeps).to_dense()


def execute_inverse(plan: Plan, dense: jax.Array,
                    placement: str = "dense") -> jax.Array:
    """Run one concrete inversion plan on a dense (n, n) matrix.

    The engine travels as a STATIC jit argument (not just the contextvar):
    the engine is resolved at trace time, so it must be part of the jit
    cache key for two plans differing only in engine to run different code.
    placement="sharded" runs the mesh-resident program instead of the dense
    one — the executor the autotuner must time for sharded-placement plans
    (no refinement stage exists there; enumeration never produces one).
    """
    if placement == "sharded":
        from repro.core.spin import spin_inverse_sharded

        return spin_inverse_sharded(dense, plan.block_size,
                                    leaf_solver=plan.leaf_solver,
                                    engine=plan.multiply_engine)
    from repro.core.spin import spin_inverse_dense

    if plan.compute_dtype != dense.dtype.name and plan.refine_sweeps:
        out = _refined_inverse(plan, dense)
    else:
        out = spin_inverse_dense(dense, plan.block_size, plan.leaf_solver,
                                 engine=plan.multiply_engine)
    # Precision-axis plans may store the result below the operand dtype
    # (the maintained-inverse serving representation). "" = operand's own.
    if plan.store_dtype and plan.store_dtype != out.dtype.name:
        out = out.astype(plan.store_dtype)
    return out


def execute_solve(plan: Plan, dense: jax.Array, rhs: jax.Array,
                  placement: str = "dense") -> jax.Array:
    """Run one concrete solve plan on dense A (n, n) and RHS B (n, k)|(n,)."""
    if placement == "sharded":
        from repro.core.solve import spin_solve_sharded

        return spin_solve_sharded(dense, rhs, plan.block_size,
                                  leaf_solver=plan.leaf_solver,
                                  engine=plan.multiply_engine)
    from repro.core.solve import spin_solve_dense

    return spin_solve_dense(dense, rhs, plan.block_size, plan.leaf_solver,
                            engine=plan.multiply_engine)


# ---------------------------------------------------------------------------
# Public planned entry points
# ---------------------------------------------------------------------------


def _ledger_record(kind: str, plan: Plan, dense: jax.Array,
                   measured_s: float) -> None:
    """Record one traced planned execution into the cost ledger.

    Only called under $SPIN_TRACE (the caller paid a block_until_ready to
    get a real wall time). The prediction is the plan's own `predicted_s`
    provenance when the autotuner annotated it, else `predict_cost` under
    the current signature — both are the Lemma-4.1 / roofline model.
    """
    from repro.obs import ledger as obs_ledger

    from .autotune import predict_cost
    from .plan import signature_for

    n = int(dense.shape[0])
    sig = signature_for(kind, n, dense.dtype)
    pred = plan.predicted_s
    if pred is None:
        try:
            pred = predict_cost(sig, plan)
        except Exception:
            pred = None
    entry = obs_ledger.ledger().record_solve(
        kind=kind, n=n, plan=plan, backend=sig.backend,
        dtype=jnp.dtype(dense.dtype).name, measured_s=measured_s,
        predicted_s=pred)
    attrs = entry.to_dict()
    attrs["solve_kind"] = attrs.pop("kind")   # "kind" names the span kind
    _TRACER.event("ledger.solve", "cost_ledger", **attrs)


def plan_inverse(dense: jax.Array, *, plan: Plan | None = None,
                 measure: bool | str = "auto",
                 cache: PlanCache | None = None,
                 return_plan: bool = False, **plan_kw):
    """Invert a dense SPD matrix with an autotuned plan.

    Equivalent to `spin_inverse_dense(dense, p.block_size, p.leaf_solver)`
    under `p`'s multiply engine — bitwise, when `p` has no refinement stage.
    Under $SPIN_TRACE the execution is synchronized and its modeled vs
    measured seconds are recorded in the cost ledger (repro.obs.ledger);
    untraced calls keep XLA's async dispatch untouched.
    """
    if plan is None:
        plan = get_plan("inverse", dense.shape[0], dense.dtype,
                        measure=measure, cache=cache, **plan_kw)
    if _TRACER.enabled:
        with _TRACER.span("plan.inverse", "solve", n=int(dense.shape[0]),
                          block_size=plan.block_size,
                          engine=plan.multiply_engine):
            t0 = time.perf_counter()
            out = jax.block_until_ready(execute_inverse(plan, dense))
            _ledger_record("inverse", plan, dense, time.perf_counter() - t0)
    else:
        out = execute_inverse(plan, dense)
    return (out, plan) if return_plan else out


def plan_solve(dense: jax.Array, rhs: jax.Array, *, plan: Plan | None = None,
               measure: bool | str = "auto",
               cache: PlanCache | None = None,
               return_plan: bool = False, **plan_kw):
    """Solve A X = B with an autotuned plan (inverse-free SPIN recursion).

    Traced calls record modeled-vs-measured seconds like `plan_inverse`.
    """
    if plan is None:
        plan = get_plan("solve", dense.shape[0], dense.dtype,
                        measure=measure, cache=cache, **plan_kw)
    if _TRACER.enabled:
        with _TRACER.span("plan.solve", "solve", n=int(dense.shape[0]),
                          block_size=plan.block_size,
                          engine=plan.multiply_engine):
            t0 = time.perf_counter()
            out = jax.block_until_ready(execute_solve(plan, dense, rhs))
            _ledger_record("solve", plan, dense, time.perf_counter() - t0)
    else:
        out = execute_solve(plan, dense, rhs)
    return (out, plan) if return_plan else out


@functools.lru_cache(maxsize=256)
def _planned_fields(kind: str, n: int, dtype_name: str,
                    block_sizes: tuple[int, ...] | None,
                    cache_path: str, mesh: str) -> tuple[int, str, str]:
    # cache_path is part of the memo key so a changed $SPIN_PLAN_CACHE (e.g.
    # a test pointing at a tmpdir) is observed instead of serving answers
    # memoized against the previous cache file. `mesh` is in the key for the
    # same reason: the ambient mesh context can change between calls, and a
    # block size memoized under a 1-device run must not serve an 8-device
    # mesh (get_plan re-derives the same descriptor via signature_for).
    kw = {"block_sizes": block_sizes} if block_sizes else {}
    plan = get_plan(kind, n, jnp.dtype(dtype_name), measure=False, **kw)
    return plan.block_size, plan.leaf_solver, plan.multiply_engine


def planned_fields(n: int, dtype=jnp.float32, kind: str = "inverse",
                   block_size: int | None = None) -> tuple[int, str, str]:
    """(block_size, leaf_solver, multiply_engine) of one cost-model plan
    for (kind, n, dtype), over the given block size only when one is
    given — trace-time safe."""
    from .cache import default_cache_path
    from .plan import mesh_descriptor

    return _planned_fields(kind, int(n), jnp.dtype(dtype).name,
                           (int(block_size),) if block_size else None,
                           default_cache_path(), mesh_descriptor())


def planned_block_size(n: int, dtype=jnp.float32, kind: str = "inverse"
                       ) -> int:
    """Cost-model-only block size for (kind, n, dtype) — trace-time safe."""
    return planned_fields(n, dtype, kind)[0]


def planned_leaf_solver(n: int, block_size: int, dtype=jnp.float32,
                        kind: str = "inverse") -> str:
    """Leaf solver for a problem whose block grid is already fixed."""
    return planned_fields(n, dtype, kind, block_size)[1]
