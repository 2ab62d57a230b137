"""Plan scoring and (optional) live refinement for the SPIN autotuner.

Scoring reuses the paper's §4 cost machinery directly:

  * CPU/GPU — `costmodel.spin_cost` (Lemma 4.1 evaluated per level) with
    calibration constants taken from the plan cache when a previous session
    has fit them via `costmodel.fit_scale`, else the defaults.
  * TPU — `costmodel.tpu_roofline_cost` (compute / HBM / ICI terms), with
    the `ring` engine credited for compute↔collective overlap (max of the
    terms) and the gather engines charged their sum.

Leaf-solver choice is modeled as a per-backend multiplier on the leafNode
term (e.g. the Pallas Gauss–Jordan kernel runs in interpret mode on CPU and
is orders of magnitude slower there; QR pays ~3x the flops of getrf/getri).
A Newton–Schulz refinement stage is charged its two full-size distributed
multiplies per sweep.

`autotune` optionally *measures* the top-k model-ranked candidates with a
short microbenchmark and picks the fastest — the paper's Fig. 4
theory-vs-practice loop, closed. Measurements along the default
(linalg/einsum/native-dtype) axis additionally feed `fit_scale`, and the
calibrated per-class constants are persisted so the *next* problem size is
predicted well without measuring.
"""

from __future__ import annotations

import dataclasses
import time

import jax

from repro.core.costmodel import (DTYPE_BYTES, STRASSEN_CUTOFF, CostParams,
                                  apply_inverse_cost, fit_scale, spin_cost,
                                  strassen_cost, strassen_multiply_counts,
                                  tpu_peaks, tpu_roofline_cost)
from repro.obs.trace import TRACER as _TRACER

from .plan import Plan, ProblemSignature

__all__ = ["predict_cost", "rank_plans", "measure_plan", "measure_plans",
           "autotune", "LEAF_SOLVER_RATE", "ENGINE_RATE",
           "SERVE_HORIZON_COLS"]

# RHS columns a maintained inverse is assumed to serve over its lifetime —
# the amortization horizon the precision axis prices storage against. A
# low-precision store pays its certification polish once but saves HBM
# bytes on EVERY served `apply_inverse` GEMM; with no horizon the one-off
# polish would always dominate and the planner could never prefer bf16.
SERVE_HORIZON_COLS = 1024


# Relative leaf-inversion rates vs LAPACK getrf/getri, per backend. The
# interpret-mode penalty for the Pallas kernels off-TPU is deliberately huge:
# they must never be chosen by the model where they run emulated. The
# blocked `pallas` leaf beats the scalar `gauss_jordan` sweep on TPU (rank-t
# MXU updates vs bs vector steps) and is slightly cheaper off-TPU too (fewer
# interpreted steps), but both stay firmly priced out off-TPU.
LEAF_SOLVER_RATE: dict[str, dict[str, float]] = {
    "linalg": {},                               # 1.0 everywhere
    "qr": {"default": 3.0},                     # ~3x getri flops
    "gauss_jordan": {"tpu": 1.2, "default": 200.0},
    "pallas": {"tpu": 1.1, "default": 150.0},
}

# Relative distributed-multiply rates per backend, same convention: the
# fused Pallas engine's GEMMs match the MXU path XLA emits on TPU (its win
# is modeled separately as fused-update HBM traffic, see predict_cost), and
# are interpret-emulated — never choosable — everywhere else. The strassen
# engine's win is likewise modeled structurally (its multiply term runs the
# 7-multiply recurrence — `costmodel.strassen_cost` on CPU, a MAC credit +
# add-traffic charge on the TPU roofline), so its rate is 1.0 everywhere:
# its classical leaves run the same einsum/SUMMA/Pallas paths the other
# engines use.
ENGINE_RATE: dict[str, dict[str, float]] = {
    "einsum": {},
    "allgather": {},
    "ring": {},
    "pallas": {"tpu": 1.0, "default": 200.0},
    "strassen": {},
}

def _leaf_rate(solver: str, backend: str) -> float:
    rates = LEAF_SOLVER_RATE.get(solver, {})
    return rates.get(backend, rates.get("default", 1.0))


def _engine_rate(engine: str, backend: str) -> float:
    rates = ENGINE_RATE.get(engine, {})
    return rates.get(backend, rates.get("default", 1.0))


def _cost_params(sig: ProblemSignature, b: int, calibration: dict | None
                 ) -> CostParams:
    kw = dict(calibration or {})
    kw = {k: kw[k] for k in ("t_flop", "t_leaf", "t_block_op", "t_elem")
          if k in kw}
    return CostParams(n=sig.n, b=b, cores=sig.cores, **kw)


def predict_cost(sig: ProblemSignature, plan: Plan,
                 calibration: dict | None = None) -> float:
    """Model seconds for `plan` on `sig`'s problem. Lower is better."""
    b = plan.grid(sig.n)
    bytes_ = DTYPE_BYTES.get(plan.compute_dtype, 4)

    if sig.backend == "tpu":
        chips = max(sig.device_count, 1)
        hw = tpu_peaks(sig.device_kind)
        peak = hw["peak_flops"]
        r = tpu_roofline_cost(sig.n, b, chips, dtype_bytes=bytes_, hw=hw)
        if plan.multiply_engine == "ring":       # overlapped collective
            total = max(r["t_compute"], r["t_memory"], r["t_collective"])
        else:
            total = r["t_compute"] + r["t_memory"] + r["t_collective"]
        # Schur-update traffic: the roofline books only the multiplies'
        # HBM bytes; the 2 subtract passes per level each stream 3 half-n²
        # operand/result arrays through HBM on the XLA engines. The fused
        # pallas kernel folds them into the GEMM's accumulator flush, so it
        # is charged none of this term — the roofline credit that makes the
        # fused engine the modeled winner for b > 1 on TPU.
        if plan.multiply_engine != "pallas":
            sub_bytes = sum(
                2**i * 2 * 3 * (sig.n / 2**(i + 1))**2 * bytes_
                for i in range(max(b.bit_length() - 1, 0)))
            total += sub_bytes / (chips * hw["hbm_bw"])
        # Leaf re-pricing: the roofline books leaf flops inside t_compute at
        # full chips-parallel rate, but the recursion SERIALIZES leaves (the
        # paper's Eq. 2 — A11 before V) and each runs on one chip. Without
        # this term b=1 (one whole-matrix serial inversion) would always be
        # the modeled argmin and auto=True would never recurse on TPU.
        bs = plan.block_size
        leaf_flops = b * 2 * bs**3 / 3 * 2
        t_leaf_parallel = leaf_flops / (chips * peak)   # roofline's credit
        t_leaf_serial = leaf_flops / peak               # what actually runs
        total += (t_leaf_serial * _leaf_rate(plan.leaf_solver, "tpu")
                  - t_leaf_parallel)
        # Strassen re-pricing on the roofline: credit the MAC saving of the
        # 7-multiply recurrence vs the classical (sub_n/2)³ the roofline
        # booked, and charge the 18 add passes per split level their HBM
        # traffic (2 reads + 1 write per element) — the crossover term.
        if plan.multiply_engine == "strassen":
            for i in range(max(b.bit_length() - 1, 0)):
                nodes, half_n = 2**i, sig.n / 2**(i + 1)
                macs, adds = strassen_multiply_counts(half_n,
                                                      STRASSEN_CUTOFF)
                total += nodes * 6 * (
                    2 * (macs - half_n**3) / (chips * peak)
                    + 3 * adds * bytes_ / (chips * hw["hbm_bw"]))
        sweep = 2 * 2 * sig.n**3 / (chips * peak)
    else:
        p = _cost_params(sig, b, calibration)
        # strassen swaps the multiply term for the 7-multiply recurrence
        # (+ its add-pass crossover charge); every other class is shared.
        c = (strassen_cost(p) if plan.multiply_engine == "strassen"
             else spin_cost(p))
        leaf, mult = c["leafNode"], c["multiply"]
        total = (c["total"] - leaf - mult
                 + leaf * _leaf_rate(plan.leaf_solver, sig.backend)
                 + mult * _engine_rate(plan.multiply_engine, sig.backend))
        if plan.compute_dtype in ("bfloat16", "float16"):
            total *= 1.5                         # emulated half-precision
        # one NS sweep = 2 full-size distributed multiplies (2 n^3 MACs)
        sweep = 2 * sig.n**3 * p.t_flop / max(1.0, min(b * b, sig.cores))
    total += plan.refine_sweeps * sweep

    # Precision axis: when the signature carries a policy, the plan is
    # priced for SERVING, not just factorization — SERVE_HORIZON_COLS
    # columns of `apply_inverse` against the stored inverse. On TPU the
    # serve GEMM is HBM-bound (costmodel.apply_inverse_cost), so a bf16
    # store halves the term and beats exact storage despite its one-off
    # certification polish. On CPU half-precision is emulated (same 1.5x
    # penalty as the compute-dtype term above), so exact storage always
    # wins there and auto_store never picks bf16 off-accelerator.
    if sig.precision and sig.kind == "inverse":
        store = plan.store_dtype or sig.dtype
        if sig.backend == "tpu":
            chips = max(sig.device_count, 1)
            t_serve = apply_inverse_cost(
                sig.n, 1, chips, dtype_bytes=DTYPE_BYTES.get(store, 4),
                hw=tpu_peaks(sig.device_kind))
        else:
            p_srv = _cost_params(sig, b, calibration)
            t_serve = (2 * sig.n**2 * p_srv.t_flop
                       / max(1.0, min(float(sig.n), sig.cores)))
            if store in ("bfloat16", "float16", "float8_e4m3fn"):
                t_serve *= 1.5               # emulated low precision
        total += SERVE_HORIZON_COLS * t_serve
        if store != sig.dtype:
            total += sweep                   # certification polish, one-off
    return float(total)


def rank_plans(sig: ProblemSignature, candidates: list[Plan],
               calibration: dict | None = None) -> list[Plan]:
    """Candidates sorted by modeled cost, each annotated with its score."""
    scored = [dataclasses.replace(p, predicted_s=predict_cost(
        sig, p, calibration)) for p in candidates]
    return sorted(scored, key=lambda p: p.predicted_s)


# ---------------------------------------------------------------------------
# Live refinement
# ---------------------------------------------------------------------------


def _bench_operands(sig: ProblemSignature):
    import jax.numpy as jnp

    from repro.core import testing

    dtype = jnp.dtype(sig.dtype)
    a = testing.make_spd(sig.n, jax.random.PRNGKey(0), dtype=dtype)
    if sig.kind == "solve":
        rhs = jax.random.normal(jax.random.PRNGKey(1), (sig.n, 8),
                                dtype=jnp.float32).astype(dtype)
        return a, rhs
    return (a,)


def measure_plans(sig: ProblemSignature, plans: list[Plan], *,
                  warmup: int = 1, iters: int = 5) -> list[float]:
    """Best-of-`iters` wall seconds for each plan, measured round-robin.

    Min, not median: scheduler noise on loaded hosts is strictly additive,
    so the fastest observation is the least-contaminated one. Round-robin
    (all candidates once per round, `iters` rounds) rather than
    per-candidate batches, so a slow system phase penalizes every candidate
    equally instead of whichever one it happened to land on.
    """
    import functools

    from . import dispatch  # late: dispatch imports this module

    operands = _bench_operands(sig)
    # Time the executor the plan will actually run under: for sharded-
    # placement signatures that is the mesh-resident program, not the dense
    # path (timing the wrong program would persist a mis-measured plan).
    run = functools.partial(
        dispatch.execute_solve if sig.kind == "solve"
        else dispatch.execute_inverse,
        placement=sig.placement)
    for plan in plans:                       # compile + warm every plan first
        for _ in range(warmup):
            jax.block_until_ready(run(plan, *operands))
    best = [float("inf")] * len(plans)
    for _ in range(iters):
        for i, plan in enumerate(plans):
            t0 = time.perf_counter()
            jax.block_until_ready(run(plan, *operands))
            best[i] = min(best[i], time.perf_counter() - t0)
    return best


def measure_plan(sig: ProblemSignature, plan: Plan, *, warmup: int = 1,
                 iters: int = 5) -> float:
    """Best-of-`iters` wall seconds of one planned execution."""
    return measure_plans(sig, [plan], warmup=warmup, iters=iters)[0]


def _calibration_points(measured: list[Plan], sig: ProblemSignature
                        ) -> dict[int, float]:
    """{b: seconds} along the default axis (linalg / einsum / native dtype)."""
    pts = {}
    for p in measured:
        if (p.leaf_solver == "linalg" and p.multiply_engine == "einsum"
                and p.compute_dtype == sig.dtype and p.refine_sweeps == 0
                and not p.store_dtype and p.measured_s is not None):
            pts[p.grid(sig.n)] = p.measured_s
    return pts


def autotune(sig: ProblemSignature, candidates: list[Plan], *,
             measure: bool = False, top_k: int | None = 4,
             calibration: dict | None = None
             ) -> tuple[Plan, dict | None]:
    """Choose a plan; returns (plan, new_calibration_or_None).

    measure=False: pure cost-model argmin (safe at trace time — no jax
    computation is issued). measure=True: microbenchmark the `top_k`
    model-ranked candidates (all of them when top_k is None) and take the
    measured argmin; calibration constants are refit when at least three
    grids were measured along the default axis.
    """
    ranked = rank_plans(sig, candidates, calibration)
    if not measure:
        if _TRACER.enabled:
            _TRACER.event(
                "planner.rank", "planner_decision", sig=sig.key(),
                decision="costmodel", candidates=len(candidates),
                chosen=ranked[0].to_dict(),
                modeled_top=[{"block_size": p.block_size,
                              "engine": p.multiply_engine,
                              "leaf_solver": p.leaf_solver,
                              "predicted_s": p.predicted_s}
                             for p in ranked[:4]])
        return ranked[0], None

    short = ranked if top_k is None else ranked[:max(top_k, 1)]
    # Outside a mesh context the SUMMA engines fall back to einsum, so
    # engine-only variants execute the SAME program — measuring them
    # separately would let timer noise pick the engine. Measure one
    # representative per behavioral group (the best-ranked one, so ties
    # resolve to the model's preference) and share its time. The signature's
    # mesh descriptor (captured at signature_for time) is the authority: it
    # is what the plan will be cached under, so grouping must agree with it.
    # The fused `pallas` engine runs different code with or without a mesh,
    # so it is always its own behavior group; `strassen` likewise — its
    # recursion differs from one einsum even off-mesh.
    mesh_active = bool(sig.mesh)

    def behavior(p: Plan) -> tuple:
        engine = p.multiply_engine
        if not mesh_active and engine in ("allgather", "ring"):
            engine = "einsum"            # SUMMA collapses to einsum off-mesh
        return (p.block_size, p.leaf_solver, p.compute_dtype,
                p.refine_sweeps, p.store_dtype, engine)

    reps: dict[tuple, Plan] = {}
    for p in short:
        reps.setdefault(behavior(p), p)
    uniq = list(reps.values())
    secs = dict(zip(map(behavior, uniq), measure_plans(sig, uniq)))
    timed = [dataclasses.replace(p, measured_s=secs[behavior(p)],
                                 source="measured") for p in short]
    best = min(timed, key=lambda p: p.measured_s)   # ties -> ranked order

    new_calib = None
    pts = _calibration_points(timed, sig)
    if sig.backend != "tpu" and len(pts) >= 3:
        fit = fit_scale(spin_cost, pts, n=sig.n, cores=sig.cores)
        new_calib = {"t_flop": fit.t_flop, "t_leaf": fit.t_leaf,
                     "t_block_op": fit.t_block_op, "t_elem": fit.t_elem}
    if _TRACER.enabled:
        _TRACER.event(
            "planner.measure", "planner_decision", sig=sig.key(),
            decision="measured", candidates=len(candidates),
            measured=len(short), behavior_groups=len(uniq),
            chosen=best.to_dict(), calibrated=new_calib is not None,
            microbench=[{"block_size": p.block_size,
                         "engine": p.multiply_engine,
                         "leaf_solver": p.leaf_solver,
                         "predicted_s": p.predicted_s,
                         "measured_s": p.measured_s}
                        for p in timed])
    return best, new_calib
