"""Refactor-or-update policy for the online inverse service (DESIGN.md §9).

A maintained inverse under churn has two ways to absorb a rank-k change:
fold it in with a Woodbury update (O(n²k), `core.update`) or re-run the
planned SPIN inversion from scratch (O(n³)-class, but it resets accumulated
drift and restores the exact-recursion solve path). This module prices both
sides with the SAME cost machinery the autotuner uses — `costmodel.
spin_cost` (calibrated, CPU/GPU) or `costmodel.tpu_roofline_cost` (TPU) via
`autotune.predict_cost` for the re-inversion, and a matching panel-GEMM
model for the SMW side — and decides per update.

The crossover rule is rent-or-buy: keep renting (SMW) until the cumulative
SMW spend since the last factorization reaches `slack ×` the modeled
re-inversion price, then buy (re-factorize). With slack=1 total spend is at
most 2× the offline optimum for any adversarial update stream — the classic
ski-rental bound. Two overriding triggers bypass the cost race:

  * drift — the probe residual estimate (`core.update.DriftTracker`)
    exceeds its dtype-aware bound: the maintained inverse is no longer
    conformant, so accuracy forces a rebuild regardless of cost;
  * rank — accumulated rank approaches n (`max_rank_fraction`): the k×k
    capacitance solve stops being "small" and SMW loses its O(n²k) edge.

Re-inversion plans are fetched with the signature's `update_rank` axis set,
so a plan priced under churn K caches separately from the offline plan for
the same (kind, n, dtype) and round-trips the schema-v2 plan cache. The
policy quantizes the axis to the next power of two before looking up: a
stream of rank-1 updates must not mint one cache entry (and one plan
enumeration + cache-file rewrite) per accumulated-rank value on the
serving hot path — bucketing bounds the distinct keys at log₂(n) and makes
every decide() after the first per bucket an in-memory cache read.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core.costmodel import DTYPE_BYTES, CostParams, tpu_peaks

from .cache import PlanCache, default_cache
from .plan import Plan, ProblemSignature, signature_for

__all__ = ["RefactorDecision", "RefactorPolicy", "smw_update_cost"]


def _store_dtype(sig: ProblemSignature) -> str:
    """Dtype the maintained inverse is resident in — the HBM-traffic dtype.

    A low-precision policy on the signature means the SMW panel products
    stream a narrower resident operand (bf16 halves the memory term that
    dominates small-k updates), which shifts the rent-or-buy crossover.
    """
    if sig.precision:
        from repro.core.precision import PrecisionPolicy

        store = PrecisionPolicy.from_descriptor(sig.precision).store_dtype
        if store:
            return store
    return sig.dtype


def smw_update_cost(sig: ProblemSignature, k: int,
                    calibration: dict | None = None) -> float:
    """Modeled seconds to fold one rank-k Woodbury update into the inverse.

    Four n×k panel products against the resident n² operand (A⁻¹U, VᵀA⁻¹,
    capacitance product, rank-k correction) plus the k³ capacitance solve.
    CPU/GPU: the paper's §4 convention — MAC units × t_flop (calibrated
    when the plan cache holds fitted constants) over PF = min(items, cores).
    TPU: roofline max of the MXU flop time and streaming the resident
    inverse through HBM twice (read + write), the term that dominates for
    small k and is exactly what the fused offline engine never pays.
    """
    n = sig.n
    if sig.backend == "tpu":
        chips = max(sig.device_count, 1)
        bytes_ = DTYPE_BYTES.get(_store_dtype(sig), 4)
        flops = (4 * n * n * k + k ** 3) * 2
        hw = tpu_peaks(sig.device_kind)
        t_compute = flops / (chips * hw["peak_flops"])
        t_memory = 2 * n * n * bytes_ / (chips * hw["hbm_bw"])
        return float(max(t_compute, t_memory))
    t_flop = (calibration or {}).get("t_flop") or CostParams(
        n=n, b=1, cores=sig.cores).t_flop
    pf = max(1.0, min(float(n * k), sig.cores))
    return float((4 * n * n * k + k ** 3) * t_flop / pf)


@dataclasses.dataclass(frozen=True)
class RefactorDecision:
    """One policy verdict, with the prices that produced it."""

    refactor: bool
    reason: str             # "smw" | "crossover" | "drift" | "rank"
    smw_cost_s: float       # modeled price of folding THIS update in
    refactor_cost_s: float  # modeled price of a fresh planned re-inversion
    cumulative_s: float     # SMW spend since last factorization, incl. this
    plan: Plan              # the re-inversion plan the refactor would run


class RefactorPolicy:
    """Prices cumulative SMW updates against a planned re-inversion.

    slack: rent-or-buy multiplier (1.0 = 2-competitive; >1 defers
    refactors, <1 hastens them). max_rank_fraction: accumulated-rank bound
    as a fraction of n. The policy is pure pricing — it mutates nothing;
    the service acts on the returned decision.
    """

    def __init__(self, *, slack: float = 1.0,
                 max_rank_fraction: float = 0.5,
                 cache: PlanCache | None = None):
        if slack <= 0:
            raise ValueError(f"slack must be positive, got {slack}")
        self.slack = slack
        self.max_rank_fraction = max_rank_fraction
        self._cache = cache

    def _plan_for(self, sig: ProblemSignature) -> tuple[Plan, dict | None]:
        from .dispatch import get_plan  # late: dispatch imports siblings

        cache = self._cache or default_cache()
        plan = get_plan(sig.kind, sig.n, jnp.dtype(sig.dtype),
                        measure=False, cache=cache,
                        placement=sig.placement,
                        update_rank=sig.update_rank,
                        precision=sig.precision or None)
        return plan, cache.get_calibration(sig)

    def decide(self, n: int, dtype, *, new_rank: int,
               pending_rank: int = 0,
               cumulative_s: float = 0.0,
               residual_est: float = 0.0,
               drift_tolerance: float = float("inf"),
               placement: str = "dense",
               precision: str = "") -> RefactorDecision:
        """Fold the next rank-`new_rank` update in, or re-factorize?

        pending_rank / cumulative_s: accumulated rank and modeled SMW spend
        since the last factorization (the service's ledger). residual_est /
        drift_tolerance: the drift tracker's probe estimate and bound.
        `precision` (a PrecisionPolicy descriptor, "" = exact) prices both
        sides at the policy's resident store dtype.
        """
        from .autotune import predict_cost  # late: avoids import cycle

        total_rank = pending_rank + int(new_rank)
        # Next power of two ≥ total_rank: the cache axis the plan is
        # fetched under (see module docstring on why not the exact rank).
        bucket = 1 << max(total_rank - 1, 0).bit_length()
        sig = signature_for("inverse", n, dtype, placement=placement,
                            update_rank=bucket, precision=precision)
        plan, calibration = self._plan_for(sig)
        smw_s = smw_update_cost(sig, int(new_rank), calibration)
        refactor_s = predict_cost(sig, plan, calibration)
        cumulative = cumulative_s + smw_s

        if residual_est > drift_tolerance:
            reason, refactor = "drift", True
        elif total_rank >= self.max_rank_fraction * n:
            reason, refactor = "rank", True
        elif cumulative >= self.slack * refactor_s:
            reason, refactor = "crossover", True
        else:
            reason, refactor = "smw", False
        return RefactorDecision(refactor=refactor, reason=reason,
                                smw_cost_s=smw_s,
                                refactor_cost_s=refactor_s,
                                cumulative_s=cumulative, plan=plan)

    def reinversion_cost(self, n: int, dtype, *,
                         placement: str = "dense",
                         precision: str = "") -> float:
        """Modeled seconds of a fresh planned inversion of an (n, n)
        matrix — the price `SpinService`'s cost-aware eviction uses: a
        matrix that is expensive to re-factorize is expensive to get
        wrong by evicting, so it earns proportionally more residency
        credit (GreedyDual). Same `predict_cost` machinery as `decide`,
        under the offline signature (no churn axis)."""
        from .autotune import predict_cost  # late: avoids import cycle

        sig = signature_for("inverse", n, dtype, placement=placement,
                            precision=precision)
        plan, calibration = self._plan_for(sig)
        return float(predict_cost(sig, plan, calibration))

    def crossover_rank(self, n: int, dtype, *, step_rank: int = 1,
                       placement: str = "dense") -> int:
        """Accumulated rank at which a steady rank-`step_rank` update stream
        first triggers a refactor (benchmark/report helper; the decision
        path itself stays incremental)."""
        cumulative, rank = 0.0, 0
        while True:
            d = self.decide(n, dtype, new_rank=step_rank,
                            pending_rank=rank, cumulative_s=cumulative,
                            placement=placement)
            rank += step_rank
            if d.refactor:
                return rank
            cumulative = d.cumulative_s
