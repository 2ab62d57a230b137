"""Plan and problem-signature types for the SPIN autotuner.

A *plan* is everything `spin_inverse`/`spin_solve` need beyond the operands:
the block grid (the paper's `b`, stored as `block_size = n/b`), the leaf
solver, the distributed-multiply engine, the compute dtype, an optional
Newton–Schulz refinement stage, and the grid-over-mesh sharding axes. A
*problem signature* is the key the plan is selected (and cached) under:
(kind, n, dtype, backend, device_count, cores) — everything the U-curve of
paper Fig. 3 depends on. Plans are plain frozen dataclasses so they
round-trip losslessly through the JSON plan cache.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp

from repro.core.leaf import LEAF_SOLVERS

__all__ = ["Plan", "ProblemSignature", "signature_for", "enumerate_plans",
           "candidate_grids", "mesh_descriptor", "compiles_on_tpu",
           "STRASSEN_MIN_N"]

# Smallest problem dimension at which the Strassen engine enters the
# default candidate space. Below this every sub-multiply of the SPIN
# recursion sits at/below the Strassen crossover cutoff (512 — see
# costmodel.STRASSEN_CUTOFF), so an enumerated strassen plan would execute
# the identical classical program and only add measurement noise; the first
# genuinely split Strassen level needs half-n > cutoff, i.e. n ≥ 2048.
STRASSEN_MIN_N = 2048


def mesh_descriptor() -> str:
    """Canonical string for the ambient mesh, e.g. "data2:model2" ("" = none).

    The signature dimension that keeps a plan tuned under one mesh topology
    from being served under another — device_count alone cannot tell a
    (8, 1) mesh from a (4, 2) one, and tells nothing about a 1-device plan
    being recalled inside an 8-device mesh context. Delegates to the single
    canonical implementation so plan-cache keys and the sharded programs'
    jit fingerprints can never drift apart.
    """
    from repro.parallel.sharded_blockmatrix import mesh_fingerprint

    return mesh_fingerprint()


@dataclasses.dataclass(frozen=True)
class ProblemSignature:
    """Everything plan selection may depend on. `key()` is the cache key."""

    kind: str            # "inverse" | "solve"
    n: int               # matrix dimension
    dtype: str           # canonical dtype name ("float32", "bfloat16", ...)
    backend: str         # jax.default_backend(): "cpu" | "gpu" | "tpu"
    device_count: int    # devices in the mesh (paper's worker count)
    cores: int           # parallel lanes for the §4 cost model's PF terms
    mesh: str = ""       # ambient mesh topology ("data2:model2", "" = none)
    placement: str = "dense"  # engine placement: "dense" | "sharded"
    update_rank: int = 0  # accumulated SMW churn the plan is priced under
    precision: str = ""  # PrecisionPolicy.descriptor() ("" = exact default)
    constraint: str = ""  # e.g. "bs64" when the block grid is pre-fixed
    device_kind: str = ""  # jax device_kind, e.g. "TPU v5 lite" (TPU peaks)

    def key(self) -> str:
        base = (f"{self.kind}/n{self.n}/{self.dtype}/{self.backend}"
                f"/d{self.device_count}/c{self.cores}"
                f"/m{self.mesh or 'none'}/{self.placement}")
        # The online-service axis (refactor_policy): a re-inversion plan
        # priced under accumulated update rank K caches under its own key.
        # Appended only when nonzero so every pre-existing key is unchanged.
        if self.update_rank:
            base += f"/u{self.update_rank}"
        # The precision axis (core.precision): a plan priced under a
        # low-precision policy caches under its own key; appended only when
        # set so exact-policy keys are unchanged. This axis is why the cache
        # schema bumped to v3 — v2 entries carry signature dicts without it.
        if self.precision:
            base += f"/p{self.precision}"
        # TPU plans are priced against one chip kind's peaks; appended only
        # for TPU signatures so every CPU/GPU key is unchanged.
        if self.backend == "tpu":
            base += f"/k{self.device_kind}"
        return f"{base}/{self.constraint}" if self.constraint else base

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def signature_for(kind: str, n: int, dtype=jnp.float32, *,
                  backend: str | None = None,
                  device_count: int | None = None,
                  cores: int | None = None,
                  mesh: str | None = None,
                  placement: str = "dense",
                  update_rank: int = 0,
                  precision: str = "",
                  constraint: str = "",
                  device_kind: str | None = None) -> ProblemSignature:
    """Build the signature for the *current* runtime.

    `cores` feeds the cost model's parallelization-factor terms: on CPU the
    XLA thread pool parallelizes block GEMMs across host cores even with one
    "device", so it defaults to os.cpu_count(); on accelerators it is the
    device count (the paper's `cores` = Spark executors). `mesh` defaults to
    the ambient mesh topology and `placement` to the dense executors; both
    are part of the cache key, so plans never cross mesh contexts.
    `device_kind` defaults to the live device's kind when `backend` is the
    live backend; a hypothetical TPU signature must name its chip kind.
    """
    live = jax.default_backend()
    backend = backend or live
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind if backend == live else ""
    device_count = device_count or jax.device_count()
    if cores is None:
        cores = (max(os.cpu_count() or 1, device_count)
                 if backend == "cpu" else device_count)
    if mesh is None:
        mesh = mesh_descriptor()
    if placement not in ("dense", "sharded"):
        raise ValueError(f"unknown placement {placement!r}")
    if update_rank < 0:
        raise ValueError(f"update_rank must be >= 0, got {update_rank}")
    return ProblemSignature(kind=kind, n=int(n), dtype=jnp.dtype(dtype).name,
                            backend=backend, device_count=int(device_count),
                            cores=int(cores), mesh=mesh, placement=placement,
                            update_rank=int(update_rank),
                            precision=precision,
                            constraint=constraint,
                            device_kind=device_kind)


@dataclasses.dataclass(frozen=True)
class Plan:
    """One executable configuration of the SPIN recursion."""

    block_size: int              # paper's n/b; grid b = n // block_size
    leaf_solver: str = "linalg"
    multiply_engine: str = "einsum"   # one of core.multiply._ENGINES
    compute_dtype: str = "float32"    # dtype the recursion runs in
    refine_sweeps: int = 0            # Newton–Schulz polish sweeps afterwards
    store_dtype: str = ""             # result storage dtype ("" = operand's)
    grid_axes: tuple[str, str] = ("data", "model")
    # provenance — not part of plan identity for execution purposes
    predicted_s: float | None = None  # cost-model score (seconds)
    measured_s: float | None = None   # microbenchmark wall-clock (seconds)
    source: str = "costmodel"         # "costmodel" | "measured" | "cache"

    def grid(self, n: int) -> int:
        return n // self.block_size

    def execution_key(self) -> tuple:
        """Identity of *what runs* (provenance fields excluded)."""
        return (self.block_size, self.leaf_solver, self.multiply_engine,
                self.compute_dtype, self.refine_sweeps, self.store_dtype,
                self.grid_axes)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["grid_axes"] = list(self.grid_axes)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Plan":
        known = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in known}
        kw["grid_axes"] = tuple(kw.get("grid_axes", ("data", "model")))
        return cls(**kw)


def candidate_grids(n: int, *, min_block: int = 8, max_grid: int = 64
                    ) -> list[int]:
    """Power-of-two grids b with n % b == 0 and n/b >= min_block.

    b=1 (single-leaf direct inversion) is always a candidate — it is the
    left endpoint of the paper's U-curve and the right answer for small n.
    """
    grids, b = [], 1
    while b <= max_grid and n % b == 0 and n // b >= min_block:
        grids.append(b)
        b *= 2
    return grids or [1]


# The Pallas kernel each kernel-backed leaf solver runs, per problem kind
# (kernels/leaf_inverse: the solve path's `pallas` leaf substitutes with
# the triangular-solve kernel instead of inverting).
_LEAF_KERNELS = {
    "inverse": {"pallas": "pallas", "gauss_jordan": "gauss_jordan"},
    "solve": {"pallas": "triangular_solve", "gauss_jordan": "gauss_jordan"},
}


def compiles_on_tpu(kind: str, block_size: int, leaf_solver: str,
                    engine: str) -> bool:
    """Whether every Pallas kernel the plan runs compiles for a TPU at
    `block_size`: a whole-block leaf kernel must fit its VMEM reckoning
    (`kernels.leaf_inverse.kernel.max_block_size`, the sizes
    tests/test_tpu_compile.py compiles), and the fused GEMM engine needs a
    Mosaic-legal leaf tiling (`kernels.strassen.ops.mosaic_legal`)."""
    from repro.kernels.leaf_inverse.kernel import max_block_size
    from repro.kernels.strassen.ops import mosaic_legal

    kernel = _LEAF_KERNELS.get(kind, {}).get(leaf_solver)
    if kernel is not None and block_size > max_block_size(kernel):
        return False
    return engine != "pallas" or mosaic_legal(block_size)


def enumerate_plans(sig: ProblemSignature, *,
                    min_block: int = 8,
                    max_grid: int = 64,
                    leaf_solvers: tuple[str, ...] | None = None,
                    engines: tuple[str, ...] | None = None,
                    include_refinement: bool | None = None,
                    block_sizes: tuple[int, ...] | None = None
                    ) -> list[Plan]:
    """The raw candidate space for `sig` (unscored, deduplicated).

    Refinement variants (bfloat16 recursion + Newton–Schulz polish back to
    the requested precision) are only enumerated for `kind="inverse"` —
    Newton–Schulz polishes an inverse, not a solve, and `execute_solve`
    would silently ignore the stage — and only where bf16 is a hardware
    dtype (TPU) with float32 results requested; on CPU bf16 is emulated and
    never wins. The sharded placement is likewise excluded: the
    mesh-resident recursion has no refinement stage, so a refined sharded
    plan would describe an execution that never happens.

    The fused-kernel ``pallas`` engine is enumerated by default only on TPU
    (same gating idea as refinement): off-TPU it runs in interpret mode and
    can never win, and top_k=None measurement sweeps would pay for warming
    interpret-mode programs. Pass `engines=(..., "pallas")` to opt in
    anywhere. The ``strassen`` engine is enumerated only for large-n
    signatures (n ≥ STRASSEN_MIN_N) where its recursion actually splits;
    pass `engines=(..., "strassen")` to opt in below that.
    """
    if leaf_solvers is None:
        leaf_solvers = tuple(LEAF_SOLVERS)
    if engines is None:
        engines = (("einsum", "allgather", "ring")
                   if sig.device_count > 1 else ("einsum",))
        if sig.backend == "tpu":
            engines = engines + ("pallas",)
        if sig.n >= STRASSEN_MIN_N:
            engines = engines + ("strassen",)
    if include_refinement is None:
        include_refinement = sig.backend == "tpu" and sig.dtype == "float32"
    include_refinement = (include_refinement and sig.kind == "inverse"
                          and sig.placement != "sharded")

    if block_sizes is not None:
        grids = sorted({sig.n // bs for bs in block_sizes if sig.n % bs == 0})
    else:
        grids = candidate_grids(sig.n, min_block=min_block, max_grid=max_grid)

    plans: list[Plan] = []
    for b in grids:
        bs = sig.n // b
        # b == 1 has no distributed multiplies — engine is irrelevant.
        for engine in (engines if b > 1 else engines[:1]):
            for leaf in leaf_solvers:
                if sig.backend == "tpu" and not compiles_on_tpu(
                        sig.kind, bs, leaf, engine):
                    continue
                plans.append(Plan(block_size=bs, leaf_solver=leaf,
                                  multiply_engine=engine,
                                  compute_dtype=sig.dtype))
                if include_refinement and b > 1:
                    plans.append(Plan(block_size=bs, leaf_solver=leaf,
                                      multiply_engine=engine,
                                      compute_dtype="bfloat16",
                                      refine_sweeps=2))
    return _store_dtype_variants(sig, plans)


def _store_dtype_variants(sig: ProblemSignature, plans: list[Plan]
                          ) -> list[Plan]:
    """Expand candidates along the precision axis (`sig.precision`).

    An exact signature passes through untouched. A pinned policy (e.g. the
    "bf16" preset) rewrites every candidate to store at the pinned dtype —
    the service will store there regardless, so pricing anything else would
    rank a plan that never runs. An `auto_store` policy prices BOTH the
    exact and the low-precision store for each candidate and lets
    `predict_cost`'s serving-amortization term decide — the path by which
    `auto=True` *chooses* low-precision serving. Solve-kind and sharded
    signatures keep exact storage: there is no maintained low-precision
    operand to store in either case.
    """
    if not sig.precision or sig.kind != "inverse" or sig.placement == "sharded":
        return plans
    from repro.core.precision import PrecisionPolicy  # late: no cycle

    policy = PrecisionPolicy.from_descriptor(sig.precision)
    out: list[Plan] = []
    for p in plans:
        for store in policy.candidate_store_dtypes(sig.dtype):
            out.append(p if store == sig.dtype
                       else dataclasses.replace(p, store_dtype=store))
    return out
