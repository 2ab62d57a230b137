"""SpinService: the online inverse server (DESIGN.md §9).

The offline stack (batched solve → planner → mesh-resident recursion →
fused kernels) answers "invert this matrix once, fast". The ROADMAP's
north star is serving: a long-lived inverse answering a *stream* of solve
requests while the matrix itself mutates underneath. `SpinService` makes
the maintained inverse a request-serving object:

  * **factorization held device-resident** — each admitted matrix keeps
    its current A and maintained A⁻¹ on device (dense arrays, or
    `ShardedBlockMatrix` pairs pinned to the mesh — the sharded state
    never gathers to dense between requests);
  * **continuous batching** — the same slot scheduler shape as
    `ServingEngine`: a fixed pool of micro-batch slots, requests admitted
    from a FIFO queue as slots free up, one `tick()` advances every live
    slot. Solve slots targeting the same matrix AND the same rhs dtype
    are COALESCED into one multi-RHS call per tick, so c concurrent
    requests cost one panel recursion/GEMM instead of c (dtype is part of
    the coalesce key: concatenating a bf16 panel next to an f32 one would
    silently upcast and change the f32 request's bitwise answer);
  * **admission control** (`serving.admission`) — a bounded queue with
    priority/deadline-aware admission and an explicit shed-load policy:
    at `max_queue` a new request either evicts a strictly lower-priority
    queued solve (the victim gets a typed `Rejection` verdict) or is
    rejected at submission with `AdmissionRejected`; `per_matrix_quota`
    keeps one hot tenant from starving the rest; queued requests whose
    deadline expires are shed, never silently served late. No rejected
    request ever hangs — every outcome is a typed verdict;
  * **observability** (`serving.metrics`) — per-request queue-wait /
    solve / total latency with rolling p50/p95/p99, queue depth sampled
    per tick, per-path and per-rejection-reason counters, surfaced as
    `SpinService.metrics()` and reported by `benchmarks/bench_serve.py`;
  * **exact solve path** — a matrix with zero pending churn serves its
    coalesced batch through the planner-configured `spin_solve` entry
    point, bitwise-identical to the offline call on the same stacked
    panel. Once SMW updates have been folded in, solves come from the
    maintained inverse in O(n²·c) (`core.update.apply_inverse`);
  * **low-precision fast path** (`core.precision.PrecisionPolicy`) — a
    matrix admitted under a low-precision policy (`add_matrix(...,
    precision="bf16")`, a policy object, or the service/env default)
    keeps its maintained inverse in the policy's STORE dtype and serves
    every request straight from it through the policy's compute dtype
    with f32 accumulation — one memory-bound GEMM at half (bf16) or a
    quarter (fp8 storage hook) of the HBM bytes, never the recursion.
    The serve error is CERTIFIED: after factorization and after every
    SMW fold the service probes the residual through the SAME
    low-precision GEMM it serves with (`estimate_inverse_residual(
    precision=...)`) and, only when the probe exceeds the policy's bound,
    fires Newton–Schulz polish sweeps (f32 compute, recast to the store
    dtype) until it is back under the bound (or the policy's give-up
    cap). The certified residual is reported on each request
    (`SolveRequest.residual_est`) exactly like degraded mode reports its
    sketch residual, and `polish_triggers`/`polish_sweeps` land in
    `stats`/`metrics()`. Low-precision serving is dense-only: sharded
    placement with a non-exact policy is rejected at `add_matrix`;
  * **incremental updates** — rank-k mutations and block row/column
    replacements (`UpdateRequest`) are folded into the maintained inverse
    by Woodbury identity in O(n²k) (`core.update.smw_update_inverse`),
    with the matrix side kept in lockstep (`add_low_rank`);
  * **refactor policy** — every update is priced by
    `planner.refactor_policy.RefactorPolicy` (cumulative SMW spend vs the
    planned re-inversion, plus drift/rank bounds). At the crossover the
    service re-factorizes in the background: the fresh inversion is
    DISPATCHED (XLA async) without blocking the scheduler loop, and the
    next consumer of the new inverse synchronizes on it naturally;
  * **multi-tenant residency** — `max_resident` bounds how many matrices
    stay device-resident. Beyond it the service evicts by cost-aware LRU
    (GreedyDual: residency credit = recency clock + the planner's modeled
    re-inversion price, `RefactorPolicy.reinversion_cost`), spilling the
    evicted pair through `core.solver_ckpt.save_matrix_spill`; a request
    for an evicted matrix rehydrates it transparently from its spill —
    the maintained inverse round-trips bit-exactly, never re-factorized.
    When every resident matrix is momentarily hot (live slot, queued
    request, background work) rehydration hits `ResidencyBusy`: the
    request is DEFERRED and retried next tick — transient pressure is
    never an error, even with max_resident < concurrently-active
    tenants. Only a genuine spill I/O `OSError` fails the request (solve
    or update alike), with a typed failed/error verdict on the object;
  * **degraded-mode serving** — with a `solve_deadline_s`, the exact
    recursion path runs guarded (retry with exponential backoff on
    `WorkerFailure`, deadline via the straggler layer's background tasks).
    A hung shard flips the matrix into degraded mode: queued solves are
    NEVER dropped — they are answered from a sketched approximate inverse
    (`core.solve.sketched_approx_inverse`: randomized sketch +
    Newton–Schulz polish to within the DriftTracker tolerance, i.e.
    drift_scale × the dtype residual tolerance) with the probe residual
    REPORTED on each request (`SolveRequest.residual_est`). When the hung
    shard's background work finally lands, the service re-factorizes and
    exits degraded mode;
  * **snapshot/restore & warm restarts** — `snapshot()` /
    `SpinService.restore()` persist every matrix's state (resident AND
    evicted) plus the straggler-guard and admission config through
    `core.solver_ckpt.save_service_snapshot`, so a restarted service
    resumes bit-identically with its deadline protection intact
    (`restore(**overrides)` is the explicit ops path to change guard
    knobs on the way back up). `snapshot_async()` captures a quiesced
    copy (JAX arrays are immutable, so the references ARE the copy) and
    runs the device→host transfer + file I/O on a background thread — the
    tick loop never stalls on a snapshot. The service turns on the
    persistent XLA compilation cache (`compat.enable_compilation_cache`:
    ``$JAX_COMPILATION_CACHE_DIR`` or `<checkout>/.jax_cache`), so a
    restarted process loads its programs instead of recompiling them.

Consistency model: per-matrix FIFO. An update acts as a barrier — solves
submitted before it complete against the pre-update matrix, solves after
it see the post-update one; requests on different matrices reorder freely
(admission drains highest-priority first across matrices, with effective
priorities clamped so the per-matrix order is preserved — see
`serving.admission`).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import tempfile
import time
from collections import defaultdict, deque
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.blockmatrix import BlockMatrix
from repro.core.precision import (PrecisionPolicy, dot_precision,
                                  resolve_precision)
from repro.core.solver_ckpt import validate_snapshot_key as \
    _validate_snapshot_key
from repro.core.solve import (sketched_approx_inverse, spin_solve_dense,
                              spin_solve_sharded)
from repro.core.spin import spin_inverse_dense, spin_inverse_sharded
from repro.obs import flight as _flight
from repro.obs.trace import TRACER as _TRACER
from repro.core.update import (DriftTracker, add_low_rank, apply_inverse,
                               block_update_factors,
                               estimate_inverse_residual,
                               smw_update_inverse)
from repro.parallel.straggler import (FaultPlan, ShardTimeout, WorkerFailure,
                                      retry_with_backoff, start_background)

from .admission import (AdmissionConfig, AdmissionRejected, Rejection,
                        order_for_admission, shed_victim)
from .metrics import ServiceMetrics

__all__ = ["SolveRequest", "UpdateRequest", "MatrixState", "ResidencyBusy",
           "SpinService"]


class ResidencyBusy(RuntimeError):
    """Transient: room is needed for one more resident matrix but every
    candidate is momentarily hot (live slot, queued request, background
    work). Admission defers the request and retries next tick — this is
    NOT a failure, unlike an `OSError` from the spill/rehydrate I/O."""


@functools.partial(jax.jit, static_argnames=("sweeps",))
def _ns_polish_dense(a: jax.Array, x: jax.Array, sweeps: int) -> jax.Array:
    """`sweeps` Newton–Schulz iterations X ← X(2I − AX) in f32 on a dense
    pair — the certification polish for low-precision maintained inverses.
    Returns f32; the caller recasts to the policy's store dtype."""
    a32 = a.astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    eye2 = 2.0 * jnp.eye(a.shape[0], dtype=jnp.float32)
    hi = dot_precision(jnp.float32)
    for _ in range(sweeps):
        x32 = jnp.matmul(x32, eye2 - jnp.matmul(a32, x32, precision=hi),
                         precision=hi)
    return x32


@dataclasses.dataclass
class SolveRequest:
    """One A⁻¹·b request. rhs: (n,) or (n, c); x gets the matching shape."""

    uid: int
    matrix_id: str
    rhs: jax.Array
    priority: int = 0                # higher admits first / sheds last
    deadline_s: Optional[float] = None   # relative to submission
    # filled by the service
    x: Optional[jax.Array] = None
    done: bool = False
    slot: Optional[int] = None
    path: Optional[str] = None       # "recursion" | "maintained" | "degraded"
    residual_est: Optional[float] = None   # reported on the degraded path
    rejected: bool = False           # shed/rejected by admission control
    verdict: Optional[Rejection] = None    # typed verdict when rejected
    failed: bool = False             # batch execution failed
    error: Optional[str] = None      # the failure, when failed
    submit_t: Optional[float] = None       # service-clock timestamps
    admit_t: Optional[float] = None
    finish_t: Optional[float] = None


@dataclasses.dataclass
class UpdateRequest:
    """One matrix mutation: rank-k factors (u, v) with A ← A + u vᵀ, or a
    symmetric block row/column replacement (delta_row, index) — see
    `core.update.block_update_factors`."""

    uid: int
    matrix_id: str
    u: Optional[jax.Array] = None
    v: Optional[jax.Array] = None
    delta_row: Optional[jax.Array] = None
    index: Optional[int] = None
    priority: int = 0
    # filled by the service
    done: bool = False
    refactored: Optional[bool] = None
    reason: Optional[str] = None     # policy verdict ("smw"/"crossover"/…)
    rejected: bool = False
    verdict: Optional[Rejection] = None
    failed: bool = False             # rehydration/apply failed
    error: Optional[str] = None      # the failure, when failed
    submit_t: Optional[float] = None
    finish_t: Optional[float] = None


@dataclasses.dataclass
class MatrixState:
    """Device-resident serving state of one maintained inverse."""

    matrix_id: str
    a: object                        # dense (n, n) array | ShardedBlockMatrix
    inv: object                      # same representation as `a`
    placement: str                   # "dense" | "sharded"
    block_size: int
    leaf_solver: str
    engine: str | None
    plan: object                     # the planner Plan the config came from
    drift: DriftTracker
    n: int = 0
    dtype: object = None
    smw_spent_s: float = 0.0         # modeled SMW spend since last factorize
    smw_applied: int = 0
    refactors: int = 0
    # low-precision serving (core.precision.PrecisionPolicy)
    precision: str = ""              # pinned policy descriptor; "" = exact
    store_dtype: str = ""            # maintained-inverse dtype ("" = operand)
    serve_bound: float = 0.0         # certified residual bound when lowp
    polish_triggers: int = 0         # certifications that needed polish
    polish_sweeps: int = 0           # total NS sweeps those firings ran
    # straggler/degraded-mode state (DESIGN.md §10)
    rank: int = 0                    # fault-plan rank of this matrix's shard
    degraded: bool = False
    sketch: object = None            # SketchedInverse, built lazily
    background: object = None        # the hung shard's BackgroundTask
    degraded_serves: int = 0
    # residency (cost-aware LRU)
    last_used: int = 0               # tick of the last touch
    credit: float = 0.0              # GreedyDual credit: clock + cost
    reinvert_cost_s: float = 0.0     # planner-modeled re-inversion price

    @property
    def pending_rank(self) -> int:
        return self.drift.update_rank


class SpinService:
    """Continuous-batching solve/update server over maintained inverses."""

    def __init__(self, *, slots: int = 8, policy=None,
                 drift_probes: int = 2, drift_scale: float = 10.0,
                 seed: int = 0, solve_deadline_s: float | None = None,
                 fault_plan=None, solve_retries: int = 1,
                 backoff_base_s: float = 0.01,
                 degraded_max_sweeps: int = 60,
                 max_queue: int | None = None,
                 per_matrix_quota: int | None = None,
                 max_resident: int | None = None,
                 spill_dir: str | None = None,
                 metrics_window: int = 4096,
                 clock=time.monotonic,
                 compile_cache: bool = True,
                 precision=None):
        from repro.compat import enable_compilation_cache
        from repro.planner import RefactorPolicy  # late: planner is optional

        self.slots = slots
        self.policy = policy or RefactorPolicy()
        # Service-default precision for add_matrix(precision=None): a
        # PrecisionPolicy, preset string, or None (per-matrix env/exact).
        self.precision = precision
        self.drift_probes = drift_probes         # 0 disables probe estimates
        self.drift_scale = drift_scale
        # Straggler guard: None deadline + None fault_plan keeps the exact
        # path a direct (bitwise-identical) call — no thread, no guard.
        self.solve_deadline_s = solve_deadline_s
        self.fault_plan = fault_plan
        self.solve_retries = solve_retries
        self.backoff_base_s = backoff_base_s
        self.degraded_max_sweeps = degraded_max_sweeps
        # SLA posture (serving.admission): defaults keep legacy behavior.
        self.admission = AdmissionConfig(max_queue=max_queue,
                                         per_matrix_quota=per_matrix_quota)
        # Residency: None = everything stays resident (legacy behavior).
        if max_resident is not None and max_resident < 1:
            raise ValueError(f"max_resident must be >= 1, got {max_resident}")
        self.max_resident = max_resident
        self._spill_dir = spill_dir
        self._evicted: dict[str, dict] = {}      # mid -> {"n", "rank", ...}
        self._evict_clock = 0.0                  # GreedyDual recency clock
        self._clock = clock
        self._metrics = ServiceMetrics(window=metrics_window, clock=clock)
        self._snapshot_task = None               # in-flight async snapshot
        # Warm restarts: XLA's persistent compilation cache, in
        # $JAX_COMPILATION_CACHE_DIR or the fixed in-checkout default
        # (compat.enable_compilation_cache); compile_cache=False leaves
        # the process's cache configuration alone.
        self.compile_cache_dir = (enable_compilation_cache()
                                  if compile_cache else None)
        self._free: deque[int] = deque(range(slots))
        self._live: dict[int, SolveRequest] = {}
        self._queue: deque = deque()
        self._matrices: dict[str, MatrixState] = {}
        self._uid = itertools.count()
        self._key = jax.random.PRNGKey(seed)
        self.ticks = 0
        self.stats = {"solves": 0, "batches": 0, "coalesced_cols": 0,
                      "updates_smw": 0, "updates_refactor": 0,
                      "degraded_serves": 0, "shard_timeouts": 0,
                      "shard_failures": 0, "retries": 0, "recoveries": 0,
                      "rejected": 0, "shed": 0, "batch_failures": 0,
                      "evictions": 0, "rehydrations": 0,
                      "lowp_serves": 0, "polish_triggers": 0,
                      "polish_sweeps": 0}

    # -- matrix admission ----------------------------------------------------

    def add_matrix(self, matrix_id: str, a, *, block_size: int | None = None,
                   leaf_solver: str | None = None, engine: str | None = None,
                   sharded: bool = False, precision=None) -> MatrixState:
        """Admit a matrix: plan its configuration, factorize, hold resident.

        `a`: dense (n, n) SPD array, or a `ShardedBlockMatrix` (implies
        sharded placement). Explicit block_size / leaf_solver / engine
        override the planner, mirroring the offline entry points.

        `precision` (PrecisionPolicy | preset string | None) selects this
        matrix's serve precision; None falls back to the service default,
        then $SPIN_PRECISION, then exact. A non-exact policy rides the
        planner signature (the plan prices bf16 storage in the roofline —
        with `auto` the PLANNER decides whether low-precision serving
        wins), the maintained inverse is held at the resolved store dtype,
        and serving is certified against the policy's residual bound.
        Dense placement only: sharded serving stays exact.
        """
        from repro.parallel.sharded_blockmatrix import ShardedBlockMatrix
        from repro.planner import get_plan

        if matrix_id in self._matrices or matrix_id in self._evicted:
            raise ValueError(f"matrix {matrix_id!r} already admitted")
        _validate_snapshot_key(matrix_id)       # snapshot dirs embed the id
        if isinstance(a, ShardedBlockMatrix):
            sharded = True
            n, dtype = a.n, a.dtype
            if block_size and block_size != a.block_size:
                raise ValueError(
                    f"block_size={block_size} conflicts with the sharded "
                    f"operand's fixed grid (block_size {a.block_size})")
            block_size = a.block_size
        elif isinstance(a, BlockMatrix):
            n, dtype = a.n, a.dtype
            # pre-blocked input: its grid is the plan constraint (same rule
            # as core.spin._resolve_sharded_config) unless explicitly
            # re-blocked — the dense path densifies and can re-block.
            block_size = block_size or a.block_size
        else:
            n, dtype = a.shape[0], a.dtype
        placement = "sharded" if sharded else "dense"
        pol = resolve_precision(
            precision if precision is not None else self.precision)
        if not pol.is_exact and placement == "sharded":
            raise ValueError(
                "low-precision serving is dense-only: sharded placement "
                "keeps the exact path (pass precision=None/'exact')")
        kw = {"block_sizes": (int(block_size),)} if block_size else {}
        plan = get_plan("inverse", n, dtype, measure=False,
                        placement=placement,
                        precision=None if pol.is_exact else pol, **kw)
        block_size = block_size or plan.block_size
        if isinstance(a, BlockMatrix) and not isinstance(
                a, ShardedBlockMatrix):
            a = a.to_dense()
        if sharded and not isinstance(a, ShardedBlockMatrix):
            a = ShardedBlockMatrix.from_dense(a, block_size)
        # Pin the policy's store decision: the plan's store_dtype is the
        # planner's (cost-priced) choice — for auto_store policies this is
        # where "should this matrix serve low-precision?" gets decided.
        op_name = jnp.dtype(dtype).name
        store = plan.store_dtype or (pol.store_dtype or "")
        if store == op_name:
            store = ""
        active = not pol.is_exact and (
            bool(store) or pol.resolve_compute(dtype) != op_name)
        if active:
            eff = dataclasses.replace(pol, store_dtype=store or None,
                                      auto_store=False)
            drift = DriftTracker(
                tolerance=self.drift_scale * eff.bound(dtype))
        else:
            eff = None
            drift = DriftTracker.for_dtype(dtype, scale=self.drift_scale)
        state = MatrixState(
            matrix_id=matrix_id, a=a, inv=None, placement=placement,
            block_size=int(block_size),
            leaf_solver=leaf_solver or plan.leaf_solver,
            engine=engine or plan.multiply_engine, plan=plan,
            drift=drift, n=int(n), dtype=jnp.dtype(dtype),
            rank=len(self._matrices) + len(self._evicted))
        if eff is not None:
            state.precision = eff.descriptor()
            state.store_dtype = store
            state.serve_bound = eff.bound(dtype)
        state.reinvert_cost_s = self._reinvert_cost(state)
        self._make_room(protect={matrix_id})
        self._factorize(state)
        self._matrices[matrix_id] = state
        self._touch(state)
        return state

    def matrix(self, matrix_id: str) -> MatrixState:
        """The matrix's serving state, rehydrating it if evicted."""
        return self._ensure_resident(matrix_id)

    def is_resident(self, matrix_id: str) -> bool:
        """Residency probe that never triggers a rehydration."""
        if matrix_id in self._matrices:
            return True
        if matrix_id in self._evicted:
            return False
        raise KeyError(f"unknown matrix {matrix_id!r}")

    def _factorize(self, state: MatrixState) -> None:
        """(Re)compute the maintained inverse. Dispatch only — XLA executes
        asynchronously, so the scheduler keeps ticking while the inversion
        runs; the first consumer of `state.inv` synchronizes on it. A
        low-precision matrix additionally CERTIFIES the fresh inverse (one
        probe, polish only if the probe exceeds the bound) — that probe is
        the one synchronization lowp factorization pays."""
        if state.placement == "sharded":
            state.inv = spin_inverse_sharded(
                state.a, leaf_solver=state.leaf_solver, engine=state.engine)
        elif state.precision:
            state.inv = spin_inverse_dense(
                state.a, state.block_size, state.leaf_solver,
                engine=state.engine, precision=self._policy_of(state))
        else:
            state.inv = spin_inverse_dense(
                state.a, state.block_size, state.leaf_solver,
                engine=state.engine)
        state.drift.reset()
        state.smw_spent_s = 0.0
        if state.precision:
            self._certify(state)

    # -- low-precision certification -----------------------------------------

    def _policy_of(self, state: MatrixState) -> PrecisionPolicy | None:
        """The matrix's pinned PrecisionPolicy (None for exact serving)."""
        if not state.precision:
            return None
        return PrecisionPolicy.from_descriptor(state.precision)

    def _probe(self, state: MatrixState, policy: PrecisionPolicy) -> float:
        """Residual probe through the SAME low-precision GEMM the policy
        serves with — an f32 probe would under-report what requests see."""
        self._key, sub = jax.random.split(self._key)
        return estimate_inverse_residual(
            lambda p: apply_inverse(state.a, p), state.inv, sub, state.n,
            probes=max(1, self.drift_probes), precision=policy)

    def _certify(self, state: MatrixState) -> float:
        """Certify the low-precision maintained inverse: probe the served
        residual, and only while it exceeds the policy's bound fire
        Newton–Schulz polish (f32 sweeps, recast to the store dtype) up to
        the policy's give-up cap. The final probe value becomes the
        per-request reported residual (`drift.residual_est`)."""
        policy = self._policy_of(state)
        res = self._probe(state, policy)
        fired = False
        sweeps_run = 0
        while (res > state.serve_bound and policy.polish_sweeps > 0
               and sweeps_run < policy.max_polish_sweeps):
            fired = True
            k = min(policy.polish_sweeps,
                    policy.max_polish_sweeps - sweeps_run)
            state.inv = _ns_polish_dense(
                state.a, state.inv, k).astype(state.inv.dtype)
            sweeps_run += k
            res = self._probe(state, policy)
        if fired:
            state.polish_triggers += 1
            state.polish_sweeps += sweeps_run
            self.stats["polish_triggers"] += 1
            self.stats["polish_sweeps"] += sweeps_run
            self._metrics.count("polish_triggers")
            self._metrics.count("polish_sweeps", sweeps_run)
        state.drift.residual_est = res
        return res

    # -- residency (cost-aware LRU over resident matrices) -------------------

    def _reinvert_cost(self, state: MatrixState) -> float:
        """The eviction price: the planner's modeled fresh-inversion cost
        (`RefactorPolicy.reinversion_cost`). Policies without the method
        (duck-typed stand-ins) degrade to pure LRU."""
        pricer = getattr(self.policy, "reinversion_cost", None)
        if pricer is None:
            return 0.0
        return float(pricer(state.n, state.dtype, placement=state.placement))

    def _touch(self, state: MatrixState) -> None:
        """GreedyDual credit refresh: an access re-earns the matrix its
        re-inversion price on top of the current recency clock."""
        state.last_used = self.ticks
        state.credit = self._evict_clock + max(state.reinvert_cost_s, 1e-12)

    def _spill(self) -> str:
        if self._spill_dir is None:
            self._spill_dir = tempfile.mkdtemp(prefix="spin-spill-")
        return self._spill_dir

    def _hot_matrices(self) -> set[str]:
        """Matrices that must not be evicted right now: referenced by a
        live slot or a queued request, or with background work in flight."""
        hot = {r.matrix_id for r in self._live.values()}
        hot.update(r.matrix_id for r in self._queue)
        hot.update(mid for mid, st in self._matrices.items()
                   if st.background is not None)
        return hot

    def _evict_one(self, protect: set[str]) -> None:
        """Evict the resident matrix with the least GreedyDual credit
        (ties: least recently used), spilling its state to disk."""
        from repro.core.solver_ckpt import save_matrix_spill

        hot = self._hot_matrices() | protect
        candidates = [st for mid, st in self._matrices.items()
                      if mid not in hot]
        if not candidates:
            raise ResidencyBusy(
                "cannot evict: every resident matrix is busy (live slot, "
                "queued request, or background work); raise max_resident")
        victim = min(candidates,
                     key=lambda st: (st.credit, st.last_used, st.matrix_id))
        meta, pair = self._matrix_payload(victim)
        save_matrix_spill(self._spill(), victim.matrix_id,
                          meta=meta, pair=pair)
        self._evicted[victim.matrix_id] = {"n": victim.n,
                                           "rank": victim.rank}
        del self._matrices[victim.matrix_id]
        self._evict_clock = victim.credit        # GreedyDual clock advance
        self.stats["evictions"] += 1
        self._metrics.count("evictions")

    def _make_room(self, protect: set[str]) -> None:
        """Ensure capacity for ONE more resident matrix."""
        if self.max_resident is None:
            return
        while len(self._matrices) >= self.max_resident:
            self._evict_one(protect)

    def _ensure_resident(self, matrix_id: str,
                         protect: set[str] = frozenset()) -> MatrixState:
        """Resident state for `matrix_id`, rehydrating from its spill if
        evicted (transparent to callers — an evicted matrix is still
        admitted, it just pays an I/O read on next touch)."""
        from repro.core.solver_ckpt import load_matrix_spill

        st = self._matrices.get(matrix_id)
        if st is not None:
            return st
        rec = self._evicted.get(matrix_id)
        if rec is None:
            raise KeyError(f"unknown matrix {matrix_id!r}")
        self._make_room(protect=set(protect) | {matrix_id})
        meta, pair = load_matrix_spill(self._spill(), matrix_id)
        st = self._state_from_meta(matrix_id, meta, pair)
        st.rank = rec["rank"]
        del self._evicted[matrix_id]
        self._matrices[matrix_id] = st
        self._touch(st)
        self.stats["rehydrations"] += 1
        self._metrics.count("rehydrations")
        return st

    def _dim_of(self, matrix_id: str) -> int:
        st = self._matrices.get(matrix_id)
        if st is not None:
            return st.n
        rec = self._evicted.get(matrix_id)
        if rec is not None:
            return rec["n"]
        raise KeyError(f"unknown matrix {matrix_id!r}")

    # -- request plumbing ----------------------------------------------------

    def submit(self, req) -> None:
        """Admission gate: validate, apply the shed-load policy, enqueue.

        Raises `KeyError` for an unknown matrix, `ValueError` for a
        malformed request (a bad rhs must fail HERE, never inside a
        coalesced batch in `tick()`), and `AdmissionRejected` — carrying
        a typed `Rejection` — when the bounded queue sheds this request.
        """
        n = self._dim_of(req.matrix_id)
        if isinstance(req, SolveRequest):
            rhs = req.rhs
            if (not hasattr(rhs, "ndim") or rhs.ndim not in (1, 2)
                    or rhs.shape[0] != n):
                raise ValueError(
                    f"rhs for matrix {req.matrix_id!r} must be (n={n},) or "
                    f"(n={n}, c), got shape "
                    f"{tuple(getattr(rhs, 'shape', ()))}")
        cfg = self.admission
        if cfg.per_matrix_quota is not None:
            queued = sum(1 for r in self._queue
                         if r.matrix_id == req.matrix_id)
            if queued >= cfg.per_matrix_quota:
                self._raise_rejected(req, "tenant_quota",
                                     f"matrix {req.matrix_id!r} already has "
                                     f"{queued} queued requests (quota "
                                     f"{cfg.per_matrix_quota})")
        if cfg.max_queue is not None and len(self._queue) >= cfg.max_queue:
            victim = shed_victim(self._queue, int(req.priority))
            if victim is None:
                self._raise_rejected(req, "queue_full",
                                     f"{len(self._queue)} queued (bound "
                                     f"{cfg.max_queue}) and no lower-"
                                     "priority request to shed")
            self._queue = deque(r for r in self._queue if r is not victim)
            self._mark_shed(victim, "shed",
                            f"evicted for priority-{req.priority} request "
                            f"{req.uid}")
        req.submit_t = self._clock()
        self._queue.append(req)

    def _raise_rejected(self, req, reason: str, detail: str):
        verdict = Rejection(reason, detail)
        req.rejected = True
        req.verdict = verdict
        req.done = True
        self.stats["rejected"] += 1
        self._metrics.observe_rejection(reason)
        raise AdmissionRejected(verdict)

    def _mark_shed(self, req, reason: str, detail: str) -> None:
        """Typed verdict for a request evicted AFTER admission (priority
        shed, deadline expiry) — its submitter already holds the object,
        so the verdict lands on the request, not in an exception."""
        req.rejected = True
        req.verdict = Rejection(reason, detail)
        req.done = True
        req.finish_t = self._clock()
        self.stats["shed"] += 1
        self._metrics.observe_rejection(reason)

    def _mark_failed(self, req, exc: BaseException) -> None:
        """Typed failure verdict on the request object (solve or update):
        the submitter sees done=True + failed=True + the error string —
        never a silent hang."""
        req.failed = True
        req.error = f"{type(exc).__name__}: {exc}"
        req.done = True
        req.finish_t = self._clock()
        self.stats["batch_failures"] += 1

    def solve(self, matrix_id: str, rhs: jax.Array, *, priority: int = 0,
              deadline_s: float | None = None) -> SolveRequest:
        req = SolveRequest(uid=next(self._uid), matrix_id=matrix_id,
                           rhs=jnp.asarray(rhs), priority=int(priority),
                           deadline_s=deadline_s)
        self.submit(req)
        return req

    def update(self, matrix_id: str, u: jax.Array | None = None,
               v: jax.Array | None = None, *,
               delta_row: jax.Array | None = None,
               index: int | None = None,
               priority: int = 0) -> UpdateRequest:
        if (u is None) == (delta_row is None):
            raise ValueError("pass exactly one of (u[, v]) or "
                             "(delta_row, index)")
        # Validate HERE, not at apply time: a malformed request must fail
        # at submission, never mid-_admit with the queue in hand.
        n = self._dim_of(matrix_id)
        if u is not None:
            uc = u.shape[1] if u.ndim == 2 else 1
            vv = u if v is None else v
            vc = vv.shape[1] if vv.ndim == 2 else 1
            if u.shape[0] != n or vv.shape[0] != n or uc != vc:
                raise ValueError(
                    f"update factors must be (n={n}, k) with equal "
                    f"k, got u{tuple(u.shape)} v{tuple(vv.shape)}")
        if delta_row is not None:
            if index is None:
                raise ValueError("delta_row updates require index=")
            bs = delta_row.shape[0]
            if delta_row.shape != (bs, n) or n % bs:
                raise ValueError(
                    f"delta_row must be (bs, n={n}) with bs | n, "
                    f"got {delta_row.shape}")
            if not 0 <= index < n // bs:
                raise ValueError(f"block index {index} out of range for "
                                 f"n={n}, bs={bs}")
        req = UpdateRequest(uid=next(self._uid), matrix_id=matrix_id,
                            u=u, v=v if v is not None else u,
                            delta_row=delta_row, index=index,
                            priority=int(priority))
        self.submit(req)
        return req

    # -- scheduling ----------------------------------------------------------

    def _live_matrices(self) -> set[str]:
        return {r.matrix_id for r in self._live.values()}

    def _expired(self, req) -> bool:
        dl = getattr(req, "deadline_s", None)
        return dl is not None and (self._clock() - req.submit_t) > dl

    def _admit(self) -> None:
        """One admission pass: highest effective priority first (per-matrix
        FIFO preserved — see `serving.admission.order_for_admission`).
        Updates execute inline the moment no earlier solve on their matrix
        is still live; a deferred request bars every later request on the
        same matrix (per-matrix order). Queued solves whose deadline has
        expired are shed with a typed verdict instead of admitted."""
        if len(self._queue) > 1:
            self._queue = order_for_admission(self._queue)
        deferred: deque = deque()
        barred: set[str] = set()
        live = self._live_matrices()
        try:
            while self._queue:
                req = self._queue.popleft()
                m = req.matrix_id
                if isinstance(req, UpdateRequest):
                    if m in barred or m in live:
                        deferred.append(req)
                        barred.add(m)
                    else:
                        try:
                            self._ensure_resident(m, protect=barred)
                        except ResidencyBusy:
                            # transient — every eviction candidate is hot
                            # right now; retry next tick (bar the matrix
                            # to keep per-matrix order)
                            deferred.append(req)
                            barred.add(m)
                            continue
                        except OSError as e:
                            # spill I/O failure — a typed verdict, never a
                            # dropped request with its submitter hanging
                            self._mark_failed(req, e)
                            self._metrics.count("rehydration_failures")
                            continue
                        self._apply_update(req)
                else:
                    if self._expired(req):
                        self._mark_shed(req, "deadline",
                                        f"deadline_s={req.deadline_s} "
                                        "expired while queued")
                        continue
                    if m in barred or not self._free:
                        deferred.append(req)
                        barred.add(m)
                    else:
                        try:
                            self._ensure_resident(m, protect=barred)
                        except ResidencyBusy:
                            # transient — nothing evictable this instant
                            # (all resident matrices hold live slots or
                            # background work); defer and retry next tick
                            deferred.append(req)
                            barred.add(m)
                            continue
                        except OSError as e:
                            # spill I/O genuinely failed — fail THIS
                            # request with the error; never lose it or
                            # its batchmates
                            self._mark_failed(req, e)
                            self._metrics.count("rehydration_failures")
                            continue
                        slot = self._free.popleft()
                        req.slot = slot
                        req.admit_t = self._clock()
                        self._live[slot] = req
                        live.add(m)
        finally:
            # An exception mid-pass (a failing update, an interrupt) must
            # not drop the requests already moved onto the local deque —
            # reattach them ahead of whatever is still queued.
            deferred.extend(self._queue)
            self._queue = deferred

    def tick(self) -> int:
        """Admit + advance: one coalesced solve per (matrix, rhs-dtype)
        group with live slots. EVERY call counts toward `ticks` — update-
        only and idle ticks included, so snapshot/restore never drifts
        from the true tick count. Returns the number of live slots after
        recycling (always 0 today — solves are single-shot — but the
        contract mirrors ServingEngine)."""
        if not _TRACER.enabled:
            return self._tick()
        with _TRACER.span("serve.tick", "serve_tick", tick=self.ticks + 1,
                          queued=len(self._queue),
                          live_slots=len(self._live)):
            return self._tick()

    def _tick(self) -> int:
        self.ticks += 1
        self._admit()
        self._metrics.observe_queue_depth(len(self._queue))
        if not self._live:
            return len(self._live)
        groups: dict[tuple[str, str], list[SolveRequest]] = defaultdict(list)
        for slot in sorted(self._live):
            req = self._live[slot]
            # dtype is part of the coalesce key: stacking a bf16 panel into
            # an f32 concatenate would silently upcast and change the f32
            # requests' bitwise answers (the coalesce-bitwise contract)
            groups[(req.matrix_id,
                    jnp.dtype(req.rhs.dtype).name)].append(req)
        for (matrix_id, _rhs_dtype), reqs in groups.items():
            state = self._matrices[matrix_id]
            self._touch(state)
            panels = [r.rhs if r.rhs.ndim == 2 else r.rhs[:, None]
                      for r in reqs]
            rhs = panels[0] if len(panels) == 1 else jnp.concatenate(
                panels, axis=1)
            try:
                x, path, residual = self._solve_batch(state, rhs)
            except Exception as e:
                # A failing batch must not leak its slots or hang its
                # co-batched requests: recycle everything, mark each
                # request failed with the error, keep serving.
                now = self._clock()
                for req in reqs:
                    req.failed = True
                    req.error = f"{type(e).__name__}: {e}"
                    req.done = True
                    req.finish_t = now
                    self._recycle(req)
                self.stats["batch_failures"] += 1
                self._metrics.count("batch_failures")
                # Post-mortem: the recent event window (worker timeline,
                # prior failures) is worth more than this one traceback.
                _flight.recorder().record(
                    "serve_event", name="batch.failed", tick=self.ticks,
                    matrix_id=matrix_id, cols=int(rhs.shape[1]),
                    requests=len(reqs), error=f"{type(e).__name__}: {e}")
                _flight.recorder().dump("batch-failure")
                continue
            col = 0
            now = self._clock()
            for req, panel in zip(reqs, panels):
                c = panel.shape[1]
                out = x[:, col:col + c]
                col += c
                req.x = out[:, 0] if req.rhs.ndim == 1 else out
                req.path = path
                req.residual_est = residual
                req.done = True
                req.finish_t = now
                self._recycle(req)
                self._metrics.observe_solve(req)
            self.stats["solves"] += len(reqs)
            self.stats["batches"] += 1
            self.stats["coalesced_cols"] += rhs.shape[1]
        return len(self._live)

    def _recycle(self, req: SolveRequest) -> None:
        """Return the request's slot to the free pool (idempotent)."""
        slot = req.slot
        if slot is not None and self._live.get(slot) is req:
            del self._live[slot]
            self._free.append(slot)

    def run_until_done(self, max_ticks: int = 10_000) -> None:
        for _ in range(max_ticks):
            if not self._queue and not self._live:
                return
            self.tick()
        raise RuntimeError("service did not drain")

    # -- observability -------------------------------------------------------

    def metrics(self) -> dict:
        """The SLA dashboard payload: rolling latency percentiles
        (queue-wait / solve / total), queue-depth distribution, per-path
        and per-rejection counters, residency and lifetime stats."""
        snap = self._metrics.snapshot()
        snap["queue"] = {"depth_now": len(self._queue),
                         "live_slots": len(self._live),
                         "free_slots": len(self._free),
                         "max_queue": self.admission.max_queue,
                         "per_matrix_quota": self.admission.per_matrix_quota}
        snap["residency"] = {"resident": len(self._matrices),
                             "evicted": len(self._evicted),
                             "max_resident": self.max_resident}
        snap["ticks"] = self.ticks
        snap["stats"] = dict(self.stats)
        # additive: the repro.obs registry view of the same service (plus
        # anything else in this process publishing there, e.g. coded runs)
        snap["registry"] = self._metrics.registry.to_json()
        return snap

    # -- execution -----------------------------------------------------------

    def _solve_batch(self, state: MatrixState, rhs: jax.Array
                     ) -> tuple[jax.Array, str, float | None]:
        """Serve one coalesced (n, c) panel for `state`.

        Zero pending churn → the planner-configured `spin_solve` entry
        point (bitwise-identical to the offline call on the same panel).
        Pending SMW churn → one panel GEMM against the maintained inverse.
        A hung or failed shard (deadline missed / retries exhausted) flips
        the matrix into degraded mode: the panel is answered from the
        sketched approximate inverse with its probe residual reported,
        and the matrix recovers when the background work lands.
        """
        if state.degraded:
            self._poll_background(state)
        if state.precision and not state.degraded:
            # Low-precision fast path: EVERY request (churned or not)
            # serves from the maintained store-dtype inverse through the
            # policy's compute/accumulate GEMM — one memory-bound panel
            # product, never the recursion. The certified probe residual
            # rides each request like degraded mode's sketch residual.
            self.stats["lowp_serves"] += 1
            return (apply_inverse(state.inv, rhs,
                                  precision=self._policy_of(state)),
                    "maintained", state.drift.residual_est)
        if state.pending_rank == 0 and not state.degraded:
            if self.solve_deadline_s is None and self.fault_plan is None:
                return self._exact_solve(state, rhs), "recursion", None
            task = start_background(self._guarded_solve(state, rhs))
            try:
                return task.wait(self.solve_deadline_s), "recursion", None
            except ShardTimeout:
                state.degraded = True
                state.background = task      # still running; lands later
                self.stats["shard_timeouts"] += 1
                _flight.recorder().record(
                    "serve_event", name="degraded.entered", tick=self.ticks,
                    matrix_id=state.matrix_id, cause="shard_timeout",
                    deadline_s=self.solve_deadline_s)
                _flight.recorder().dump("degraded-shard-timeout")
            except WorkerFailure:
                state.degraded = True
                state.background = None      # dead, nothing to wait on
                self.stats["shard_failures"] += 1
                _flight.recorder().record(
                    "serve_event", name="degraded.entered", tick=self.ticks,
                    matrix_id=state.matrix_id, cause="worker_failure")
                _flight.recorder().dump("degraded-worker-failure")
        if state.degraded:
            sketch = self._ensure_sketch(state)
            state.degraded_serves += 1
            self.stats["degraded_serves"] += 1
            return (apply_inverse(sketch.inverse, rhs), "degraded",
                    sketch.residual_est)
        return apply_inverse(state.inv, rhs), "maintained", None

    def _exact_solve(self, state: MatrixState, rhs: jax.Array) -> jax.Array:
        if state.placement == "sharded":
            return spin_solve_sharded(state.a, rhs,
                                      leaf_solver=state.leaf_solver,
                                      engine=state.engine)
        return spin_solve_dense(state.a, rhs, state.block_size,
                                state.leaf_solver, engine=state.engine)

    def _guarded_solve(self, state: MatrixState, rhs: jax.Array):
        """The exact solve wrapped for background execution: fault-plan
        injection per attempt (rank = the matrix's admission index), retry
        with exponential backoff on WorkerFailure, and synchronization
        inside the worker so the deadline sees real compute time."""
        def attempt(i: int) -> jax.Array:
            if self.fault_plan is not None:
                self.fault_plan.apply(state.rank, step=i)
            return jax.block_until_ready(self._exact_solve(state, rhs))

        def run() -> jax.Array:
            x, used = retry_with_backoff(attempt,
                                         retries=self.solve_retries,
                                         base_s=self.backoff_base_s)
            if used > 1:
                self.stats["retries"] += used - 1
            return x

        return run

    def _ensure_sketch(self, state: MatrixState):
        """Lazily build the degraded-mode sketched inverse of the CURRENT
        matrix (updates invalidate it), polished until the probe residual
        is within the DriftTracker tolerance — i.e. drift_scale × the
        dtype residual tolerance, the service's advertised degraded bound."""
        if state.sketch is None:
            a = state.a
            if state.placement == "sharded":
                a = a.to_blockmatrix().to_dense()
            self._key, sub = jax.random.split(self._key)
            state.sketch = sketched_approx_inverse(
                a, sub, block_size=state.block_size,
                tol=state.drift.tolerance,
                max_sweeps=self.degraded_max_sweeps,
                probes=max(1, self.drift_probes))
        return state.sketch

    def _poll_background(self, state: MatrixState) -> None:
        """Exit degraded mode once the hung shard's background work lands:
        the recovered shard re-factorizes (async dispatch, like any
        refactor) and subsequent solves take the exact path again. A
        background task that DIED keeps the matrix degraded."""
        task = state.background
        if task is None or not task.done:
            return
        state.background = None
        if task.error is not None:
            self.stats["shard_failures"] += 1
            return                           # still degraded, still serving
        state.degraded = False
        state.sketch = None
        self._factorize(state)
        state.refactors += 1
        self.stats["recoveries"] += 1
        # record-only: a recovery is good news, no dump needed
        _flight.recorder().record(
            "serve_event", name="degraded.recovered", tick=self.ticks,
            matrix_id=state.matrix_id, degraded_serves=state.degraded_serves)

    def _apply_update(self, req: UpdateRequest) -> None:
        state = self._matrices[req.matrix_id]
        self._touch(state)
        if req.delta_row is not None:
            u, v = block_update_factors(req.delta_row, req.index, state.n)
        else:
            u = req.u if req.u.ndim == 2 else req.u[:, None]
            v = req.v if req.v.ndim == 2 else req.v[:, None]
        k = u.shape[1]
        decision = self.policy.decide(
            state.n, state.dtype, new_rank=k,
            pending_rank=state.pending_rank,
            cumulative_s=state.smw_spent_s,
            residual_est=state.drift.residual_est,
            drift_tolerance=state.drift.tolerance,
            placement=state.placement)
        state.a = add_low_rank(state.a, u, v)
        state.sketch = None          # the degraded sketch tracks CURRENT A
        if decision.refactor:
            self._factorize(state)               # background: async dispatch
            state.refactors += 1
            self.stats["updates_refactor"] += 1
        else:
            state.inv = smw_update_inverse(state.inv, u, v)
            state.drift.note(k)
            state.smw_spent_s = decision.cumulative_s
            state.smw_applied += 1
            self.stats["updates_smw"] += 1
            if state.precision:
                # the low-precision certify IS the drift probe, plus the
                # polish-on-exceed repair the exact path never needs
                self._certify(state)
            elif self.drift_probes:
                self._key, sub = jax.random.split(self._key)
                state.drift.residual_est = estimate_inverse_residual(
                    lambda p: apply_inverse(state.a, p), state.inv, sub,
                    state.n, probes=self.drift_probes)
        req.done = True
        req.finish_t = self._clock()
        req.refactored = decision.refactor
        req.reason = decision.reason

    # -- snapshot / restore --------------------------------------------------

    def _matrix_payload(self, st: MatrixState
                        ) -> tuple[dict, dict[str, BlockMatrix]]:
        """One matrix's snapshot entry: (meta dict, {"a","inv"} pair)."""
        meta = {
            "placement": st.placement, "block_size": st.block_size,
            "leaf_solver": st.leaf_solver, "engine": st.engine,
            "plan": st.plan.to_dict(), "n": st.n,
            "dtype": jnp.dtype(st.dtype).name,
            "drift": {"tolerance": st.drift.tolerance,
                      "update_rank": st.drift.update_rank,
                      "updates": st.drift.updates,
                      "residual_est": st.drift.residual_est},
            "smw_spent_s": st.smw_spent_s,
            "smw_applied": st.smw_applied, "refactors": st.refactors,
            "precision": st.precision, "store_dtype": st.store_dtype,
            "serve_bound": st.serve_bound,
            "polish_triggers": st.polish_triggers,
            "polish_sweeps": st.polish_sweeps,
        }
        if st.placement == "sharded":
            pair = {"a": st.a.to_blockmatrix(),
                    "inv": st.inv.to_blockmatrix()}
        else:
            pair = {"a": BlockMatrix.from_dense(st.a, st.block_size),
                    "inv": BlockMatrix.from_dense(st.inv, st.block_size)}
        return meta, pair

    def _state_from_meta(self, mid: str, m: dict,
                         pair: dict[str, BlockMatrix]) -> MatrixState:
        """Inverse of `_matrix_payload` (shared by restore + rehydrate)."""
        from repro.parallel.sharded_blockmatrix import ShardedBlockMatrix
        from repro.planner.plan import Plan

        if m["placement"] == "sharded":
            a = ShardedBlockMatrix.from_blockmatrix(pair["a"])
            inv = ShardedBlockMatrix.from_blockmatrix(pair["inv"])
        else:
            a, inv = pair["a"].to_dense(), pair["inv"].to_dense()
        st = MatrixState(
            matrix_id=mid, a=a, inv=inv, placement=m["placement"],
            block_size=m["block_size"], leaf_solver=m["leaf_solver"],
            engine=m["engine"], plan=Plan.from_dict(m["plan"]),
            drift=DriftTracker(**m["drift"]), n=m["n"],
            dtype=jnp.dtype(m["dtype"]),
            smw_spent_s=m["smw_spent_s"],
            smw_applied=m["smw_applied"], refactors=m["refactors"])
        # .get(): pre-precision snapshots restore as exact-serving states
        st.precision = m.get("precision", "")
        st.store_dtype = m.get("store_dtype", "")
        st.serve_bound = m.get("serve_bound", 0.0)
        st.polish_triggers = m.get("polish_triggers", 0)
        st.polish_sweeps = m.get("polish_sweeps", 0)
        st.reinvert_cost_s = self._reinvert_cost(st)
        return st

    def _snapshot_payload(self) -> tuple[dict, dict]:
        """Quiesce-checked, immutable snapshot payload (meta + matrices —
        resident ones by reference, evicted ones read from their spills).
        JAX arrays are immutable, so holding references IS a consistent
        copy: updates applied after this call rebind `state.a`/`state.inv`
        without mutating the captured arrays."""
        from repro.core.solver_ckpt import load_matrix_spill

        if self._queue or self._live:
            raise RuntimeError(
                "snapshot requires a quiesced service (drain with "
                "run_until_done() first); "
                f"{len(self._queue)} queued / {len(self._live)} live")
        pending = [mid for mid, st in self._matrices.items()
                   if st.background is not None]
        if pending:
            raise RuntimeError(
                "snapshot requires landed background work; hung-shard "
                f"tasks still pending on {pending}")
        meta = {"slots": self.slots, "ticks": self.ticks,
                "drift_probes": self.drift_probes,
                "drift_scale": self.drift_scale,
                "stats": dict(self.stats),
                # the straggler-guard config MUST survive a restart — a
                # restored service silently losing its deadline protection
                # is an outage waiting for a straggler
                "guard": {
                    "solve_deadline_s": self.solve_deadline_s,
                    "solve_retries": self.solve_retries,
                    "backoff_base_s": self.backoff_base_s,
                    "degraded_max_sweeps": self.degraded_max_sweeps,
                    "fault_plan": (None if self.fault_plan is None
                                   else self.fault_plan.to_json()),
                },
                "admission": {
                    "max_queue": self.admission.max_queue,
                    "per_matrix_quota": self.admission.per_matrix_quota,
                },
                # service-default precision (per-matrix policies live in
                # each matrix entry; this only seeds future add_matrix)
                "precision": ("" if self.precision is None else
                              resolve_precision(self.precision).descriptor()),
                "residency": {"max_resident": self.max_resident},
                "matrices": {}}
        matrices: dict[str, dict[str, BlockMatrix]] = {}
        for mid, st in self._matrices.items():
            meta["matrices"][mid], matrices[mid] = self._matrix_payload(st)
        for mid in self._evicted:
            m, pair = load_matrix_spill(self._spill(), mid)
            meta["matrices"][mid], matrices[mid] = m, pair
        return meta, matrices

    def snapshot(self, directory: str) -> None:
        """Persist every matrix's serving state (quiesce first: pending
        queue entries and live slots are NOT snapshotted)."""
        from repro.core.solver_ckpt import save_service_snapshot

        meta, matrices = self._snapshot_payload()
        save_service_snapshot(directory, meta=meta, matrices=matrices)

    def snapshot_async(self, directory: str):
        """`snapshot()` without stalling the tick loop: the quiesced copy
        is captured NOW (cheap — immutable array references), then the
        device→host transfer and file I/O run on a background thread.
        Returns the `BackgroundTask`; `task.wait()` for durability, and
        serving may continue immediately — later updates/evictions cannot
        leak into the captured payload. One snapshot in flight at a time."""
        from repro.core import solver_ckpt

        if self._snapshot_task is not None and not self._snapshot_task.done:
            raise RuntimeError("a snapshot is already in flight; wait() on "
                               "it before starting another")
        meta, matrices = self._snapshot_payload()
        task = start_background(
            lambda: solver_ckpt.save_service_snapshot(
                directory, meta=meta, matrices=matrices))
        self._snapshot_task = task
        return task

    @classmethod
    def restore(cls, directory: str, *, policy=None, seed: int = 0,
                **overrides) -> "SpinService":
        """Rebuild a service from `snapshot()` output. The maintained
        inverse is reloaded, NOT recomputed — a restart costs I/O, never a
        re-factorization — and resumed serving is bit-identical. The
        straggler-guard (solve_deadline_s, fault_plan, solve_retries,
        backoff_base_s, degraded_max_sweeps) and admission/residency
        config are rehydrated from the snapshot; `**overrides` is the
        explicit ops path to change any constructor knob on the way back
        up (e.g. ``restore(d, solve_deadline_s=0.5)``)."""
        from repro.core.solver_ckpt import load_service_snapshot

        meta, matrices = load_service_snapshot(directory)
        guard = dict(meta.get("guard", {}))
        fault_plan = guard.pop("fault_plan", None)
        if fault_plan is not None:
            guard["fault_plan"] = FaultPlan.from_json(fault_plan)
        kwargs = {**guard, **meta.get("admission", {}),
                  **meta.get("residency", {})}
        if meta.get("precision"):
            kwargs["precision"] = meta["precision"]
        kwargs.update(overrides)
        svc = cls(slots=meta["slots"], policy=policy,
                  drift_probes=meta["drift_probes"],
                  drift_scale=meta["drift_scale"], seed=seed, **kwargs)
        svc.stats.update(meta.get("stats", {}))
        svc.ticks = meta.get("ticks", 0)
        for mid, m in meta["matrices"].items():
            st = svc._state_from_meta(mid, m, matrices[mid])
            st.rank = len(svc._matrices)
            svc._matrices[mid] = st
            svc._touch(st)
        # a restored set larger than max_resident spills back down
        if svc.max_resident is not None:
            while len(svc._matrices) > svc.max_resident:
                svc._evict_one(protect=set())
        return svc
