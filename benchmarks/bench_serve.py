"""Online inverse service benchmark: request throughput, SLA latency
percentiles, shed-load behavior, and the update-vs-refactor crossover
(DESIGN.md §9).

Measurements on a `serving.SpinService` (each wrapped in a profile-
decorated phase — `serving.metrics.PhaseLedger`, with
`jax.profiler.TraceAnnotation` so phases show up named in a captured
profile):

  * ``first_request`` — wall seconds from process-cold service creation to
    the first answered request (trace + compile + factorize + solve). The
    service keeps a persistent XLA compilation cache
    (``$JAX_COMPILATION_CACHE_DIR`` or `<checkout>/.jax_cache`), so a
    SECOND process run of this benchmark must show this number collapse
    to ~zero retrace — that delta IS the warm-restart story, and CI runs
    the benchmark twice to assert it;
  * ``solve_recursion`` — requests/sec of the exact coalesced-`spin_solve`
    path (zero pending churn), `slots` requests per tick;
  * ``solve_maintained`` — requests/sec once SMW churn has switched solves
    to the O(n²·c) maintained-inverse GEMM path;
  * ``precision`` — the same maintained-path serve with the inverse stored
    in bf16 behind `precision="bf16"` (DESIGN.md §12): f32-vs-bf16 req/s,
    the speedup against the recorded 1.5x floor (2.0x TPU target) as a
    WARN-only throughput gate, and the certified residual as a HARD gate —
    a bf16 row that serves outside its certified bound fails the benchmark;
  * ``latency`` — the service's own rolling p50/p95/p99 for the
    queue-wait / solve / total split plus the per-tick queue-depth
    distribution (`SpinService.metrics()`), reported as a point row;
  * ``saturation`` — a bounded-queue service driven past its admission
    capacity: every outcome is a typed verdict (served, shed, or
    `AdmissionRejected`) and the row records the split — the explicit
    shed-load contract, measured;
  * ``crossover`` — the refactor policy's modeled crossover rank for a
    steady rank-k update stream, AND the rank the live service actually
    refactored at (they agree by construction — the service asks the same
    policy — so the sweep documents the deployed decision boundary).

Standalone usage (the shared `--reduced --json` convention of common.py):

    PYTHONPATH=src python -m benchmarks.bench_serve --reduced \
        --json BENCH_serve.json
"""

from __future__ import annotations

import time

from .common import bench_arg_parser, csv_row, emit_header, write_json_report

N = 1024
REQUESTS = 64
SLOTS = 8
UPDATE_RANK = 8

REDUCED_N = 256
REDUCED_REQUESTS = 16


def _drain_requests(svc, matrix_id: str, panels) -> float:
    """Submit every panel, drain, block on the last answer; wall seconds."""
    import jax

    t0 = time.perf_counter()
    reqs = [svc.solve(matrix_id, p) for p in panels]
    svc.run_until_done()
    jax.block_until_ready(reqs[-1].x)
    return time.perf_counter() - t0


def run(emit, *, n: int = N, requests: int = REQUESTS, slots: int = SLOTS,
        update_rank: int = UPDATE_RANK,
        json_path: str | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import testing
    from repro.obs.registry import default_registry
    from repro.obs.trace import tracing
    from repro.planner import RefactorPolicy
    from repro.serving import AdmissionRejected, PhaseLedger, SpinService

    ledger = PhaseLedger()
    a = testing.make_spd(n, jax.random.PRNGKey(n))
    panels = [jax.random.normal(jax.random.PRNGKey(1000 + i), (n,))
              for i in range(requests)]
    points = []

    # -- cold start → first answer (the number a warm compile cache cuts) ---
    with ledger.profile("first_request"):
        svc = SpinService(slots=slots)       # persistent compile cache on
        st = svc.add_matrix("bench", a)
        first = svc.solve("bench", panels[0])
        svc.run_until_done()
        jax.block_until_ready(first.x)
    first_request_s = ledger.seconds["first_request"]
    emit(csv_row(f"serve/first_request/n{n}", first_request_s,
                 f"compile_cache={'on' if svc.compile_cache_dir else 'off'}"))

    # -- exact recursion path (fresh matrix), warm then measure -------------
    with ledger.profile("solve_recursion"):
        _drain_requests(svc, "bench", panels[:slots])  # compile + warm
        dt = _drain_requests(svc, "bench", panels)
    points.append({"id": f"serve/solve_recursion/n{n}", "n": n,
                   "requests": requests, "slots": slots, "seconds": dt,
                   "req_per_s": requests / dt})
    emit(csv_row(f"serve/solve_recursion/n{n}", dt / requests,
                 f"req_per_s={requests / dt:.1f}"))

    # -- maintained-inverse path (after one folded update) ------------------
    u = jax.random.normal(jax.random.PRNGKey(7), (n, update_rank)) / n ** 0.5
    up = svc.update("bench", u)
    svc.run_until_done()
    assert not up.refactored, "benchmark update unexpectedly refactored"
    with ledger.profile("solve_maintained"):
        _drain_requests(svc, "bench", panels[:slots])  # compile + warm
        dt = _drain_requests(svc, "bench", panels)
    points.append({"id": f"serve/solve_maintained/n{n}", "n": n,
                   "requests": requests, "slots": slots, "seconds": dt,
                   "req_per_s": requests / dt})
    emit(csv_row(f"serve/solve_maintained/n{n}", dt / requests,
                 f"req_per_s={requests / dt:.1f}"))
    f32_rps = requests / dt

    # -- tracing overhead: the same maintained drain under $SPIN_TRACE ------
    # Off-is-free is proven structurally (tests/test_obs_overhead.py checks
    # jaxpr equality), so the off point IS the row above; this row measures
    # the ON cost end-to-end so a regression in the host-side span path
    # shows up as a throughput delta. WARN-only: tracing is a debugging
    # mode, not a serving SLA.
    with ledger.profile("solve_traced"):
        with tracing(True, clear=True):
            dt_traced = _drain_requests(svc, "bench", panels)
    traced_rps = requests / dt_traced
    note = (f"req_per_s={traced_rps:.1f};untraced={f32_rps:.1f}"
            if traced_rps >= 0.8 * f32_rps else
            f"WARN req_per_s={traced_rps:.1f} < 80% of "
            f"untraced {f32_rps:.1f}")
    emit(csv_row(f"serve/tracing_overhead/n{n}", dt_traced / requests, note))
    points.append({"id": f"serve/tracing_overhead/n{n}", "n": n,
                   "requests": requests,
                   "untraced_req_per_s": f32_rps,
                   "traced_req_per_s": traced_rps,
                   "overhead_gate": "warn"})

    # -- low-precision fast path: bf16 store, identical churn ---------------
    # Same matrix, same folded update, same panels — the only axis that
    # moves is the storage dtype, so req/s deltas are the HBM-bytes story.
    with ledger.profile("solve_bf16"):
        lp = SpinService(slots=slots)
        lp_state = lp.add_matrix("bench", a, precision="bf16")
        lp.update("bench", u)
        lp.run_until_done()
        _drain_requests(lp, "bench", panels[:slots])  # compile + warm
        dt_bf16 = _drain_requests(lp, "bench", panels)
    bf16_rps = requests / dt_bf16
    speedup = bf16_rps / f32_rps
    # Throughput is WARN-only: the 1.5x floor (2.0x on TPU, where bf16 is a
    # hardware dtype) is the recorded target, but CPU emulated-bf16 GEMMs
    # legitimately miss it. The residual gate below is the hard one.
    target, target_tpu = 1.5, 2.0
    floor = target_tpu if jax.default_backend() == "tpu" else target
    gate_note = (f"speedup={speedup:.2f}x" if speedup >= floor
                 else f"WARN speedup={speedup:.2f}x < {floor:.1f}x target")
    emit(csv_row(f"serve/solve_bf16/n{n}", dt_bf16 / requests,
                 f"req_per_s={bf16_rps:.1f};{gate_note}"))
    # Residual is the HARD gate: a bf16 serve outside its certified bound
    # is an accuracy regression, not a perf footnote.
    residual = float(lp_state.drift.residual_est)
    bound = float(lp_state.serve_bound)
    assert residual <= bound, (
        f"bf16 serve residual {residual:.3e} exceeds certified bound "
        f"{bound:.3e} (polish_triggers={lp_state.polish_triggers})")
    emit(csv_row(f"serve/residual_bf16/n{n}", 0,
                 f"residual={residual:.2e};bound={bound:.1e};"
                 f"polish_triggers={lp_state.polish_triggers}"))
    points.append({"id": f"serve/precision/n{n}", "n": n,
                   "requests": requests, "slots": slots,
                   "f32_req_per_s": f32_rps, "bf16_req_per_s": bf16_rps,
                   "speedup": speedup,
                   "target": target, "target_tpu": target_tpu,
                   "throughput_gate": "warn",
                   "residual": residual, "bound": bound,
                   "residual_gate": "hard",
                   "polish_triggers": lp_state.polish_triggers,
                   "polish_sweeps": lp_state.polish_sweeps,
                   "lowp_serves": lp.stats["lowp_serves"],
                   "residual_summary": lp.metrics()["residual"]})

    # -- SLA latency percentiles (the service's own rolling reservoirs) -----
    metrics = svc.metrics()
    lat = metrics["latency_s"]
    points.append({"id": f"serve/latency/n{n}", "n": n,
                   "queue_wait_s": lat["queue_wait"],
                   "solve_s": lat["solve"], "total_s": lat["total"],
                   "queue_depth": metrics["queue_depth"]})
    emit(csv_row(f"serve/latency/n{n}", lat["total"]["p50"],
                 f"p95={lat['total']['p95']:.2e};"
                 f"p99={lat['total']['p99']:.2e};"
                 f"queue_p95={metrics['queue_depth']['p95']:.1f}"))

    # -- saturation: drive a bounded queue past capacity --------------------
    with ledger.profile("saturation"):
        sat = SpinService(slots=max(slots // 4, 1),
                          max_queue=max(requests // 4, 2))
        sat.add_matrix("bench", a)
        served_reqs, rejected = [], 0
        for i, p in enumerate(panels):
            try:
                served_reqs.append(sat.solve("bench", p,
                                             priority=i % 3))
            except AdmissionRejected as e:
                assert e.rejection.reason in ("queue_full", "tenant_quota")
                rejected += 1
        sat.run_until_done()
    shed = sum(1 for r in served_reqs if r.rejected)
    served = sum(1 for r in served_reqs if r.done and not r.rejected)
    assert served + shed + rejected == requests      # typed, never lost
    sat_m = sat.metrics()
    points.append({"id": f"serve/saturation/n{n}", "n": n,
                   "offered": requests, "served": served, "shed": shed,
                   "rejected": rejected,
                   "max_queue": sat.admission.max_queue,
                   "queue_depth": sat_m["queue_depth"],
                   "counters": sat_m["counters"]})
    emit(csv_row(f"serve/saturation/n{n}", 0,
                 f"served={served};shed={shed};rejected={rejected}"))

    # -- update-vs-refactor crossover sweep ---------------------------------
    policy = RefactorPolicy()
    modeled = policy.crossover_rank(n, jnp.float32, step_rank=update_rank)
    svc2 = SpinService(slots=slots, policy=policy, drift_probes=0)
    st2 = svc2.add_matrix("sweep", a)
    observed = None
    with ledger.profile("crossover_sweep"):
        for i in range(4 * max(modeled // update_rank, 1)):
            upd = svc2.update(
                "sweep", jax.random.normal(jax.random.PRNGKey(2000 + i),
                                           (n, update_rank)) / n ** 0.5)
            svc2.run_until_done()
            if upd.refactored:
                observed = (i + 1) * update_rank
                break
    points.append({"id": f"serve/crossover/n{n}/k{update_rank}", "n": n,
                   "update_rank": update_rank,
                   "modeled_crossover_rank": modeled,
                   "observed_crossover_rank": observed,
                   "smw_applied": st2.smw_applied,
                   "refactors": st2.refactors})
    emit(csv_row(f"serve/crossover/n{n}/k{update_rank}", 0,
                 f"modeled_rank={modeled};observed_rank={observed}"))

    report = {"benchmark": "serve", "backend": jax.default_backend(),
              "n": n, "slots": slots,
              "plan": {"block_size": st.block_size,
                       "leaf_solver": st.leaf_solver, "engine": st.engine},
              "compile_cache": {"dir": svc.compile_cache_dir,
                                "first_request_s": first_request_s},
              "phases": ledger.to_dict(),
              "metrics": metrics,
              "registry": default_registry().to_json(),
              "points": points}
    write_json_report(report, json_path, emit, "serve")
    return report


def main() -> None:
    args = bench_arg_parser(__doc__).parse_args()
    emit_header()
    if args.reduced:
        run(print, n=REDUCED_N, requests=REDUCED_REQUESTS,
            json_path=args.json)
    else:
        run(print, json_path=args.json)


if __name__ == "__main__":
    main()
