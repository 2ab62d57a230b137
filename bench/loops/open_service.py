"""Open loop: a YCSB-style stream of solves and updates against
`SpinService`.

Requests fall due on the schedule `data.open_loop_schedule` makes from the
mix and the seed, and are submitted when due; the service ticks while it
has work. A solve's latency runs from when it was due to when its answer
array is ready (polled with `is_ready`). Requests still queued at the close
are drained and counted. A quarter (the mix's `check_share`) of the solves,
drawn from the seed, are judged after the window against the matrix as it
stood when each was answered, rebuilt from the seed and the updates sent.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from collections import defaultdict

import numpy as np

from bench import data, reference
from bench.common import Check, Outcome, Window, peak_bytes, span


@dataclasses.dataclass
class Sent:
    """One scheduled request as the client saw it."""

    plan: data.Request
    due_ns: int
    version: int                     # tenant's updates sent before it
    req: object = None
    submit_ns: int = 0
    ready_ns: int = 0


def pools(n: int, mix: dict, seed: int):
    """The mix's right-hand sides and update factors, as lists of arrays
    (split in one program: indexing a stacked pool would compile per item)."""
    import jax

    split = jax.jit(tuple)
    panels = split(data.panel_pool(n, int(mix["panel_pool"]),
                                   int(mix["solve_cols"]), seed))
    factors = split(data.factor_pool(n, int(mix["factor_pool"]),
                                     int(mix["update_rank"]), seed))
    return jax.block_until_ready((list(panels), list(factors)))


def build_service(config: dict, mix: dict, seed: int):
    """The service with every tenant admitted, and the request pools."""
    from repro.serving import SpinService

    n = int(config["n"])
    svc = SpinService(**config["service"])
    with span("bench.generate"):
        for t in range(int(mix["tenants"])):
            a = data.spd_matrix(n, seed, t)
            svc.add_matrix(f"t{t}", a, block_size=int(config["block_size"]),
                           leaf_solver=config["leaf_solver"],
                           engine=config["engine"])
            del a
        panels, factors = pools(n, mix, seed)
    return svc, panels, factors


def plan_versions(schedule, tenants: int):
    """Each request's matrix version (its tenant's updates sent before it,
    the warm-up's included) and each tenant's update history in order.
    The warm-up sends tenant t the factor-pool item t."""
    history = {t: [t] for t in range(tenants)}
    versions = []
    for p in schedule:
        versions.append(len(history[p.tenant]))
        if p.op == "update":
            history[p.tenant].append(p.item)
    return versions, history


def warm_service(svc, slots: int, tenants: int, panels, factors) -> None:
    """Run every program the window can reach, on tenant t0: each coalesced
    width on the recursion path, a Woodbury update with its drift probe,
    and each width on the maintained path. Every tenant gets one update
    (factor item t), so the window opens in the steady state, with no
    tenant left at zero churn from its first factorization."""
    import jax

    with span("bench.warmup"):
        for path in ("recursion", "maintained"):
            if path == "maintained":
                for t in range(tenants):
                    svc.update(f"t{t}", factors[t])
                svc.tick()
            for k in range(1, slots + 1):
                reqs = [svc.solve("t0", panels[i]) for i in range(k)]
                svc.tick()
                jax.block_until_ready([r.x for r in reqs])
                got = {r.path for r in reqs}
                if got != {path}:
                    raise RuntimeError(f"warm-up expected path {path!r}, "
                                       f"the service took {sorted(got)}")


def drive(svc, sent: list[Sent], panels, factors, window: Window,
          seconds: float) -> list[int]:
    """Submit each request when due, tick while the service has work, poll
    answers for readiness, then drain what is left after the close. Fills
    each `Sent`'s timestamps; returns the widths of the maintained batches."""
    nxt = 0                      # next request to submit
    open_reqs: list[Sent] = []   # submitted, not yet done
    answered: list[Sent] = []    # done, answer not yet ready
    batches: list[int] = []
    while nxt < len(sent) or open_reqs or answered:
        if not window.t_end and nxt == len(sent) and (
                window.elapsed() >= seconds):
            window.stop_trace()
            window.close()
        window.poll()
        now = time.perf_counter_ns()
        if nxt < len(sent) and sent[nxt].due_ns <= now:
            with span("bench.submit"):
                while nxt < len(sent) and sent[nxt].due_ns <= now:
                    s = sent[nxt]
                    s.submit_ns = time.perf_counter_ns()
                    tid = f"t{s.plan.tenant}"
                    if s.plan.op == "solve":
                        s.req = svc.solve(tid, panels[s.plan.item])
                    else:
                        s.req = svc.update(tid, factors[s.plan.item])
                    open_reqs.append(s)
                    nxt += 1
        if open_reqs:
            with span("bench.tick"):
                svc.tick()
            widths: dict[int, int] = defaultdict(int)
            for s in open_reqs:
                if not s.req.done:
                    continue
                if s.plan.op == "update" or s.req.x is None:
                    s.ready_ns = time.perf_counter_ns()
                    continue
                answered.append(s)
                if s.req.path == "maintained":
                    widths[s.plan.tenant] += s.req.x.shape[1]
            batches += list(widths.values())
            open_reqs = [s for s in open_reqs if not s.req.done]
        if answered:
            with span("bench.poll"):
                for s in answered:
                    if s.req.x.is_ready():
                        s.ready_ns = time.perf_counter_ns()
                answered = [s for s in answered if not s.ready_ns]
        if not open_reqs:
            if answered:
                with span("bench.wait"):
                    time.sleep(0.0001)
            elif nxt < len(sent):
                wait = (sent[nxt].due_ns - time.perf_counter_ns()) / 1e9
                if wait > 0:
                    with span("bench.idle"):
                        time.sleep(min(wait, 0.0005))
    if not window.t_end:
        window.stop_trace()
        window.close()
    return batches


def latencies_ms(sent: list[Sent]) -> np.ndarray:
    """Each solve's time from due to answer ready."""
    return np.array([(s.ready_ns - s.due_ns) / 1e6 for s in sent
                     if s.plan.op == "solve"])


def run(config: dict, mix: dict, *, seed: int, seconds: float, trace_dir,
        devices, t_start: float, limits: dict) -> Outcome:
    svc, panels, factors = build_service(config, mix, seed)
    tenants = int(mix["tenants"])
    warm_service(svc, int(config["service"]["slots"]), tenants, panels,
                 factors)
    schedule = data.open_loop_schedule(mix, seconds, seed)
    versions, history = plan_versions(schedule, tenants)
    window = Window(seconds, trace_dir, float(mix["trace_seconds"]))
    before = dict(svc.stats)

    t0 = window.open()
    t0_ns = int(t0 * 1e9)
    sent = [Sent(plan=p, due_ns=t0_ns + int(p.due * 1e9), version=v)
            for p, v in zip(schedule, versions)]
    batches = drive(svc, sent, panels, factors, window, seconds)
    drained_s = (time.perf_counter_ns() - t0_ns) / 1e9 - seconds
    peak = peak_bytes(devices)

    bad = [s for s in sent if s.req.failed or s.req.rejected
           or getattr(s.req, "path", None) == "degraded"]
    lat_ms = latencies_ms(sent)
    late_ms = np.array([(s.submit_ns - s.due_ns) / 1e6 for s in sent])
    stats = {k: svc.stats[k] - before.get(k, 0) for k in svc.stats}
    paths: dict[str, int] = defaultdict(int)
    for s in sent:
        if s.plan.op == "solve":
            paths[s.req.path] += 1
    kept = [(s.plan.tenant, s.version, s.plan.item, s.req.x)
            for s in sent if s.plan.check and s.req.x is not None]
    work_ns = [(s.submit_ns, s.ready_ns) for s in sent]
    del svc, sent
    gc.collect()

    n = int(config["n"])
    residuals = reference.replay_residuals(
        n, lambda t: data.spd_matrix(n, seed, t), history, kept, panels,
        factors)
    n_check = sum(p.check for p in schedule)
    limit = float(limits["solve_residual_max"])
    checks = [
        Check("answers_checked", float(len(residuals)), "==",
              float(n_check), len(residuals) == n_check and n_check > 0),
        Check("requests_failed", float(len(bad)), "==", 0.0, not bad),
        Check("solve_residual_max", max(residuals, default=None), "<=",
              limit, bool(residuals) and max(residuals) <= limit)]
    return Outcome(
        metrics={"setup_s": t0 - t_start,
                 "solve_p50_ms": float(np.percentile(lat_ms, 50)),
                 "solve_p99_ms": float(np.percentile(lat_ms, 99))},
        attempted=len(schedule), failed=len(bad), checks=checks,
        counters={"solves": len(lat_ms), "paths": dict(paths),
                  "refactors": stats["updates_refactor"],
                  "smw_updates": stats["updates_smw"],
                  "maintained_batches": batches,
                  "late_p99_ms": float(np.percentile(late_ms, 99)),
                  "drained_s": drained_s, "work_ns": work_ns,
                  "window_span_start_ns": window.span_start_ns,
                  "residuals": residuals},
        memory_peak_bytes=peak, window_compiles=window.compiles)
