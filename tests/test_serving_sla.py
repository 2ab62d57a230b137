"""SLA hardening tests: admission control sheds with typed verdicts (never
a silent hang), priorities reorder across matrices but never break the
per-matrix FIFO barrier, deadlines expire queued requests, cost-aware LRU
residency evicts and transparently rehydrates, async snapshots capture a
consistent copy without stalling the tick loop, and the admission/residency
posture survives snapshot/restore."""

import tempfile
import threading

import jax
import jax.numpy as jnp
import pytest

from repro.core import spin_solve_dense
from repro.core.testing import make_spd
from repro.serving import AdmissionRejected, SpinService
from repro.serving.admission import (effective_priorities,
                                     order_for_admission, shed_victim)

N, BS = 128, 32


class FakeClock:
    """Injectable monotonic clock: deadlines and latency math on rails."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _service(slots=1, **kw) -> tuple[jax.Array, SpinService]:
    a = make_spd(N, jax.random.PRNGKey(0))
    svc = SpinService(slots=slots, **kw)
    svc.add_matrix("m", a, block_size=BS)
    return a, svc


def _rhs(seed: int) -> jax.Array:
    return jax.random.normal(jax.random.PRNGKey(seed), (N,))


# -- admission: bounded queue, shedding, quotas -------------------------------


def test_queue_full_rejects_with_typed_verdict():
    _, svc = _service(max_queue=2)
    queued = [svc.solve("m", _rhs(i)) for i in range(2)]
    with pytest.raises(AdmissionRejected) as exc:
        svc.solve("m", _rhs(9))
    assert exc.value.rejection.reason == "queue_full"
    assert svc.stats["rejected"] == 1
    assert svc.metrics()["counters"]["rejected_queue_full"] == 1
    svc.run_until_done()                          # admitted work unharmed
    assert all(r.done and not r.rejected for r in queued)


def test_higher_priority_sheds_lowest_queued_solve():
    """At the bound, an incoming higher-priority request evicts the lowest
    -priority queued solve (latest submitted among equals); the victim
    gets a typed verdict on its request object — never a silent hang."""
    _, svc = _service(max_queue=2)
    keeper = svc.solve("m", _rhs(1), priority=1)
    victim = svc.solve("m", _rhs(2), priority=0)
    vip = svc.solve("m", _rhs(3), priority=5)     # sheds `victim`
    assert victim.done and victim.rejected
    assert victim.verdict.reason == "shed" and victim.x is None
    assert svc.stats["shed"] == 1
    svc.run_until_done()
    assert keeper.done and not keeper.rejected
    assert vip.done and not vip.rejected and vip.path == "recursion"


def test_equal_priority_never_sheds():
    """Shedding requires STRICTLY lower priority — equal-priority traffic
    at the bound is rejected itself, not allowed to churn the queue."""
    _, svc = _service(max_queue=1)
    first = svc.solve("m", _rhs(1), priority=3)
    with pytest.raises(AdmissionRejected) as exc:
        svc.solve("m", _rhs(2), priority=3)
    assert exc.value.rejection.reason == "queue_full"
    assert not first.rejected


def test_updates_are_never_shed():
    """Updates are state mutations: an incoming high-priority solve at the
    bound must not evict one (it would silently lose a write)."""
    _, svc = _service(max_queue=1)
    up = svc.update("m", jnp.ones((N, 1)) / N, priority=0)
    with pytest.raises(AdmissionRejected):
        svc.solve("m", _rhs(1), priority=99)
    assert not up.rejected
    svc.run_until_done()
    assert up.done


def test_per_matrix_quota_preserves_fairness():
    a, svc = _service(per_matrix_quota=2)
    svc.add_matrix("other", make_spd(N, jax.random.PRNGKey(5)),
                   block_size=BS)
    hogs = [svc.solve("m", _rhs(i)) for i in range(2)]
    with pytest.raises(AdmissionRejected) as exc:
        svc.solve("m", _rhs(9))                   # tenant at quota
    assert exc.value.rejection.reason == "tenant_quota"
    other = svc.solve("other", _rhs(10))          # other tenant: admitted
    svc.run_until_done()
    assert other.done and all(r.done for r in hogs)


def test_deadline_expires_queued_request():
    clock = FakeClock()
    _, svc = _service(clock=clock)
    urgent = svc.solve("m", _rhs(1), deadline_s=1.0)
    lazy = svc.solve("m", _rhs(2))                # no deadline
    clock.advance(2.0)                            # deadline passes in queue
    svc.run_until_done()
    assert urgent.done and urgent.rejected
    assert urgent.verdict.reason == "deadline" and urgent.x is None
    assert lazy.done and not lazy.rejected        # unaffected
    assert len(svc._free) == svc.slots            # no slot consumed
    assert svc.metrics()["counters"]["rejected_deadline"] == 1


def test_deadline_met_when_served_in_time():
    clock = FakeClock()
    _, svc = _service(clock=clock)
    req = svc.solve("m", _rhs(1), deadline_s=10.0)
    clock.advance(1.0)
    svc.run_until_done()
    assert req.done and not req.rejected and req.path == "recursion"


# -- priority ordering vs per-matrix FIFO -------------------------------------


def test_priority_reorders_across_matrices():
    _, svc = _service(slots=1)
    svc.add_matrix("other", make_spd(N, jax.random.PRNGKey(5)),
                   block_size=BS)
    low = svc.solve("m", _rhs(1), priority=0)
    high = svc.solve("other", _rhs(2), priority=5)
    svc.tick()                                    # one slot: high wins it
    assert high.done and not low.done
    svc.run_until_done()
    assert low.done


def test_priority_cannot_overtake_same_matrix_barrier():
    """A priority-10 solve behind a priority-0 update on the SAME matrix
    inherits the barrier: it must see the post-update matrix."""
    a, svc = _service(slots=1)
    rhs = _rhs(1)
    blocker = svc.solve("m", rhs)                 # occupies the slot first
    u = jax.random.normal(jax.random.PRNGKey(7), (N, 4)) / N ** 0.5
    up = svc.update("m", u, priority=0)
    after = svc.solve("m", rhs, priority=10)
    svc.tick()
    assert blocker.done and not up.done and not after.done
    svc.run_until_done()
    assert up.done and after.done
    a2 = a + u @ u.T
    assert float(jnp.max(jnp.abs(a2 @ after.x - rhs))) < 1e-3
    assert not bool((blocker.x == after.x).all())


def test_effective_priority_clamp_is_per_matrix():
    class R:
        def __init__(self, mid, p):
            self.matrix_id, self.priority = mid, p

    q = [R("a", 5), R("a", 9), R("b", 7), R("a", 2), R("b", 1)]
    assert effective_priorities(q) == [5, 5, 7, 2, 1]
    ordered = order_for_admission(q)
    assert [(r.matrix_id, r.priority) for r in ordered] == \
        [("b", 7), ("a", 5), ("a", 9), ("a", 2), ("b", 1)]
    assert shed_victim(q, incoming_priority=5) is None   # no rhs attr
    q[3].rhs = object()
    q[4].rhs = object()
    assert shed_victim(q, incoming_priority=2) is q[4]   # strictly lower
    assert shed_victim(q, incoming_priority=1) is None


# -- multi-tenant residency: cost-aware LRU eviction + rehydration ------------


def test_lru_eviction_and_transparent_rehydration():
    with tempfile.TemporaryDirectory() as spill:
        a, svc = _service(slots=2, max_resident=1, spill_dir=spill)
        st = svc.matrix("m")
        offline = spin_solve_dense(a, _rhs(3)[:, None], st.block_size,
                                   st.leaf_solver, engine=st.engine)[:, 0]
        b = make_spd(N, jax.random.PRNGKey(5))
        svc.add_matrix("other", b, block_size=BS)
        assert not svc.is_resident("m")           # evicted for "other"
        assert svc.is_resident("other")
        assert svc.stats["evictions"] == 1
        req = svc.solve("m", _rhs(3))             # transparent rehydration
        svc.run_until_done()
        assert svc.is_resident("m") and not svc.is_resident("other")
        assert svc.stats["rehydrations"] == 1
        assert req.path == "recursion"
        assert bool((req.x == offline).all())     # round-trip is bit-exact


def test_eviction_is_cost_aware_not_pure_lru():
    """GreedyDual: the matrix cheap to re-invert goes first, even when the
    expensive one is older — recency alone must not decide."""
    with tempfile.TemporaryDirectory() as spill:
        svc = SpinService(slots=2, max_resident=2, spill_dir=spill)
        svc.add_matrix("big", make_spd(256, jax.random.PRNGKey(1)),
                       block_size=64)             # oldest, expensive
        svc.add_matrix("small", make_spd(64, jax.random.PRNGKey(2)),
                       block_size=32)
        big = svc.matrix("big")
        small = svc.matrix("small")
        assert big.reinvert_cost_s > small.reinvert_cost_s > 0
        svc.add_matrix("third", make_spd(64, jax.random.PRNGKey(3)),
                       block_size=32)
        assert svc.is_resident("big")             # survived despite age
        assert not svc.is_resident("small")


def test_evicted_matrix_still_updates_and_snapshots():
    """An evicted matrix is still admitted: updates rehydrate it, and a
    snapshot covers resident AND evicted matrices alike."""
    with tempfile.TemporaryDirectory() as spill:
        a, svc = _service(slots=2, max_resident=1, spill_dir=spill)
        svc.add_matrix("other", make_spd(N, jax.random.PRNGKey(5)),
                       block_size=BS)
        assert not svc.is_resident("m")
        u = jax.random.normal(jax.random.PRNGKey(7), (N, 2)) / N ** 0.5
        up = svc.update("m", u)                   # rehydrates on apply
        svc.run_until_done()
        assert up.done and svc.is_resident("m")
        with tempfile.TemporaryDirectory() as d:
            svc.snapshot(d)                       # includes evicted "other"
            restored = SpinService.restore(d, max_resident=None)
            assert set(restored._matrices) == {"m", "other"}
            r = restored.solve("m", _rhs(8))
            restored.run_until_done()
            a2 = a + u @ u.T
            assert float(jnp.max(jnp.abs(a2 @ r.x - r.rhs))) < 1e-3


def test_unknown_matrix_still_raises_keyerror():
    _, svc = _service(max_resident=1)
    with pytest.raises(KeyError):
        svc.solve("nope", jnp.zeros((N,)))
    with pytest.raises(KeyError):
        svc.is_resident("nope")


def test_transient_residency_pressure_defers_solve_not_fails():
    """Regression: with max_resident < concurrently-active tenants, every
    resident matrix can be momentarily hot (live slot / queued request).
    That is TRANSIENT — the solve must be deferred and succeed on a later
    tick, never failed with a 'cannot evict' error."""
    with tempfile.TemporaryDirectory() as spill:
        a, svc = _service(slots=4, max_resident=1, spill_dir=spill)
        svc.add_matrix("other", make_spd(N, jax.random.PRNGKey(5)),
                       block_size=BS)                 # evicts "m"
        r_m = svc.solve("m", _rhs(1))     # needs rehydration, no room yet
        r_o = svc.solve("other", _rhs(2))  # keeps "other" hot this tick
        svc.tick()
        assert r_o.done and not r_o.failed
        assert not r_m.done and not r_m.failed        # deferred, NOT failed
        svc.run_until_done()
        assert r_m.done and not r_m.failed and not r_m.rejected
        assert r_m.path == "recursion"
        assert svc.stats["batch_failures"] == 0
        assert float(jnp.max(jnp.abs(a @ r_m.x - r_m.rhs))) < 1e-3


def test_transient_residency_pressure_defers_update_not_drops():
    """Regression: an update needing rehydration while every resident
    matrix is hot used to raise out of tick() AFTER the request left the
    queue — silently dropped, submitter hung forever. It must be deferred
    and applied on a later tick."""
    with tempfile.TemporaryDirectory() as spill:
        a, svc = _service(slots=2, max_resident=1, spill_dir=spill)
        svc.add_matrix("other", make_spd(N, jax.random.PRNGKey(5)),
                       block_size=BS)                 # evicts "m"
        r_o = svc.solve("other", _rhs(1))  # holds "other" hot this tick
        u = jax.random.normal(jax.random.PRNGKey(7), (N, 1)) / N ** 0.5
        up = svc.update("m", u)
        svc.run_until_done()
        assert r_o.done and not r_o.failed
        assert up.done and not up.rejected and not up.failed
        r = svc.solve("m", _rhs(8))
        svc.run_until_done()
        a2 = a + u @ u.T
        assert float(jnp.max(jnp.abs(a2 @ r.x - r.rhs))) < 1e-3


def test_update_rehydration_io_failure_is_typed_not_dropped(monkeypatch):
    """A genuine spill I/O error on the update path must land a typed
    failed/error verdict on the request — never propagate out of tick()
    with the request dropped and its submitter waiting on done forever."""
    import repro.core.solver_ckpt as ckpt

    with tempfile.TemporaryDirectory() as spill:
        _, svc = _service(slots=2, max_resident=1, spill_dir=spill)
        svc.add_matrix("other", make_spd(N, jax.random.PRNGKey(5)),
                       block_size=BS)                 # evicts "m"

        def boom(*args, **kw):
            raise OSError("spill device gone")

        monkeypatch.setattr(ckpt, "load_matrix_spill", boom)
        up = svc.update("m", jnp.ones((N, 1)) / N)
        svc.run_until_done()                          # must not raise
        assert up.done and up.failed and not up.rejected
        assert "OSError" in up.error
        assert svc.stats["batch_failures"] == 1
        assert svc.metrics()["counters"]["rehydration_failures"] == 1


# -- async snapshots ----------------------------------------------------------


def test_async_snapshot_never_stalls_the_tick_loop(monkeypatch):
    """Block the snapshot's file I/O on an event: the service must keep
    admitting and serving while the writer thread is stuck, the captured
    payload must be the quiesced PRE-update state (immutable-copy
    semantics), and a second in-flight snapshot is refused."""
    import repro.core.solver_ckpt as solver_ckpt

    a, svc = _service(slots=2)
    st = svc.matrix("m")
    inv_before = st.inv
    gate, started = threading.Event(), threading.Event()
    orig = solver_ckpt.save_service_snapshot

    def gated(*args, **kwargs):
        started.set()
        assert gate.wait(30.0)
        return orig(*args, **kwargs)

    monkeypatch.setattr(solver_ckpt, "save_service_snapshot", gated)
    with tempfile.TemporaryDirectory() as d:
        task = svc.snapshot_async(d)
        assert started.wait(30.0)
        with pytest.raises(RuntimeError):         # one in flight at a time
            svc.snapshot_async(d)
        ticks0 = svc.ticks
        req = svc.solve("m", _rhs(1))             # serving while I/O blocked
        u = jax.random.normal(jax.random.PRNGKey(7), (N, 2)) / N ** 0.5
        svc.update("m", u)
        svc.run_until_done()
        assert req.done and svc.ticks > ticks0    # tick loop never stalled
        assert not task.done                      # writer still gated
        gate.set()
        task.wait(30.0)
        restored = SpinService.restore(d)
        st2 = restored.matrix("m")
        # pre-update capture: the mid-snapshot update never leaked in
        assert st2.smw_applied == 0
        assert bool((st2.inv == inv_before).all())
        assert bool((st2.a == a).all())


def test_async_snapshot_requires_quiesced_service():
    _, svc = _service()
    svc.solve("m", _rhs(1))
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(RuntimeError):
            svc.snapshot_async(d)
    svc.run_until_done()


# -- warm restarts: persistent compilation cache ------------------------------


def test_enable_compilation_cache_wiring(tmp_path, monkeypatch):
    """The cache lands in $JAX_COMPILATION_CACHE_DIR when it is set and in
    the fixed in-checkout default otherwise; entries are produced on the
    next compile even when enabled AFTER earlier compilations latched the
    cache module; the service turns it on unless told not to."""
    import os
    import pathlib

    from repro import compat

    cache_dir = str(tmp_path / "xla-cache")
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
        assert compat.enable_compilation_cache() == cache_dir
        assert jax.config.jax_compilation_cache_dir == cache_dir
        assert os.path.isdir(cache_dir)
        jax.jit(lambda x: x * 3.0 + 1.0)(
            jnp.ones((16, 16))).block_until_ready()
        assert len(os.listdir(cache_dir)) > 0            # entries landed
        assert SpinService(slots=1).compile_cache_dir == cache_dir
        assert SpinService(slots=1, compile_cache=False).compile_cache_dir \
            is None                                      # explicit off

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        default = compat.DEFAULT_COMPILATION_CACHE_DIR
        repo = pathlib.Path(__file__).resolve().parents[1]
        assert pathlib.Path(default) == repo / ".jax_cache"
        assert compat.enable_compilation_cache() == default
        assert jax.config.jax_compilation_cache_dir == default
    finally:                     # restore the process's cache directory
        jax.config.update("jax_compilation_cache_dir", was)
        from jax.experimental.compilation_cache import (
            compilation_cache as cc)

        cc.reset_cache()


# -- config persistence -------------------------------------------------------


def test_restore_preserves_admission_and_residency_config():
    _, svc = _service(max_queue=7, per_matrix_quota=3, max_resident=4)
    with tempfile.TemporaryDirectory() as d:
        svc.snapshot(d)
        restored = SpinService.restore(d)
        assert restored.admission.max_queue == 7
        assert restored.admission.per_matrix_quota == 3
        assert restored.max_resident == 4
        retuned = SpinService.restore(d, max_queue=2, max_resident=None)
        assert retuned.admission.max_queue == 2
        assert retuned.max_resident is None
        assert retuned.admission.per_matrix_quota == 3   # untouched knob
