"""PrecisionPolicy API + low-precision certified serving (DESIGN.md §12).

Covers the policy object itself (presets, descriptor round-trip, env
resolution, the fp8 capability gate, deprecation shims), the planner's
precision axis (bf16 storage priced into the roofline, cache-key
separation, the v2→v3 schema bump), the core low-precision entry points,
and the SpinService certified bf16 serve path — conformance over the
matrix zoo, polish triggering, and snapshot/restore of the policy.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from repro import compat
from repro.core import (PRECISION_PRESETS, PrecisionPolicy, apply_inverse,
                        current_engine, resolve_precision, spin_inverse_dense,
                        spin_solve_dense)
from repro.core.precision import (_WARNED_SITES, policy_from_compute_dtype,
                                  warn_deprecated_dtype_kwarg)
from repro.core.solve import spin_inverse_batched
from repro.core.testing import (MATRIX_FAMILIES, make_ill_conditioned_spd,
                                make_spd)
from repro.core.verify import inverse_residual, residual_tolerance
from repro.planner import (PLAN_CACHE_VERSION, Plan, PlanCache,
                           enumerate_plans, predict_cost, signature_for)
from repro.serving import SpinService

BF16 = PRECISION_PRESETS["bf16"]
BF16_BOUND = BF16.bound("float32")


# ----------------------------------------------------------- policy object

def test_presets_and_aliases():
    assert PRECISION_PRESETS["exact"].is_exact
    assert PRECISION_PRESETS["f32"] is PRECISION_PRESETS["exact"]
    assert PRECISION_PRESETS["bfloat16"] is PRECISION_PRESETS["bf16"]
    assert BF16.store_dtype == "bfloat16"
    assert BF16.resolve_compute(jnp.float32) == "bfloat16"
    assert BF16.accum_dtype == "float32"


def test_descriptor_round_trip_preset_and_custom():
    assert BF16.descriptor() == "bf16"
    assert PrecisionPolicy.from_descriptor("bf16") == BF16
    custom = PrecisionPolicy(name="x", store_dtype="bfloat16",
                             polish_sweeps=3, tolerance=5e-3)
    assert PrecisionPolicy.from_descriptor(custom.descriptor()) == custom


def test_bound_defaults_to_weakest_dtype_tolerance():
    assert BF16_BOUND == residual_tolerance(jnp.bfloat16)
    # explicit tolerance wins
    tight = dataclasses.replace(BF16, tolerance=1e-3)
    assert tight.bound(jnp.float32) == 1e-3


def test_resolve_env_and_field_overrides(monkeypatch):
    monkeypatch.setenv("SPIN_PRECISION", "bf16")
    monkeypatch.setenv("SPIN_PRECISION_POLISH_SWEEPS", "4")
    pol = resolve_precision(None)
    assert pol.store_dtype == "bfloat16" and pol.polish_sweeps == 4
    # an explicitly constructed policy is taken verbatim — no env overrides
    assert resolve_precision(BF16).polish_sweeps == BF16.polish_sweeps


def test_resolve_default_is_exact(monkeypatch):
    monkeypatch.delenv("SPIN_PRECISION", raising=False)
    assert resolve_precision(None).is_exact


def test_unknown_preset_and_bad_dtype_fail_loudly():
    with pytest.raises(ValueError):
        resolve_precision("no_such_preset")
    with pytest.raises(ValueError):
        PrecisionPolicy(store_dtype="int8")


def test_fp8_storage_hook_gated_on_capability():
    if compat.supports_float8():
        pol = resolve_precision("fp8")
        assert pol.store_dtype == "float8_e4m3fn"
        assert pol.compute_dtype == "bfloat16"    # fp8 math needs scaling
    else:
        assert "fp8" not in PRECISION_PRESETS
        with pytest.raises(ValueError):
            PrecisionPolicy(store_dtype="float8_e4m3fn")


# ----------------------------------------------------------- shims

def test_deprecated_compute_dtype_warns_once_and_is_bitwise():
    a = make_spd(64, jax.random.PRNGKey(0))
    _WARNED_SITES.discard("spin_inverse_dense")
    with pytest.warns(DeprecationWarning):
        old = spin_inverse_dense(a, 32, "linalg", compute_dtype=jnp.bfloat16)
    # second call: warn-once means NO further warning from this site
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)
        old2 = spin_inverse_dense(a, 32, "linalg",
                                  compute_dtype=jnp.bfloat16)
    new = spin_inverse_dense(
        a, 32, "linalg", precision=policy_from_compute_dtype(jnp.bfloat16))
    assert old.dtype == jnp.float32           # legacy cast-in/cast-out
    assert (old == new).all() and (old == old2).all()


def test_warn_once_helper_is_per_site():
    _WARNED_SITES.discard("site_a")
    with pytest.warns(DeprecationWarning):
        warn_deprecated_dtype_kwarg("site_a")
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)
        warn_deprecated_dtype_kwarg("site_a")   # silent second time


# ----------------------------------------------------------- planner axis

def test_planner_tpu_auto_prefers_bf16_store():
    """The acceptance criterion: on a TPU-backend signature (no hardware
    needed — pure cost model), auto precision makes the cheapest plan a
    bf16-stored one, because bf16 halves the memory-bound serve roofline."""
    sig = signature_for("inverse", 4096, jnp.float32, backend="tpu", device_kind="TPU v5 lite",
                        device_count=4, cores=4, precision="auto")
    plans = enumerate_plans(sig)
    assert {p.store_dtype for p in plans} == {"", "bfloat16"}
    best = min(plans, key=lambda p: predict_cost(sig, p))
    assert best.store_dtype == "bfloat16"


def test_planner_cpu_auto_keeps_exact_store():
    """On CPU there is no native bf16 GEMM — the emulated-half penalty
    makes exact storage win, so auto resolves to exact serving."""
    sig = signature_for("inverse", 4096, jnp.float32, backend="cpu",
                        device_count=1, cores=8, precision="auto")
    best = min(enumerate_plans(sig), key=lambda p: predict_cost(sig, p))
    assert best.store_dtype == ""


def test_precision_is_a_cache_key_axis(tmp_path):
    plain = signature_for("inverse", 256, jnp.float32)
    lowp = signature_for("inverse", 256, jnp.float32, precision="bf16")
    assert plain.key() != lowp.key()
    cache = PlanCache(str(tmp_path / "plans.json"))
    cache.put(plain, Plan(block_size=32))
    assert cache.get(lowp) is None            # never cross-served
    cache.put(lowp, Plan(block_size=64, store_dtype="bfloat16"))
    assert cache.get(plain).block_size == 32
    assert cache.get(lowp).store_dtype == "bfloat16"


def test_plan_cache_v2_files_are_discarded(tmp_path):
    """Schema bump regression: a v2 cache file predates the precision axis
    and Plan.store_dtype — v2 plans were never priced along it, so the
    whole file must be discarded (not mis-hit) by a v3 reader."""
    assert PLAN_CACHE_VERSION >= 3
    path = tmp_path / "plans.json"
    sig = signature_for("inverse", 128, jnp.float32)
    # a v2-era file: same layout, old version, key without the /p suffix
    path.write_text(json.dumps({
        "version": 2,
        "plans": {sig.key(): {"sig": {}, "plan": Plan(block_size=8).to_dict()}},
        "calibration": {},
    }))
    assert PlanCache(str(path)).get(sig) is None


# ----------------------------------------------------------- core entry points

def test_bf16_inverse_conformance_well_posed_zoo():
    """bf16 serve over the well-posed families: residual within the
    certified bound both with and without polish (polish only tightens)."""
    raw = dataclasses.replace(BF16, polish_sweeps=0)
    for name, gen in MATRIX_FAMILIES.items():
        if name == "ill_conditioned_spd":
            continue                          # κ-limited: separate test
        a = gen(128, jax.random.PRNGKey(3))
        for pol in (BF16, raw):
            x = spin_inverse_dense(a, 32, "linalg", precision=pol)
            assert x.dtype == jnp.bfloat16
            assert inverse_residual(a, x) <= BF16_BOUND, (name, pol.name)


def test_bf16_inverse_ill_conditioned_needs_polish():
    """κ=1e2 ill-conditioned SPD (stress, but within bf16-store reach —
    the bf16 analogue of the f32 harness's κ=1e4): the RAW bf16 recursion
    exceeds the certified bound, Newton–Schulz polish repairs it."""
    a = make_ill_conditioned_spd(128, jax.random.PRNGKey(3), cond=1e2)
    raw = spin_inverse_dense(
        a, 32, "linalg", precision=dataclasses.replace(BF16, polish_sweeps=0))
    polished = spin_inverse_dense(
        a, 32, "linalg", precision=dataclasses.replace(BF16, polish_sweeps=3))
    assert inverse_residual(a, raw) > BF16_BOUND
    assert inverse_residual(a, polished) <= BF16_BOUND


def test_solve_and_batched_accept_precision():
    n = 64
    a = make_spd(n, jax.random.PRNGKey(0))
    b = jax.random.normal(jax.random.PRNGKey(1), (n, 3))
    x = spin_solve_dense(a, b, 32, "linalg", precision="bf16")
    assert x.dtype == b.dtype                 # solves return at rhs dtype
    ref = jnp.linalg.solve(a, b)
    rel = float(jnp.linalg.norm(x - ref) / jnp.linalg.norm(ref))
    assert rel <= BF16_BOUND

    batch = jnp.stack([make_spd(n, jax.random.PRNGKey(i)) for i in range(2)])
    invs = spin_inverse_batched(batch, 32, "linalg", precision="bf16")
    assert invs.dtype == jnp.bfloat16
    for i in range(2):
        assert inverse_residual(batch[i], invs[i]) <= BF16_BOUND


def test_apply_inverse_precision_serves_at_compute_dtype():
    n = 64
    a = make_spd(n, jax.random.PRNGKey(0))
    inv = spin_inverse_dense(a, 32, "linalg", precision="bf16")
    rhs = jax.random.normal(jax.random.PRNGKey(1), (n, 2))
    x = apply_inverse(inv, rhs, precision="bf16")
    assert x.dtype == rhs.dtype
    ref = jnp.linalg.solve(a, rhs)
    assert float(jnp.linalg.norm(x - ref) / jnp.linalg.norm(ref)) <= BF16_BOUND


def test_exact_precision_is_bitwise_noop():
    a = make_spd(64, jax.random.PRNGKey(0))
    plain = spin_inverse_dense(a, 32, "linalg")
    exact = spin_inverse_dense(a, 32, "linalg", precision="exact")
    assert (plain == exact).all() and exact.dtype == plain.dtype


# ----------------------------------------------------------- serving path

def _serve_one(svc, mid, rhs):
    req = svc.solve(mid, rhs)
    svc.run_until_done()
    return req


def test_service_bf16_serves_maintained_with_residual():
    n = 128
    a = make_spd(n, jax.random.PRNGKey(0))
    svc = SpinService(slots=4)
    st = svc.add_matrix("m", a, precision="bf16")
    assert st.precision == "bf16" and st.store_dtype == "bfloat16"
    assert st.inv.dtype == jnp.bfloat16
    assert st.serve_bound == BF16_BOUND
    assert st.drift.residual_est <= st.serve_bound     # certified at admit

    rhs = jax.random.normal(jax.random.PRNGKey(1), (n,))
    req = _serve_one(svc, "m", rhs)
    assert req.path == "maintained"                    # never the recursion
    assert req.residual_est is not None
    assert req.residual_est <= st.serve_bound
    assert req.x.dtype == rhs.dtype
    ref = jnp.linalg.solve(a, rhs)
    assert float(jnp.linalg.norm(req.x - ref)
                 / jnp.linalg.norm(ref)) <= BF16_BOUND
    assert svc.stats["lowp_serves"] == 1
    snap = svc.metrics()
    assert snap["residual"]["count"] == 1
    assert snap["counters"]["path_maintained"] == 1


def test_service_certifies_under_churn_and_counts_polish():
    """SMW churn degrades a bf16-maintained inverse; certification must
    re-probe through the lowp GEMM and fire polish when the probe exceeds
    the bound — counted in stats AND metrics()."""
    n = 128
    a = make_ill_conditioned_spd(n, jax.random.PRNGKey(5), cond=1e2)
    svc = SpinService(slots=2)
    st = svc.add_matrix("ill", a, precision="bf16")
    assert st.polish_triggers >= 1            # raw bf16 exceeds the bound
    assert st.drift.residual_est <= st.serve_bound
    for i in range(3):
        u = 0.05 * jax.random.normal(jax.random.PRNGKey(10 + i), (n, 2))
        svc.update("ill", u, u)
        svc.run_until_done()
        assert st.drift.residual_est <= st.serve_bound
    req = _serve_one(svc, "ill",
                     jax.random.normal(jax.random.PRNGKey(2), (n,)))
    assert req.path == "maintained" and req.residual_est <= st.serve_bound
    assert svc.stats["polish_triggers"] >= 1
    assert svc.stats["polish_sweeps"] >= svc.stats["polish_triggers"]
    assert svc.metrics()["counters"]["polish_triggers"] >= 1


def test_service_snapshot_restores_policy_and_serves_bitwise(tmp_path):
    n = 64
    a = make_spd(n, jax.random.PRNGKey(0))
    svc = SpinService(slots=2, precision="bf16")
    st = svc.add_matrix("m", a)               # service default policy
    rhs = jax.random.normal(jax.random.PRNGKey(1), (n,))
    before = _serve_one(svc, "m", rhs)
    svc.snapshot(str(tmp_path / "snap"))

    svc2 = SpinService.restore(str(tmp_path / "snap"))
    st2 = svc2._matrices["m"]
    assert st2.precision == st.precision
    assert st2.store_dtype == st.store_dtype
    assert st2.serve_bound == st.serve_bound
    assert st2.polish_triggers == st.polish_triggers
    assert st2.inv.dtype == jnp.bfloat16      # store dtype survives the I/O
    after = _serve_one(svc2, "m", rhs)
    assert after.path == "maintained"
    assert (after.x == before.x).all()        # bit-identical resumed serving
    # the restored service default seeds future add_matrix
    assert resolve_precision(svc2.precision).descriptor() == "bf16"


def test_service_eviction_spill_preserves_bf16_store(tmp_path):
    """A bf16-stored inverse must survive the residency spill round-trip
    (matrix_io raw-views non-numpy dtypes) and keep serving certified."""
    n = 64
    svc = SpinService(slots=2, max_resident=1,
                      spill_dir=str(tmp_path / "spill"))
    a1 = make_spd(n, jax.random.PRNGKey(0))
    a2 = make_spd(n, jax.random.PRNGKey(1))
    svc.add_matrix("hot", a1, precision="bf16")
    svc.add_matrix("cold", a2)                # evicts "hot"
    assert not svc.is_resident("hot")
    rhs = jax.random.normal(jax.random.PRNGKey(2), (n,))
    req = _serve_one(svc, "hot", rhs)         # rehydrates
    st = svc._matrices["hot"]
    assert st.precision == "bf16" and st.inv.dtype == jnp.bfloat16
    assert req.path == "maintained" and req.residual_est <= st.serve_bound


def test_service_rejects_sharded_lowp():
    svc = SpinService(slots=2)
    a = make_spd(64, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="dense-only"):
        svc.add_matrix("m", a, sharded=True, precision="bf16")


def test_service_exact_path_unchanged_without_policy():
    """No policy ⇒ the legacy exact path, bit-for-bit: recursion serve,
    no reported residual, no lowp counters."""
    n = 64
    a = make_spd(n, jax.random.PRNGKey(0))
    svc = SpinService(slots=2)
    st = svc.add_matrix("m", a)
    assert st.precision == "" and st.inv.dtype == jnp.float32
    req = _serve_one(svc, "m", jax.random.normal(jax.random.PRNGKey(1), (n,)))
    assert req.path == "recursion" and req.residual_est is None
    assert svc.stats["lowp_serves"] == 0


# ----------------------------------------------------------- public surface

def test_top_level_reexports():
    import repro.core as core
    import repro.serving as serving

    for mod in (core, serving):
        assert mod.PrecisionPolicy is PrecisionPolicy
        assert mod.resolve_precision is resolve_precision
        assert "PrecisionPolicy" in mod.__all__
    # the multiply footgun: the function is the package-level export, and
    # the engine helpers ride along so nobody needs the shadowed submodule
    assert callable(core.multiply) and callable(core.current_engine)
    assert current_engine() in ("einsum", "pallas", "allgather", "ring")
