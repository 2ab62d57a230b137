"""Distributed-path tests, on the reusable mesh harness (mesh_harness.py).

Each test runs a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count so the shard_map engines,
the mesh-resident sharded SPIN recursion, EP MoE, sharded embedding, and
elastic checkpoint restore execute on a real (fake-)multi-device mesh. The
main pytest process must keep seeing exactly one device (per the brief),
hence subprocesses; structured assertions marshal back via run_mesh."""

import pytest

from mesh_harness import run_mesh, run_py


def test_multiply_engines_and_spin_on_mesh():
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.compat import AxisType, make_mesh, set_mesh
        from repro.core import BlockMatrix, multiply_engine, testing, \\
            spin_inverse, lu_inverse, multiply

        mesh = make_mesh((4, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
        a = testing.make_spd(512, jax.random.PRNGKey(1))
        A = BlockMatrix.from_dense(a, 64)
        with set_mesh(mesh):
            sh = NamedSharding(mesh, P("data", "model", None, None))
            Ab = jax.device_put(A.blocks, sh)
            for eng in ("einsum", "allgather", "ring"):
                with multiply_engine(eng):
                    inv = jax.jit(lambda x: spin_inverse(
                        BlockMatrix(x)).blocks)(Ab)
                r = jnp.linalg.norm(BlockMatrix(inv).to_dense() @ a
                                    - jnp.eye(512)) / 512 ** 0.5
                assert float(r) < 1e-3, (eng, float(r))
                print(eng, "resid", float(r))
            with multiply_engine("ring"):
                inv = jax.jit(lambda x: lu_inverse(BlockMatrix(x)).blocks)(Ab)
            r = jnp.linalg.norm(BlockMatrix(inv).to_dense() @ a
                                - jnp.eye(512)) / 512 ** 0.5
            assert float(r) < 1e-3
            print("OK")
    """)
    assert "OK" in out


def test_moe_ep_matches_local():
    """Expert-parallel all_to_all dispatch must equal the single-device
    reference bit-for-bit in routing semantics (same capacity, same gates)."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.compat import AxisType, make_mesh, set_mesh
        from repro.configs import get_arch
        from repro.models import moe as moe_mod
        from repro.models.layers import init_tree
        import dataclasses as dc

        cfg = get_arch("dbrx-132b").reduced()
        # 4 experts over 4-way model axis -> E_loc = 1
        defs = moe_mod.moe_params(cfg, model_size_hint=4)
        params = init_tree(defs, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                              jnp.float32).astype(jnp.bfloat16)
        ref, aux_ref, z_ref = moe_mod.moe_apply(params, x, cfg)

        mesh = make_mesh((4, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
        with set_mesh(mesh):
            got, aux, z = jax.jit(
                lambda p, x: moe_mod.moe_apply(p, x, cfg))(params, x)
        err = jnp.max(jnp.abs(got.astype(jnp.float32)
                              - ref.astype(jnp.float32)))
        print("max err", float(err), "aux", float(aux), float(aux_ref))
        assert float(err) < 2e-2, float(err)
        # aux is a per-group (per-shard) load-balance loss under EP — close
        # to but not identical with the single-group reference
        assert abs(float(aux) - float(aux_ref)) < 0.15
        assert abs(float(z) - float(z_ref)) < 1e-3
        print("OK")
    """)
    assert "OK" in out


def test_embed_lookup_sharded_matches_take():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.compat import AxisType, make_mesh, set_mesh
        from repro.models.embedding import embed_lookup

        emb = jax.random.normal(jax.random.PRNGKey(0), (64, 32),
                                jnp.float32)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 12), 0, 64)
        want = jnp.take(emb, toks, axis=0)
        mesh = make_mesh((4, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
        with set_mesh(mesh):
            got = jax.jit(embed_lookup)(emb, toks)
        assert jnp.allclose(got, want, atol=1e-6)
        print("OK")
    """)
    assert "OK" in out


def test_elastic_checkpoint_restore_across_meshes():
    """Save sharded on a 2x2 mesh, restore onto 8-way — elastic rescale."""
    out = run_py("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.compat import AxisType, make_mesh
        from repro.checkpoint.ckpt import save, restore

        devs = jax.devices()
        mesh_a = make_mesh((2, 2), ("data", "model"),
                           axis_types=(AxisType.Auto,)*2,
                           devices=devs[:4])
        w = jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8)
        w_sharded = jax.device_put(
            w, NamedSharding(mesh_a, P("data", "model")))
        state = {"w": w_sharded, "step": jnp.int32(5)}
        with tempfile.TemporaryDirectory() as d:
            save(d, 5, state)
            mesh_b = make_mesh((8,), ("data",),
                               axis_types=(AxisType.Auto,),
                               devices=devs[:8])
            shardings = {"w": NamedSharding(mesh_b, P("data", None)),
                         "step": NamedSharding(mesh_b, P())}
            got, _ = restore(d, 5, state, shardings=shardings)
            assert np.array_equal(np.asarray(got["w"]), np.asarray(w))
            assert got["w"].sharding.num_devices == 8
        print("OK")
    """)
    assert "OK" in out


def test_compressed_psum_pod_axis():
    out = run_py("""
        import jax, jax.numpy as jnp, functools
        from jax.sharding import PartitionSpec as P
        from repro.compat import AxisType, make_mesh, set_mesh, shard_map
        from repro.parallel.compression import compressed_psum

        mesh = make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
        with set_mesh(mesh):
            got = jax.jit(shard_map(
                functools.partial(compressed_psum, axis_name="pod"),
                mesh=mesh, in_specs=P("pod", None), out_specs=P(None, None),
                check_vma=False))(x)
        want = jnp.broadcast_to(x.sum(0), (64,))
        rel = float(jnp.max(jnp.abs(got[0] - want)) /
                    (jnp.max(jnp.abs(want)) + 1e-9))
        assert rel < 0.05, rel      # int8 quantization tolerance
        print("OK")
    """, devices=4)
    assert "OK" in out


# ---------------------------------------------------------------------------
# Mesh-resident sharded SPIN (ISSUE 3 tentpole): parity with the dense path
# plus the no-replication-between-levels property, asserted from the spec
# ledger AND the jaxpr/lowering of the one-program recursion.
# ---------------------------------------------------------------------------

MESHES = [pytest.param(4, (2, 2), id="4dev-2x2"),
          pytest.param(8, (4, 2), id="8dev-4x2")]


@pytest.mark.parametrize("devices,mesh_shape", MESHES)
def test_sharded_spin_parity_and_mesh_residency(devices, mesh_shape):
    [res] = run_mesh(f"""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.compat import AxisType, make_mesh, set_mesh
        from repro.core import (BlockMatrix, multiply_engine, spin_inverse,
                                spin_inverse_sharded, spin_solve_sharded,
                                testing)
        from repro.core.verify import (inverse_residual, residual_tolerance,
                                       solve_residual)
        from repro.parallel import (ShardedBlockMatrix, assert_mesh_resident,
                                    record_specs, sharded_spin_inverse)

        n, bs = 256, 32
        mesh = make_mesh({mesh_shape}, ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
        a = testing.make_spd(n, jax.random.PRNGKey(0))
        rhs = jax.random.normal(jax.random.PRNGKey(1), (n, 4))

        def count_sharding_constraints(jaxpr):
            c = 0
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "sharding_constraint":
                    c += 1
                for v in eqn.params.values():
                    if hasattr(v, "jaxpr"):
                        c += count_sharding_constraints(v.jaxpr)
            return c

        out = {{"devices": jax.device_count(), "engines": {{}}}}
        with set_mesh(mesh):
            dense_inv = spin_inverse(BlockMatrix.from_dense(a, bs)).to_dense()
            sh = NamedSharding(mesh, P("data", "model", None, None))
            Ab = jax.device_put(BlockMatrix.from_dense(a, bs).blocks, sh)
            fn = lambda x: sharded_spin_inverse(ShardedBlockMatrix(x)).blocks

            # (a) no-replication property, from the ledger + the jaxpr +
            # the lowered HLO's sharding annotations
            with record_specs() as recs:
                lowered = jax.jit(fn).lower(Ab)
            out["residency"] = assert_mesh_resident(recs, min_records=20)
            out["ledger_records"] = len(recs)
            out["jaxpr_constraints"] = count_sharding_constraints(
                jax.make_jaxpr(fn)(Ab).jaxpr)
            # Shardy prints each constraint as
            # `sdy.sharding_constraint %x <@mesh, [{"data"}, {"model"}, ...]>`
            out["lowering_sharded_ops"] = sum(
                1 for line in lowered.as_text().splitlines()
                if "sdy.sharding_constraint" in line
                and ('{"data"}' in line or '{"model"}' in line))

            # (b) dtype-aware parity with the dense path, per engine
            for eng in ("einsum", "allgather", "ring"):
                with multiply_engine(eng):
                    x = spin_inverse_sharded(a, bs)
                out["engines"][eng] = {{
                    "residual": inverse_residual(a, x),
                    "parity": float(jnp.max(jnp.abs(x - dense_inv))),
                }}

            # (c) mesh-resident multi-RHS solve
            xs = spin_solve_sharded(a, rhs, bs)
            out["solve_residual"] = solve_residual(a, xs, rhs)
            out["tolerance"] = residual_tolerance(jnp.float32)
        emit_result(out)
    """, devices=devices)

    assert res["devices"] == devices
    tol = res["tolerance"]
    for eng, stats in res["engines"].items():
        assert stats["residual"] < tol, (eng, stats)
        assert stats["parity"] < tol, (eng, stats)
    assert res["solve_residual"] < tol
    # the recursion really was constrained level by level, and the
    # constraints survived into the jaxpr and the lowered SPMD program
    assert res["residency"]["grid_sharded"] >= 1
    assert res["jaxpr_constraints"] >= res["ledger_records"]
    assert res["lowering_sharded_ops"] > 0


@pytest.mark.parametrize("devices,mesh_shape", MESHES)
def test_sharded_conformance_sweep_on_mesh(devices, mesh_shape):
    """ISSUE 3 satellite: the core/verify.py conformance sweep (residuals +
    Algorithm-2 op-count oracle) on the sharded path, asserting parity with
    the dense path, under 4- and 8-device fake meshes."""
    [reports] = run_mesh(f"""
        import jax
        from repro.compat import AxisType, make_mesh, set_mesh
        from repro.core import verify

        mesh = make_mesh({mesh_shape}, ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
        with set_mesh(mesh):
            reports = verify.run_conformance(grids=(2, 4, 8), block_size=16,
                                             sharded=True)
        emit_result([r.as_dict() for r in reports])
    """, devices=devices, timeout=900)   # eager sweep; slow on loaded hosts

    assert len(reports) == 12           # 4 families x 3 grids
    bad = [r for r in reports if not r["ok"]]
    assert not bad, bad
    for r in reports:
        assert r["path"] == "sharded"
        assert r["op_counts_ok"], r     # paper op-count oracle on sharded path
        assert r["parity_vs_dense"] is not None
        assert r["parity_vs_dense"] < r["tolerance"], r


def test_planner_signature_sees_mesh_topology():
    """ISSUE 3 satellite (fix): a plan tuned without a mesh must not be
    recalled inside one — the signature (and the trace-safe memo) key on the
    ambient mesh descriptor."""
    [res] = run_mesh("""
        import jax, jax.numpy as jnp
        from repro.compat import AxisType, make_mesh, set_mesh
        from repro.planner import (default_cache, get_plan, mesh_descriptor,
                                   planned_block_size, signature_for)

        out = {"outside": mesh_descriptor()}
        sig_out = signature_for("inverse", 256, jnp.float32)
        get_plan("inverse", 256, jnp.float32, measure=False)
        bs_out = planned_block_size(256)
        mesh = make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
        with set_mesh(mesh):
            out["inside"] = mesh_descriptor()
            sig_in = signature_for("inverse", 256, jnp.float32)
            get_plan("inverse", 256, jnp.float32, measure=False)
            bs_in = planned_block_size(256)
            sig_sharded = signature_for("inverse", 256, jnp.float32,
                                        placement="sharded")
        out["keys"] = [sig_out.key(), sig_in.key(), sig_sharded.key()]
        out["block_sizes_divide"] = (256 % bs_out == 0 and 256 % bs_in == 0)
        cache = default_cache()
        out["cached_plan_keys"] = sorted(cache._load()["plans"])
        emit_result(out)
    """, devices=8)

    assert res["outside"] == ""
    assert res["inside"] == "data4:model2"
    assert len(set(res["keys"])) == 3, res["keys"]   # all three distinct
    assert res["block_sizes_divide"]
    # both topologies planned and cached under their own keys
    assert any("/mnone/" in k for k in res["cached_plan_keys"])
    assert any("/mdata4:model2/" in k for k in res["cached_plan_keys"])


def test_pallas_engine_parity_on_mesh():
    """ISSUE 4: the fused-kernel engine inside the mesh-resident recursion —
    per-shard grid GEMMs run the Pallas kernel under shard_map (interpret
    mode on the fake CPU mesh) and must agree with the dense XLA-engine
    result; the recursion must stay mesh-resident (no replication leak)."""
    [res] = run_mesh("""
        import jax, jax.numpy as jnp
        from repro.compat import AxisType, make_mesh, set_mesh
        from repro.core import (spin_inverse_dense, spin_inverse_sharded,
                                spin_solve_dense, spin_solve_sharded, testing)
        from repro.parallel import assert_mesh_resident, record_specs

        n, bs = 128, 32
        mesh = make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
        a = testing.make_spd(n, jax.random.PRNGKey(0))
        rhs = jax.random.normal(jax.random.PRNGKey(1), (n, 4))
        want = spin_inverse_dense(a, bs, engine="einsum")
        want_x = spin_solve_dense(a, rhs, bs, engine="einsum")
        out = {"devices": jax.device_count()}
        with set_mesh(mesh):
            with record_specs() as recs:
                got = spin_inverse_sharded(a, bs, engine="pallas")
            out["residency"] = assert_mesh_resident(recs, min_records=10)
            out["inv_parity"] = float(jnp.max(jnp.abs(got - want)))
            got_x = spin_solve_sharded(a, rhs, bs, engine="pallas")
            out["solve_parity"] = float(jnp.max(jnp.abs(got_x - want_x)))
        emit_result(out)
    """, devices=4, timeout=900)

    assert res["devices"] == 4
    assert res["residency"]["grid_sharded"] >= 1
    assert res["inv_parity"] < 1e-3
    assert res["solve_parity"] < 1e-3
