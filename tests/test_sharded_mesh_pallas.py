"""The mesh-resident inverse with the Pallas engine and leaf on a (2, 2)
mesh, and what the mesh adds to the op counts (`OpCounts.gather_bytes`,
`pipelined_gather_bytes`, `replicated_block_gemms`, `replicated_leaves`,
`local_splits`, `local_arranges`) and to the named scopes (the `gather`
step); the pipelined SUMMA product against the one-gather product it
replaces.

n=512 at block 64 is a grid of 8: the nodes at depths 0 and 1 have
quadrant grids of 4 and 2, which divide the mesh, split and arrange
interleaved quadrants on the device and multiply by SUMMA; the four nodes
at depth 2 have one-block quadrants, split contiguously and multiply
replicated, on every device, as do the eight leaves at depth 3. Four
devices need a subprocess (`tests/mesh_harness.py`); the Pallas kernels
run interpreted there.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, AxisType

from repro.core import BlockMatrix, count_ops, spin_inverse
from repro.core.multiply import multiply_engine
from repro.core.spin import inverse_op_scopes
from repro.core.testing import make_spd
from repro.obs.trace import STEPS
from repro.parallel import ShardedBlockMatrix, sharded_spin_inverse
from tests.mesh_harness import run_mesh

N, BS, MESH = 512, 64, (2, 2)
COUNTERS = ("gather_bytes", "pipelined_gather_bytes",
            "replicated_block_gemms", "replicated_leaves", "local_splits",
            "local_arranges")
COLLECTIVE = r"all-gather|all-to-all|collective-permute"


def summa_steps(local_k: int, mesh: tuple[int, int]) -> int:
    """The steps of a Pallas SUMMA product over `local_k` local k blocks:
    its largest divisor up to 4 where both mesh axes hold two devices,
    else 1."""
    if mesh != (2, 2):
        return 1
    return max(s for s in range(1, 5) if local_k % s == 0)


def closed_forms(n: int, bs: int, mesh: tuple[int, int]) -> dict:
    """The six mesh counters of one f32 inversion. A node at depth k has
    2**k peers and six products of quadrants h = grid/2**(k+1) blocks on a
    side. Where h divides both mesh axes a product is SUMMA: each device
    holds (h/d)×(h/m) blocks of each operand and receives the rest of A's
    row panel along `model` and of B's column panel along `data`, all of it
    but the first of its `summa_steps` chunks while a GEMM step runs; on a
    square mesh the node also splits and arranges on the device. Where it
    does not, every device computes its h³ block GEMMs. Every leaf is
    inverted on every device."""
    d, m = mesh
    grid = n // bs
    out = dict.fromkeys(COUNTERS, 0)
    out["replicated_leaves"] = grid
    k = 0
    while grid >> k > 1:
        h, products = grid >> (k + 1), 6 * 2 ** k
        if h % d == 0 and h % m == 0:
            blocks_in = (h // d) * (h - h // m) + (h - h // d) * (h // m)
            out["gather_bytes"] += products * blocks_in * bs * bs * 4
            steps = summa_steps(h // m, mesh)
            out["pipelined_gather_bytes"] += (
                products * blocks_in * bs * bs * 4 * (steps - 1) // steps)
            if d == m:
                out["local_splits"] += 2 ** k
                out["local_arranges"] += 2 ** k
        else:
            out["replicated_block_gemms"] += products * h ** 3
        k += 1
    return out


@pytest.fixture(scope="module")
def on_the_mesh():
    """One sharded Pallas inversion on four devices: its residual, its
    distance from NumPy's inverse, its counters, its gather scopes and
    the levels of its collectives under a `split` or `arrange` step."""
    (got,) = run_mesh(f"""
        import re
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType, Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P
        from repro.compat import set_mesh
        from repro.core import count_ops, spin_inverse_sharded
        from repro.core.spin import inverse_op_scopes
        from repro.core.testing import make_spd

        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        a = jax.device_put(make_spd({N}, jax.random.PRNGKey(7)),
                           NamedSharding(mesh, P("data", "model")))
        with set_mesh(mesh), count_ops() as counts:
            x = spin_inverse_sharded(a, {BS}, leaf_solver="pallas",
                                     engine="pallas")
        # The formula of bench/reference.inverse_residual, at HIGHEST.
        r = jnp.matmul(a, x, precision=jax.lax.Precision.HIGHEST)
        residual = jnp.linalg.norm(r - jnp.eye({N})) / np.sqrt({N})
        a64, x64 = np.asarray(a, np.float64), np.asarray(x, np.float64)
        scopes = inverse_op_scopes({N}, {BS}, "pallas", "pallas", mesh=mesh)
        emit_result({{
            "residual": float(residual),
            "vs_numpy": float(np.abs(x64 - np.linalg.inv(a64)).max()),
            "counts": counts.as_dict(),
            "gather_levels": sorted({{lv for ops in scopes.values()
                                     for lv, st in ops.values()
                                     if st == "gather"}}),
            "layout_collective_levels": sorted({{
                lv for ops in scopes.values()
                for name, (lv, st) in ops.items()
                if st in ("split", "arrange")
                and re.search({COLLECTIVE!r}, name)}})}})
    """, devices=4)
    return got


# A = B Bᵀ/n + I has its spectrum in about [1, 5], so ‖A⁻¹‖₂ ≤ 1 and the
# condition number is about 5. The CPU reads a residual of about 3e-7 and
# a largest entry error of about 6e-7 here.
@pytest.mark.parametrize("reading,limit", [
    # ‖A X − I‖_F/√n: f32 rounding (6e-8 a step) grows over the four
    # depths and the 64-wide leaf eliminations; 2e-6 leaves 6× room above
    # the reading and is 500× below one bf16 pass (the benchmark's control
    # on a CPU reads ~1e-3).
    ("residual", 2e-6),
    # max |X − A⁻¹| against NumPy in float64: condition × the residual, with
    # the same room.
    ("vs_numpy", 5e-6),
])
def test_sharded_pallas_inverse_matches_the_reference(on_the_mesh, reading,
                                                      limit):
    assert 0 < on_the_mesh[reading] < limit, on_the_mesh


@pytest.mark.parametrize("counter", COUNTERS)
def test_mesh_counters_equal_their_closed_forms(on_the_mesh, counter):
    assert on_the_mesh["counts"][counter] == closed_forms(N, BS,
                                                          MESH)[counter]


def test_gathers_are_named_at_the_summa_levels_only(on_the_mesh):
    assert "gather" in STEPS
    assert on_the_mesh["gather_levels"] == [0, 1]


def test_split_and_arrange_move_no_bytes_at_the_summa_levels(on_the_mesh):
    """Interleaved quadrants stay on their device at depths 0 and 1; the
    one-block quadrants of depth 2 keep the contiguous split, whose
    reshards show that the search finds collectives."""
    assert on_the_mesh["layout_collective_levels"] == [2]


@pytest.fixture(scope="module")
def split_on_device():
    """`_split_on_device` of the n=512 grid on four devices, beside the
    interleaved index sets taken from the dense matrix."""
    (got,) = run_mesh(f"""
        import numpy as np
        import jax
        from jax.sharding import AxisType, Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P
        from repro.compat import set_mesh
        from repro.core import BlockMatrix
        from repro.core.testing import make_spd
        from repro.parallel.sharded_blockmatrix import (
            ShardedBlockMatrix, _arrange_on_device, _on_device_spec,
            _split_on_device)

        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        dense = make_spd({N}, jax.random.PRNGKey(5))
        spec = P("data", "model", None, None)
        blocks = jax.device_put(BlockMatrix.from_dense(dense, {BS}).blocks,
                                NamedSharding(mesh, spec))

        def split_arrange(x):
            a = ShardedBlockMatrix(x)
            quads = _split_on_device(a, _on_device_spec(a))
            back = _arrange_on_device(*quads, _on_device_spec(a))
            return tuple(q.blocks for q in quads), back.blocks

        with set_mesh(mesh):
            quads, back = jax.jit(split_arrange)(blocks)
        # Each device holds l = 4 block rows; the leading half is the
        # first l/2 of every device's rows.
        grid, d = {N} // {BS}, 2
        local = grid // d
        lead = [i * local + j for i in range(d) for j in range(local // 2)]
        trail = [i for i in range(grid) if i not in lead]
        ref = np.asarray(blocks)
        want = [ref[np.ix_(r, c)] for r in (lead, trail)
                for c in (lead, trail)]
        emit_result({{
            "quadrants_are_the_interleaved_sets": all(
                np.array_equal(np.asarray(q), w) for q, w in zip(quads, want)),
            "arrange_after_split_is_the_identity": bool(
                np.array_equal(np.asarray(back), ref)),
            "every_piece_stays_grid_sharded": all(
                x.sharding.spec[:2] == ("data", "model")
                for x in (*quads, back)),
        }})
    """, devices=4)
    return got


@pytest.mark.parametrize("check", [
    "quadrants_are_the_interleaved_sets",
    "arrange_after_split_is_the_identity",
    "every_piece_stays_grid_sharded",
])
def test_split_on_device(split_on_device, check):
    assert split_on_device[check] is True, split_on_device


@pytest.mark.parametrize("n,bs", [(4096, 512), (32768, 1024)])
def test_counters_at_size_under_an_abstract_mesh(n, bs):
    """Counted at trace time, so tracing under a (2, 2) mesh with no device
    behind it is enough: the benchmark's recorded size and the mesh cell's
    configuration (6.04e9 gathered bytes per device, 4.03e9 of them behind
    a GEMM step, 96 replicated GEMMs, 32 replicated leaves, 15 nodes split
    and arranged on the device)."""
    mesh = AbstractMesh(MESH, ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)
    grid = n // bs

    def inverse(blocks):
        with multiply_engine("pallas"):
            return sharded_spin_inverse(
                ShardedBlockMatrix(blocks).constrain(), "pallas").blocks

    with jax.sharding.use_abstract_mesh(mesh), count_ops() as counts:
        jax.eval_shape(inverse, jax.ShapeDtypeStruct((grid, grid, bs, bs),
                                                     jnp.float32))
    assert {c: getattr(counts, c) for c in COUNTERS} == closed_forms(
        n, bs, MESH)


@pytest.mark.parametrize("entry", ["dense", "sharded"])
def test_counters_are_zero_off_the_mesh(entry):
    """The recursions run op by op, so the counts never come from a jit
    cache that an earlier test filled."""
    a = make_spd(128, jax.random.PRNGKey(3))
    with count_ops() as counts, multiply_engine("pallas"):
        if entry == "dense":
            spin_inverse(BlockMatrix.from_dense(a, 32), leaf_solver="pallas")
        else:
            sharded_spin_inverse(ShardedBlockMatrix.from_dense(a, 32),
                                 "pallas")
    assert counts.block_gemms > 0 and counts.leaf_inversions == 4
    assert {c: getattr(counts, c) for c in COUNTERS} == dict.fromkeys(
        COUNTERS, 0)


def test_one_device_compile_has_no_gather_scope():
    (ops,) = inverse_op_scopes(256, 64, "pallas", "pallas").values()
    steps = {st for _, st in ops.values()}
    assert "gather" not in steps
    assert {"II", "schur", "leaf"} <= steps


SUMMA_BS = 32
SUMMA_LOCAL_K = (1, 2, 4, 8)
SUMMA_OPS = ("multiply", "schur_negate_c", "schur_keep_c")


@pytest.fixture(scope="module")
def summa_products():
    """On four devices, each Pallas SUMMA product (A·B, A·B − C, C − A·B)
    beside the product it replaces, one all-gather per operand and one
    kernel, at local k extents of 1, 2, 4 and 8 blocks; and the residual
    of a sharded Pallas solve."""
    (got,) = run_mesh(f"""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType, Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P
        from repro.compat import set_mesh, shard_map
        from repro.core import spin_solve_sharded
        from repro.core.multiply import (_gather_panels, multiply_blocks,
                                         schur_update_blocks)
        from repro.core.testing import make_spd
        from repro.kernels.matmul import ops as mm_ops

        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        spec = P("data", "model", None, None)

        def one_gather(c, a, b, alpha=None, beta=None):
            def local(c, a, b):
                a_full, b_full = _gather_panels(a, b, model_axis="model",
                                                data_axis="data")
                if alpha is None:
                    return mm_ops.grid_matmul(a_full, b_full)
                return mm_ops.grid_schur_update(c, a_full, b_full,
                                                alpha=alpha, beta=beta)
            return shard_map(local, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec, check_vma=False)(c, a, b)

        out = {{}}
        keys = iter(jax.random.split(jax.random.PRNGKey(11), 12))
        for local_k in {SUMMA_LOCAL_K}:
            h = 2 * local_k
            c, a, b = (jax.device_put(
                jax.random.normal(next(keys), (h, h, {SUMMA_BS}, {SUMMA_BS})),
                NamedSharding(mesh, spec)) for _ in range(3))
            with set_mesh(mesh):
                pairs = {{
                    "multiply": (multiply_blocks(a, b, "pallas"),
                                 one_gather(c, a, b)),
                    "schur_negate_c": (
                        schur_update_blocks(c, a, b, negate_c=True,
                                            engine="pallas"),
                        one_gather(c, a, b, 1.0, -1.0)),
                    "schur_keep_c": (
                        schur_update_blocks(c, a, b, negate_c=False,
                                            engine="pallas"),
                        one_gather(c, a, b, -1.0, 1.0)),
                }}
            for op, (x, want) in pairs.items():
                x, want = np.asarray(x), np.asarray(want)
                out[f"{{op}}/{{local_k}}"] = {{
                    "bitwise": bool(np.array_equal(x, want)),
                    "error": float(np.abs(x - want).max()
                                   / np.abs(want).max())}}

        n = 256
        a = make_spd(n, jax.random.PRNGKey(3))
        rhs = jax.random.normal(jax.random.PRNGKey(4), (n, 8))
        with set_mesh(mesh):
            x = spin_solve_sharded(a, rhs, {SUMMA_BS}, leaf_solver="pallas",
                                   engine="pallas")
        r = jnp.matmul(a, x, precision=jax.lax.Precision.HIGHEST) - rhs
        out["solve_residual"] = float(jnp.linalg.norm(r)
                                      / jnp.linalg.norm(rhs))
        emit_result(out)
    """, devices=4)
    return got


@pytest.mark.parametrize("op", SUMMA_OPS)
@pytest.mark.parametrize("local_k", SUMMA_LOCAL_K)
def test_pipelined_summa_equals_the_one_gather_product(summa_products,
                                                       local_k, op):
    """In one step the program is the one-gather product, so the result
    is too, bit for bit. In more, only the order of the f32 k-sum differs:
    over sums of 128 to 512 products of unit normals the CPU reads 3.3e-7
    to 1.2e-6 of the largest entry, growing with k; 1e-5 leaves eight
    times the largest, and a chunk paired with the wrong rows reads order
    1."""
    got = summa_products[f"{op}/{local_k}"]
    if summa_steps(local_k, MESH) == 1:
        assert got["bitwise"], got
    else:
        assert got["error"] < 1e-5, got


def test_sharded_pallas_solve_on_the_mesh(summa_products):
    """‖A X − B‖_F/‖B‖_F of the sharded Pallas solve, n = 256 at block 32:
    the CPU reads 2.5e-7; 2e-6 leaves eight times that."""
    assert 0 < summa_products["solve_residual"] < 2e-6, summa_products
