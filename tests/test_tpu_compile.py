"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel at a real width for one chip of a
described `v5e:2x2` topology and compiles it with the TPU compiler, which
refuses what interpret mode accepts (unaligned slices, unlowerable
primitives, more VMEM than the kernel may scope). Each kernel must come
out as a Mosaic `tpu_custom_call`. The leaf kernels compile at the largest
block size the planner may offer them on a TPU signature
(`kernel.max_block_size`), so a plan the planner offers is a plan that
compiles.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.leaf_inverse import kernel as leaf
from repro.kernels.matmul import kernel as mm


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out of the cache.
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *shapes, sharding, dtype=jnp.float32) -> str:
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_schur_update_compiles_at_2048(one_chip):
    n = 2048
    text = _compiled_text(
        lambda c, a, b: mm.schur_update_pallas(
            c, a, b, tiles=mm.auto_tiles(n, n, n)),
        (n, n), (n, n), (n, n), sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fn,n,dtype,name", [
    (lambda a, b: mm.matmul_pallas(a, b), 8192, jnp.float32,
     "matmul_pallas"),
    (lambda c, a, b: mm.schur_update_pallas(c, a, b), 8192, jnp.float32,
     "schur_update_pallas"),
    (lambda c, a, b: mm.schur_update_pallas(c, a, b, out_dtype=jnp.float32),
     4096, jnp.bfloat16, "schur_update_pallas"),
])
def test_gemm_kernels_compile_with_their_default_tiles(fn, n, dtype, name,
                                                       one_chip, monkeypatch):
    """The tile rule's choice at the recursion's largest product compiles
    with no more VMEM than its reckoning: the limit here is the reckoning
    alone, without the margin the kernels ask for, since a kernel that XLA
    fuses into a consumer gets only the budget."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(mm, "vmem_compiler_params", lambda v, sem: (
        pltpu.CompilerParams(dimension_semantics=sem, vmem_limit_bytes=v)))
    jax.clear_caches()      # no kernel traced under the usual limit
    shapes = [(n, n)] * (fn.__code__.co_argcount)
    text = _compiled_text(fn, *shapes, sharding=one_chip, dtype=dtype)
    assert _kernel_names(text) == {name}


@pytest.mark.parametrize("kernel,fn", [
    ("gauss_jordan", leaf.leaf_inverse_pallas),
    ("pallas", leaf.blocked_leaf_inverse_pallas),
])
def test_leaf_kernel_compiles_at_planner_max(kernel, fn, one_chip):
    bs = leaf.max_block_size(kernel)
    assert bs >= 1024
    text = _compiled_text(fn, (1, bs, bs), sharding=one_chip)
    assert "tpu_custom_call" in text


def test_blocked_leaf_is_one_kernel_at_the_refresh_block(one_chip):
    """At the K-FAC refresh cell's block, 256, the unrolled panel sweep
    compiles to one Mosaic kernel per leaf, under the name the benchmark's
    leaf marker matches."""
    text = _compiled_text(leaf.blocked_leaf_inverse_pallas, (1, 256, 256),
                          sharding=one_chip)
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1
    assert _kernel_names(text) == {"blocked_leaf_inverse_pallas"}


def test_triangular_solve_compiles(one_chip):
    text = _compiled_text(leaf.triangular_solve_pallas, (1, 512, 512),
                          (1, 512, 64), sharding=one_chip)
    assert "tpu_custom_call" in text


def test_triangular_solve_compiles_at_planner_max_with_wide_rhs(one_chip):
    # The recursion's leaf solve sees up to ~n right-hand-side columns;
    # they are tiled, so only the T block scales the VMEM bill.
    bs = leaf.max_block_size("triangular_solve")
    text = _compiled_text(leaf.triangular_solve_pallas, (1, bs, bs),
                          (1, bs, 1000), sharding=one_chip)
    assert "tpu_custom_call" in text


def test_sharded_pallas_recursion_compiles_on_a_four_chip_mesh(
        topo, monkeypatch):
    """The mesh-resident recursion with the Pallas engine and leaf, for a
    described (2, 2) v5e mesh. The TPU lowering refuses a Mosaic kernel in
    the automatically partitioned part of a mesh program, so the leaf
    inversions and the multiplies whose grid no longer divides the mesh
    must run inside a shard_map (`kernels.mesh_safe`)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.compat import set_mesh
    from repro.kernels.leaf_inverse import ops as leaf_ops
    from repro.kernels.matmul import ops as mm_ops
    from repro.parallel.sharded_blockmatrix import (_inverse_program,
                                                    mesh_fingerprint)

    # The wrappers ask the (CPU) backend whether to interpret: compile.
    monkeypatch.setattr(leaf_ops, "pallas_interpret_default", lambda: False)
    monkeypatch.setattr(mm_ops, "pallas_interpret_default", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    grid, bs = 4, 512
    blocks = jax.ShapeDtypeStruct(
        (grid, grid, bs, bs), jnp.float32,
        sharding=NamedSharding(mesh, P("data", "model", None, None)))
    with set_mesh(mesh):
        text = _inverse_program.lower(
            blocks, "pallas", "pallas", ("data", "model"),
            mesh_fingerprint()).compile().as_text()
    assert "tpu_custom_call" in text


def test_mesh_summa_swaps_each_step_behind_the_kernels(topo, monkeypatch):
    """An L0-shaped product of the mesh cell, a 16×16 grid of 1024 blocks
    on the (2, 2) mesh (8 local k blocks, so 4 steps), compiled for a
    described v5e:2x2. Each step swaps one A and one B chunk, each by its
    own collective-permute: none merged, no all-gather left. In the
    scheduled program every swap but the first step's is started before a
    Pallas kernel and awaited after it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.compat import set_mesh
    from repro.core.multiply import multiply_blocks
    from repro.kernels.matmul import ops as mm_ops

    monkeypatch.setattr(mm_ops, "pallas_interpret_default", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    grid, bs, steps = 16, 1024, 4
    x = jax.ShapeDtypeStruct(
        (grid, grid, bs, bs), jnp.float32,
        sharding=NamedSharding(mesh, P("data", "model", None, None)))
    with set_mesh(mesh):
        text = jax.jit(lambda a, b: multiply_blocks(a, b, "pallas")).lower(
            x, x).compile().as_text()
    entry = text[text.index("\nENTRY"):].splitlines()
    started, overlapped, kernels = {}, 0, 0
    for line in entry:
        name = re.match(r"\s*(?:ROOT )?%(\S+) = ", line)
        if name is None:
            continue
        if 'custom_call_target="tpu_custom_call"' in line:
            kernels += 1
        elif " collective-permute-start(" in line:
            started[name.group(1)] = kernels
        elif done := re.search(r" collective-permute-done\(%(\S+?)\)",
                               line):
            overlapped += kernels > started[done.group(1)]
    assert "all-gather" not in "\n".join(entry)
    assert len(started) == 2 * steps
    assert overlapped == 2 * (steps - 1)


def test_pallas_recursion_compiles_with_its_rule_tiles(one_chip,
                                                      monkeypatch):
    """The one-chip recursion at products of 2048 and 1024, where the tile
    rule reaches its largest tiles. XLA fuses a kernel's output into the
    in-place arrange that consumes it, and a fused kernel is held to the
    default scoped VMEM whatever it asks: the rule's budget must hold."""
    from repro.core.spin import _spin_inverse_dense
    from repro.kernels.leaf_inverse import ops as leaf_ops
    from repro.kernels.matmul import ops as mm_ops

    monkeypatch.setattr(leaf_ops, "pallas_interpret_default", lambda: False)
    monkeypatch.setattr(mm_ops, "pallas_interpret_default", lambda: False)
    x = jax.ShapeDtypeStruct((4096, 4096), jnp.float32, sharding=one_chip)
    text = _spin_inverse_dense.lower(x, 1024, "pallas", "pallas").compile(
        ).as_text()
    assert _kernel_names(text) == {"matmul_pallas", "schur_update_pallas",
                                   "blocked_leaf_inverse_pallas"}


def _kernel_names(text: str) -> set[str]:
    """The instruction names of the Mosaic kernels, less their `.N`."""
    return {re.match(r"\s*(?:ROOT )?%([\w-]+?)(?:\.\d+)? = ", line).group(1)
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line}


@pytest.mark.parametrize("fn,shapes,name", [
    (lambda a, b: mm.matmul_pallas(a, b), [(1024, 1024)] * 2,
     "matmul_pallas"),
    (lambda c, a, b: mm.schur_update_pallas(c, a, b), [(1024, 1024)] * 3,
     "schur_update_pallas"),
    (lambda x: leaf.leaf_inverse_pallas(x), [(1, 256, 256)],
     "leaf_inverse_pallas"),
    (lambda x: leaf.blocked_leaf_inverse_pallas(x), [(1, 1024, 1024)],
     "blocked_leaf_inverse_pallas"),
    (lambda t, b: leaf.triangular_solve_pallas(t, b),
     [(1, 512, 512), (1, 512, 64)], "triangular_solve_pallas"),
])
def test_pallas_kernels_keep_their_pinned_names(fn, shapes, name, one_chip):
    """Each `pallas_call` passes `name=`: a trace names the kernel after it,
    whatever function wraps it."""
    assert _kernel_names(_compiled_text(fn, *shapes,
                                        sharding=one_chip)) == {name}


def test_pallas_recursion_names_its_kernels_by_step(one_chip, monkeypatch):
    """In the recursion compiled for one chip, the kernels carry the pinned
    names that `bench/opclasses.json` matches, and the step scopes they
    were called in."""
    import json
    import pathlib

    from repro.core.spin import _spin_inverse_dense
    from repro.kernels.leaf_inverse import ops as leaf_ops
    from repro.kernels.matmul import ops as mm_ops
    from repro.obs.trace import hlo_op_scopes

    monkeypatch.setattr(leaf_ops, "pallas_interpret_default", lambda: False)
    monkeypatch.setattr(mm_ops, "pallas_interpret_default", lambda: False)
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32, sharding=one_chip)
    text = _spin_inverse_dense.lower(x, 256, "pallas", "pallas").compile(
        ).as_text()
    (ops,) = hlo_op_scopes(text).values()
    steps: dict[str, set] = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.match(r"\s*(?:ROOT )?%(\S+) = ", line).group(1)
            steps.setdefault(name.split(".")[0], set()).add(ops[name])
    assert steps == {
        "matmul_pallas": {(lv, st) for lv in (0, 1)
                          for st in ("II", "III", "C12", "C21")},
        "schur_update_pallas": {(lv, st) for lv in (0, 1)
                                for st in ("schur", "C11")},
        "blocked_leaf_inverse_pallas": {(2, "leaf")},
    }
    classes = json.loads((pathlib.Path(__file__).resolve().parents[1]
                          / "bench" / "opclasses.json").read_text())
    gemm = re.compile(classes["gemm"][1]["pattern"])
    leaf_marker = re.compile(classes["leaf_marker"][1]["pattern"])
    kernels = [line.strip() for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert all(gemm.search(k) or leaf_marker.search(k) for k in kernels)


def test_batched_pallas_refresh_compiles_and_fits_one_chip(one_chip,
                                                           monkeypatch):
    """The K-FAC refresh cell's program: Granite-8B-Code's (36, 4096, 4096)
    factor stack through `spin_inverse_batched` at block 256, the Pallas
    engine and leaf inside the scan. Its own buffers (the stack in, the
    inverses out and the temporaries) fit a 16 GB chip."""
    from repro.core.solve import _spin_inverse_batched
    from repro.kernels.leaf_inverse import ops as leaf_ops
    from repro.kernels.matmul import ops as mm_ops

    monkeypatch.setattr(leaf_ops, "pallas_interpret_default", lambda: False)
    monkeypatch.setattr(mm_ops, "pallas_interpret_default", lambda: False)
    x = jax.ShapeDtypeStruct((36, 4096, 4096), jnp.float32, sharding=one_chip)
    compiled = _spin_inverse_batched.lower(x, 256, "pallas",
                                           "pallas").compile()
    assert _kernel_names(compiled.as_text()) == {
        "matmul_pallas", "schur_update_pallas", "blocked_leaf_inverse_pallas"}
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16e9, total
