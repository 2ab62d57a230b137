"""One SPIN recursion for every placement of the blocks.

`core.recursion.invert` walks a node through the container's own
operations, so a `BlockMatrix` and a mesh-resident `ShardedBlockMatrix`
must book the same Algorithm-2 operations at every (level, step) and
trace the same named scopes, off a mesh and on a (2, 2) mesh. The mesh
container differs only in its placement hooks: where it puts each product,
how it splits a node (interleaved quadrants where the grid divides the
mesh) and whether the Schur steps fuse. The SUMMA `gather` step is left
out of the scope sets: it belongs to the multiply engine under a mesh.

The layering checks hold the grid-over-mesh rule and the leaf registry to
one home in `core` each.
"""

import ast
import pathlib

import pytest

from tests.mesh_harness import run_mesh

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
N, BS = 256, 32                                    # grid 8: levels 0-3
ENGINES = {"einsum": "linalg", "allgather": "linalg", "pallas": "pallas"}


@pytest.fixture(scope="module")
def walks():
    """For each placement and engine, each container's Algorithm-2 counts
    by (level, step) and the (level, step) scopes of its traced program,
    from one trace of the recursion on four devices."""
    (got,) = run_mesh(f"""
        import collections
        import contextlib
        import numpy as np
        import jax
        from jax.extend import source_info_util
        from jax.sharding import AxisType, Mesh
        from repro.compat import set_mesh
        from repro.core import BlockMatrix, OpCounts, spin_inverse
        from repro.core.blockmatrix import _COUNTS
        from repro.core.multiply import multiply_engine
        from repro.core.testing import make_spd
        from repro.obs.trace import op_scope
        from repro.parallel import ShardedBlockMatrix, sharded_spin_inverse

        ALG2 = ("multiplies", "block_gemms", "subtracts", "scalar_muls",
                "leaf_inversions", "splits", "arranges")

        class ByScope(OpCounts):
            # Books each counter's increments under the innermost
            # (level, step) scope open when it was bumped.
            def __setattr__(self, field, value):
                by = value - getattr(self, field)
                if by:
                    lv, st = op_scope(
                        str(source_info_util.current_name_stack()) + "/op")
                    self.__dict__.setdefault("booked", collections.Counter())[
                        f"{{lv}}/{{st}}/{{field}}"] += by
                super().__setattr__(field, value)

        def scopes(jaxpr, prefix=""):
            out = set()
            for eqn in jaxpr.eqns:
                stack = prefix + str(eqn.source_info.name_stack) + "/"
                out.add(op_scope(stack + eqn.primitive.name))
                for param in eqn.params.values():
                    for sub in param if isinstance(param, (list, tuple)) \\
                            else [param]:
                        inner = getattr(sub, "jaxpr", sub)
                        if hasattr(inner, "eqns"):
                            out |= scopes(inner, stack)
            return out

        containers = {{
            "BlockMatrix": lambda x, leaf: spin_inverse(
                BlockMatrix(x), leaf_solver=leaf).blocks,
            "ShardedBlockMatrix": lambda x, leaf: sharded_spin_inverse(
                ShardedBlockMatrix(x).constrain(), leaf).blocks,
        }}
        blocks = BlockMatrix.from_dense(
            make_spd({N}, jax.random.PRNGKey(0)), {BS}).blocks
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        result = {{}}
        for placement in ("off_mesh", "mesh_2x2"):
            for engine, leaf in {ENGINES!r}.items():
                case = result[f"{{placement}}-{{engine}}"] = {{}}
                for name, fn in containers.items():
                    counts = ByScope()
                    token = _COUNTS.set(counts)
                    try:
                        with (set_mesh(mesh) if placement == "mesh_2x2"
                              else contextlib.nullcontext()), \\
                                multiply_engine(engine):
                            jaxpr = jax.make_jaxpr(
                                lambda x: fn(x, leaf))(blocks).jaxpr
                    finally:
                        _COUNTS.reset(token)
                    booked = counts.__dict__.get("booked", {{}})
                    case[name] = {{
                        "counts": {{k: v for k, v in booked.items()
                                   if k.split("/")[2] in ALG2}},
                        "scopes": sorted(f"{{lv}}/{{st}}" for lv, st
                                         in scopes(jaxpr)
                                         if lv is not None
                                         and st not in (None, "gather")),
                        "local_splits": counts.local_splits,
                    }}
        emit_result(result)
    """, devices=4)
    return got


CASES = [f"{p}-{e}" for p in ("off_mesh", "mesh_2x2") for e in ENGINES]


@pytest.mark.parametrize("case", CASES)
def test_one_walk_books_and_names_alike_on_both_containers(walks, case):
    plain, mesh = walks[case]["BlockMatrix"], walks[case]["ShardedBlockMatrix"]
    assert plain["counts"] == mesh["counts"]
    assert plain["scopes"] == mesh["scopes"]
    # Every level of the grid-8 recursion, its nodes' steps and its leaves.
    assert {s.split("/")[0] for s in plain["scopes"]} == {"0", "1", "2", "3"}
    assert {"2/schur", "2/arrange", "3/leaf"} <= set(plain["scopes"])
    assert plain["counts"]["0/II/multiplies"] == 1
    assert plain["counts"]["3/leaf/leaf_inversions"] == 8
    # On the mesh the sharded container took its own split at depths 0, 1.
    assert mesh["local_splits"] == (3 if case.startswith("mesh") else 0)


# ---------------------------------------------------------------- layering


def _imports(package: str):
    """(file, module, [(name, asname)], tree) for each import statement
    under src/repro/<package>, relative modules made absolute; a plain
    `import` has name None."""
    for path in sorted((SRC / package).rglob("*.py")):
        tree = ast.parse(path.read_text())
        pkg = ["repro", *path.relative_to(SRC).parts[:-1]]
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                base = pkg[:len(pkg) - node.level + 1] if node.level else []
                module = ".".join(base + [node.module] if node.module
                                  else base)
                yield path, module, [(a.name, a.asname)
                                     for a in node.names], tree
            elif isinstance(node, ast.Import):
                for a in node.names:
                    yield path, a.name, [(None, a.asname)], tree


def _private_from_parallel(tree, module, names) -> list[str]:
    """Underscore names taken from repro.parallel: imported by name, or
    read off an imported parallel module."""
    found = []
    for name, asname in names:
        target = module if name is None else f"{module}.{name}"
        if not target.startswith("repro.parallel"):
            continue
        if name and name.startswith("_"):
            found.append(target)
        alias = asname or name
        found += [f"{target}.{node.attr}" for node in ast.walk(tree)
                  if isinstance(node, ast.Attribute)
                  and isinstance(node.value, ast.Name)
                  and node.value.id == alias and node.attr.startswith("_")]
    return found


def _core_takes_private_names_from_parallel():
    return [f"{path.name}: {name}"
            for path, module, names, tree in _imports("core")
            for name in _private_from_parallel(tree, module, names)]


def _parallel_imports_core_spin():
    return [f"{path.name}: {module} {names}"
            for path, module, names, _ in _imports("parallel")
            if module == "repro.core.spin"
            or (module == "repro.core"
                and any(n == "spin" for n, _ in names))]


def _leaf_registry_from_elsewhere():
    return [f"{path.name}: {module}"
            for package in ("core", "parallel", "planner", "serving")
            for path, module, names, _ in _imports(package)
            if any(n == "LEAF_SOLVERS" for n, _ in names)
            and module != "repro.core.leaf"]


@pytest.mark.parametrize("rule", [
    _core_takes_private_names_from_parallel,
    _parallel_imports_core_spin,
    _leaf_registry_from_elsewhere,
], ids=lambda rule: rule.__name__.strip("_"))
def test_layering(rule):
    """core takes no private name from parallel (the placement rule lives
    in core.placement), parallel does not reach up into core.spin, and the
    leaf registry has one home (core.leaf) every user imports."""
    assert rule() == []
