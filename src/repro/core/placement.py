"""Where a block grid lives on a device mesh: the one grid-over-mesh rule.

A (b, b, bs, bs) block grid lies on a mesh's (data, model) axes, block
rows over `data` and block columns over `model`:

    grid (g_r, g_c) blocks  ->  P(data if g_r % |data| == 0 else None,
                                  model if g_c % |model| == 0 else None,
                                  None, None)

i.e. a grid stays fully sharded as long as it still covers each mesh axis;
where it no longer divides one, that axis degrades to replicated. A dense
(rows, k) solve panel shards its rows over `data` under the same rule.
Off a mesh nothing is constrained.

The mesh container (`repro.parallel.ShardedBlockMatrix`), the SUMMA
engines (`core.multiply`), the Strassen engine and the SMW updates all
place what they produce through this module. Every constraint it issues is
also recorded in a trace-time *spec ledger* (`record_specs`), which is how
tests assert the no-replication property from the jaxpr rather than
trusting a docstring: each `with_sharding_constraint` issued here appears
once in the ledger and once as a `sharding_constraint` eqn in the lowered
program.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Iterator

import jax
from jax.sharding import PartitionSpec as P

from repro import compat

__all__ = ["DEFAULT_AXES", "active_mesh", "mesh_axes", "grid_spec",
           "panel_spec", "mesh_fingerprint", "constrain_grid",
           "constrain_panel", "SpecRecord", "record_specs",
           "assert_mesh_resident"]

DEFAULT_AXES = ("data", "model")


def active_mesh():
    """The ambient mesh, or None where there is none (or it is empty)."""
    mesh = compat.get_abstract_mesh()
    return mesh if mesh is not None and mesh.shape else None


def mesh_axes(mesh) -> tuple[str, str]:
    """The (data, model) axis names of `mesh`: those names where it has
    them, else its first and its last axis."""
    names = list(mesh.shape.keys())
    return ("data" if "data" in names else names[0],
            "model" if "model" in names else names[-1])


def grid_spec(grid_rows: int, grid_cols: int, mesh,
              axes: tuple[str, str] = DEFAULT_AXES) -> P:
    """Divisibility-aware grid-over-mesh spec for a (gr, gc, bs, bs) array."""
    shape = dict(mesh.shape)
    d, m = axes
    row = d if d in shape and grid_rows % shape[d] == 0 else None
    col = m if m in shape and grid_cols % shape[m] == 0 else None
    return P(row, col, None, None)


def panel_spec(rows: int, mesh, axes: tuple[str, str] = DEFAULT_AXES) -> P:
    """Row-sharding spec for a dense (rows, k) solve panel."""
    d = axes[0]
    shape = dict(mesh.shape)
    row = d if d in shape and rows % shape[d] == 0 else None
    return P(row, None)


def mesh_fingerprint(mesh=None) -> str:
    """Canonical string for the ambient mesh, e.g. "data2:model2" ("" = none).

    Used (a) as the static jit-cache key component of the sharded programs
    and (b) by the planner's ProblemSignature as its mesh dimension. It is
    topology only: the constraints name mesh AXES, and jit places each
    compiled program on the devices of the concrete mesh that `set_mesh`
    installed, so two same-topology meshes over different devices share a
    trace but never a device assignment (DESIGN.md §6).
    """
    if mesh is None:
        mesh = compat.get_abstract_mesh()
    if mesh is None or not mesh.shape:
        return ""
    return ":".join(f"{k}{v}" for k, v in mesh.shape.items())


# ---------------------------------------------------------------------------
# Spec ledger: what was constrained, recorded at trace time.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SpecRecord:
    """One with_sharding_constraint issued through this module."""

    op: str                                  # producing op ("split", "multiply", …)
    kind: str                                # "grid" (b,b,bs,bs) | "panel" (n,k)
    shape: tuple[int, ...]                   # array shape at the constraint
    spec: tuple | None                       # P as a tuple, None if skipped
    axes: tuple[str, str]                    # intended (data, model) axis names
    mesh_axes: tuple[tuple[str, int], ...]   # mesh shape at trace time

    @property
    def grid_sharded(self) -> bool:
        """Both grid axes mapped to mesh axes (nothing replicated)."""
        return (self.spec is not None and self.spec[0] is not None
                and self.spec[1] is not None)


_LEDGER: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "sharded_blockmatrix_spec_ledger", default=None
)


@contextlib.contextmanager
def record_specs() -> Iterator[list[SpecRecord]]:
    """Collect every sharding constraint issued here (trace-time).

    Like `count_ops`, records only accumulate while something is actually
    tracing/executing the ops — a jit cache hit replays the compiled
    program and records nothing.
    """
    records: list[SpecRecord] = []
    token = _LEDGER.set(records)
    try:
        yield records
    finally:
        _LEDGER.reset(token)


def _record(op: str, kind: str, shape: tuple[int, ...], spec,
            axes: tuple[str, str], mesh) -> None:
    ledger = _LEDGER.get()
    if ledger is None:
        return
    mesh_axes = (tuple(sorted(dict(mesh.shape).items()))
                 if mesh is not None else ())
    ledger.append(SpecRecord(op=op, kind=kind, shape=tuple(shape),
                             spec=None if spec is None else tuple(spec),
                             axes=axes, mesh_axes=mesh_axes))


def assert_mesh_resident(records: list[SpecRecord],
                         min_records: int = 1) -> dict[str, int]:
    """Assert the ledger shows a mesh-resident recursion; return a tally.

    Every grid record whose grid axes are divisible by the mesh MUST have
    been constrained onto both mesh axes, and every panel record with a
    data-divisible row count must be row-sharded — i.e. no intermediate
    that *could* stay distributed was left for the partitioner to
    replicate. Returns {"total", "grid_sharded", "panel_sharded",
    "partial"} counts ("grid_sharded" counts grid records only).
    """
    if len(records) < min_records:
        raise AssertionError(
            f"expected >= {min_records} sharding records, got {len(records)} "
            "(was the program served from the jit cache?)")
    bad = []
    tally = {"total": len(records), "grid_sharded": 0, "panel_sharded": 0,
             "partial": 0}
    for r in records:
        sizes = dict(r.mesh_axes)
        d_size = sizes.get(r.axes[0], 0)
        m_size = sizes.get(r.axes[1], 0)
        if r.kind == "grid":
            resident = r.grid_sharded
            expect = (d_size and m_size and r.shape[0] % d_size == 0
                      and r.shape[1] % m_size == 0)
            bucket = "grid_sharded"
        else:                                   # panel: rows over data only
            resident = r.spec is not None and r.spec[0] is not None
            expect = bool(d_size) and r.shape[0] % d_size == 0
            bucket = "panel_sharded"
        tally[bucket if resident else "partial"] += 1
        if expect and not resident:
            bad.append(r)
    if bad:
        raise AssertionError(
            "mesh-divisible intermediates were not grid-sharded "
            f"(replication leak): {bad[:5]}")
    return tally


# ---------------------------------------------------------------------------
# Constraints
# ---------------------------------------------------------------------------


def constrain_grid(blocks: jax.Array, op: str,
                   axes: tuple[str, str] | None = None) -> jax.Array:
    """Re-assert the grid rule on a freshly produced grid, and record it.

    `op` names the producing step in the ledger. axes=None takes the mesh's
    own (data, model) names (`mesh_axes`).
    """
    mesh = active_mesh()
    if mesh is None:
        _record(op, "grid", blocks.shape, None, axes or DEFAULT_AXES, None)
        return blocks
    axes = axes or mesh_axes(mesh)
    spec = grid_spec(blocks.shape[0], blocks.shape[1], mesh, axes)
    blocks = jax.lax.with_sharding_constraint(blocks, spec)
    _record(op, "grid", blocks.shape, spec, axes, mesh)
    return blocks


def constrain_panel(x: jax.Array, op: str,
                    axes: tuple[str, str] = DEFAULT_AXES) -> jax.Array:
    """Re-assert the panel rule on a dense (rows, k) panel, and record it."""
    mesh = active_mesh()
    if mesh is None:
        _record(op, "panel", x.shape, None, axes, None)
        return x
    spec = panel_spec(x.shape[0], mesh, axes)
    x = jax.lax.with_sharding_constraint(x, spec)
    _record(op, "panel", x.shape, spec, axes, mesh)
    return x
