"""Device time by recursion level and step: a trace joined to the program's
named scopes.

The program names every operation of the SPIN recursion with a named scope:
`spin.L<k>` for the node at depth k, inside it one scope per step (`split`,
`II`, `III`, `schur`, `C12`, `C21`, `C11`, `neg`, `arrange`), `leaf` at a
leaf, and `spin.layout` for the dense entry's block layout (the program's
`repro.obs.trace`). A scope is HLO metadata, which a device trace does not
carry: its `XLA Ops` events hold only the instruction's text. So the join
goes through the compiled program: `repro.core.spin.inverse_op_scopes`
compiles the inversion the cell ran and maps each instruction name, by
module, to its (level, step); a fusion counts under its root's scope.

The program's entry points also open host spans (`spin.inverse_dense`,
`spin.inverse_sharded`) on the trace's clock; `xtrace.load` keeps only the
benchmark's own spans, so they are read here. Device idle time inside them
is time the host spent in the program's argument resolution and dispatch.

    python3 -m bench.scopes table TRACE SCOPES --calls N [--block B]
    python3 -m bench.scopes record --n N --block B --leaf L --engine E --out PREFIX

`table` prints device milliseconds per inversion by level and step for a
trace and its scope map (a `.scopes.json` written by `record`), with the
share of device time no scope covers and, given the block size, how the
scope-based GEMM and leaf times agree with the classes of `opclasses.json`.
`record` runs on the chip: it traces two inversions the way the benchmark's
closed loop does and writes `PREFIX.xplane.pb` and `PREFIX.scopes.json`.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import pathlib
import re
import shutil
import sys
import tempfile
from collections import defaultdict

from bench import xtrace

ENTRY_SPAN = "spin.inverse_"
LAYOUT = "spin.layout"
LAYOUT_STEPS = (LAYOUT, "split", "arrange", "neg")
GEMM_STEPS = ("II", "III", "schur", "C12", "C21", "C11")
INSTRUCTION = re.compile(r"^%(\S+) = ")


@dataclasses.dataclass
class ScopeTimes:
    """Device seconds in the traced window, summed over devices, by the
    (level, step) of each op; (None, None) is time no scope covers."""

    scope_s: dict[tuple, float]
    dispatch_idle_s: float | None    # mean over devices; None: no entry span
    class_scope_s: dict[tuple[str, str], float]   # (opclass, scope kind)

    @property
    def device_s(self) -> float:
        return sum(self.scope_s.values())

    @property
    def unscoped_s(self) -> float:
        return self.scope_s.get((None, None), 0.0)

    def level_s(self, level: int) -> float:
        return sum(s for (lv, _), s in self.scope_s.items() if lv == level)

    def steps_s(self, steps) -> float:
        return sum(s for (_, st), s in self.scope_s.items() if st in steps)


def kind(scope: tuple) -> str:
    """gemm, leaf, layout, other or unscoped: the scope's step, grouped."""
    level, step = scope
    if step in GEMM_STEPS:
        return "gemm"
    if step == "leaf":
        return "leaf"
    if step in LAYOUT_STEPS:
        return "layout"
    return "unscoped" if level is None else "other"


def entry_spans(path) -> list[xtrace.Op]:
    """The program's entry spans (`spin.inverse_*`) on the host planes."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    return [xtrace.Op(e.name, float(e.start_ns),
                      float(e.start_ns + e.duration_ns))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(ENTRY_SPAN)]


def reduce(trace: xtrace.Trace, spans: list[xtrace.Op], scopes: dict,
           block: int | None = None) -> ScopeTimes | None:
    """Join each op of the traced window to its scope; None when the trace
    holds no device operation or no window span. With `block`, also cross
    the scopes with the op classes of `opclasses.json`."""
    windows = [s for s in trace.spans if s.name == xtrace.WINDOW_SPAN]
    if not trace.ops or not windows:
        return None
    w = max(windows, key=lambda s: s.end_ns - s.start_ns)
    lo, hi = w.start_ns, w.end_ns
    rules = xtrace.load_rules() if block else None
    scope_s: dict[tuple, float] = defaultdict(float)
    class_scope_s: dict[tuple[str, str], float] = defaultdict(float)
    inside = xtrace.union(((s.start_ns, s.end_ns) for s in spans), lo, hi)
    idle = []
    for dev, ops in sorted(trace.ops.items()):
        ops = sorted(ops, key=lambda o: o.start_ns)
        module = xtrace._module_of(sorted(trace.modules.get(dev, []),
                                          key=lambda m: m.start_ns))
        classes = (xtrace.classify(ops, rules, block) if block
                   else [None] * len(ops))
        for o, cls in zip(ops, classes):
            d = (min(o.end_ns, hi) - max(o.start_ns, lo)) / 1e9
            if d <= 0:
                continue
            m = INSTRUCTION.match(o.name)
            scope = scopes.get(module(o.start_ns), {}).get(
                m.group(1) if m else "", (None, None))
            scope_s[scope] += d
            if cls:
                class_scope_s[cls, kind(scope)] += d
        busy = xtrace.union(((o.start_ns, o.end_ns) for o in ops), lo, hi)
        idle.append(xtrace.intersect_total(xtrace.gaps(busy, lo, hi),
                                           inside) / 1e9)
    return ScopeTimes(
        scope_s=dict(scope_s),
        dispatch_idle_s=sum(idle) / len(idle) if spans else None,
        class_scope_s=dict(class_scope_s))


def load_scopes(path) -> dict:
    """A scope map written by `record`: JSON lists back to tuples."""
    raw = json.loads(pathlib.Path(path).read_text())
    return {mod: {op: tuple(s) for op, s in ops.items()}
            for mod, ops in raw.items()}


def program_scopes(config: dict, chips: int) -> dict | None:
    """The scope map of the inversion program the cell ran, or None where
    the program names no scopes (a checkout older than the names)."""
    try:
        from repro.core.spin import inverse_op_scopes
    except ImportError:
        return None
    import jax

    from bench.common import mesh_for

    # Where the loops put the matrices: on the first chip, or on the mesh.
    devices = jax.devices()[:chips]
    mesh = mesh_for(config, devices)
    return inverse_op_scopes(
        int(config["n"]), int(config["block_size"]), config["leaf_solver"],
        config["engine"], mesh=mesh,
        sharding=jax.sharding.SingleDeviceSharding(devices[0]))


@functools.lru_cache(maxsize=2)
def _cell_times(trace_path: str, config_json: str,
                chips: int) -> ScopeTimes | None:
    scopes = program_scopes(json.loads(config_json), chips)
    if not scopes:
        return None
    return reduce(xtrace.load(trace_path), entry_spans(trace_path), scopes)


def cell_times(ctx) -> ScopeTimes | None:
    """The traced window of a `--trace 1` run joined to the scopes of the
    program it ran (read once for all the metrics that need it), or None
    where there is no trace or the program names no scopes."""
    root = getattr(ctx.cell, "root", None)
    found = root and xtrace.find_trace(root / ".bench_out" / "trace")
    if not found:
        return None
    return _cell_times(str(found), json.dumps(ctx.config, sort_keys=True),
                       int(ctx.chips))


def per_call_ms(ctx, seconds) -> float | None:
    calls = ctx.counters.get("calls_traced", 0)
    return None if seconds is None or not calls else 1000.0 * seconds / calls


# ---------------------------------------------------------------------------
# Command line
# ---------------------------------------------------------------------------


def table(times: ScopeTimes, calls: int) -> str:
    """Device ms per inversion by level (rows) and step (columns)."""
    present = {st for _, st in times.scope_s}
    steps = [s for s in (LAYOUT, "split", "II", "III", "schur", "C12",
                         "C21", "C11", "neg", "arrange", "leaf")
             if s in present]
    levels = sorted({lv for lv, _ in times.scope_s if lv is not None})
    rows = [["level", *steps, "total"]]
    for lv in [None, *levels]:
        cells = [times.scope_s.get((lv, s), 0.0) for s in steps]
        total = sum(cells)
        if total == 0:
            continue
        rows.append(["-" if lv is None else f"L{lv}",
                     *(f"{1000 * c / calls:.3f}" for c in cells),
                     f"{1000 * total / calls:.3f}"])
    out = ["| " + " | ".join(r) + " |" for r in rows]
    out.insert(1, "|" + "---|" * len(rows[0]))
    dev = times.device_s
    out.append("")
    out.append(f"device {1000 * dev / calls:.3f} ms per inversion; "
               f"unscoped {100 * times.unscoped_s / dev:.4f}% "
               f"({1000 * times.unscoped_s / calls:.4f} ms)")
    if times.dispatch_idle_s is not None:
        out.append(f"idle inside {ENTRY_SPAN}*: "
                   f"{1000 * times.dispatch_idle_s / calls:.4f} ms per "
                   "inversion")
    if times.class_scope_s:
        out.append("opclasses vs scopes (ms per inversion): " + ", ".join(
            f"{c}/{k} {1000 * s / calls:.3f}"
            for (c, k), s in sorted(times.class_scope_s.items())))
    return "\n".join(out)


def record(n: int, block: int, leaf: str, engine: str, out: str,
           calls: int = 2) -> None:
    """Trace `calls` inversions as the closed loop does, write the trace
    and the program's scope map next to each other, and print the table
    and the trace's ops that the map lacks (none, where the executable's
    instruction names are the trace's)."""
    import jax

    from bench import data
    from bench.common import span
    from repro.core import spin_inverse_dense

    a = data.spd_matrix(n, 0, 0, jax.sharding.SingleDeviceSharding(
        jax.devices()[0]))
    spin_inverse_dense(a, block, leaf, engine=engine).block_until_ready()
    scopes = program_scopes({"n": n, "block_size": block, "leaf_solver": leaf,
                             "engine": engine}, 1)
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        with span(xtrace.WINDOW_SPAN):
            for _ in range(calls):
                with span("bench.offline"):
                    spin_inverse_dense(a, block, leaf,
                                       engine=engine).block_until_ready()
        jax.profiler.stop_trace()
        found = xtrace.find_trace(tmp)
        shutil.copy(found, f"{out}.xplane.pb")
    trace = xtrace.load(f"{out}.xplane.pb")
    (program,) = scopes.values()
    names = [m.group(1) for ops in trace.ops.values() for o in ops
             if (m := INSTRUCTION.match(o.name))]
    missing = sorted({name for name in names if name not in program})
    pathlib.Path(f"{out}.scopes.json").write_text(json.dumps(
        {mod: {op: list(s) for op, s in ops.items()}
         for mod, ops in scopes.items()}, separators=(",", ":")))
    times = reduce(trace, entry_spans(f"{out}.xplane.pb"), scopes, block)
    print(table(times, calls) if times else "the trace holds no device op")
    print(f"trace ops {len(names)}, of which not in the scope map: "
          f"{len(missing)} {missing[:10]}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = p.add_subparsers(dest="cmd", required=True)
    t = sub.add_parser("table")
    t.add_argument("trace")
    t.add_argument("scopes")
    t.add_argument("--calls", type=int, required=True)
    t.add_argument("--block", type=int)
    r = sub.add_parser("record")
    r.add_argument("--n", type=int, required=True)
    r.add_argument("--block", type=int, required=True)
    r.add_argument("--leaf", required=True)
    r.add_argument("--engine", required=True)
    r.add_argument("--out", required=True)
    args = p.parse_args(argv)
    if args.cmd == "table":
        times = reduce(xtrace.load(args.trace), entry_spans(args.trace),
                       load_scopes(args.scopes), args.block)
        print(table(times, args.calls))
    else:
        from bench import env

        env.setup(pathlib.Path(__file__).resolve().parents[1])
        record(args.n, args.block, args.leaf, args.engine, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
