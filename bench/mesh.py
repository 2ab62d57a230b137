"""What the mesh cell adds to the reduction of a trace: which recursion
levels run replicated, collective time that no compute hides, the level and
step table with the SUMMA `gather` step, and a four-chip recording.

On a (d, m) mesh the recursion's products run as SUMMA (each device
gathers its panels, then multiplies its own share) while a node's
quadrants divide the mesh; below that level every device computes each
product and each leaf whole (`replicated_levels`). The program names its
gathers `spin.L<k>/<step>/gather` (`repro.obs.trace`); `bench/scopes.py`
joins the trace to those names.

    python3 -m bench.mesh record --n N --block B --out PREFIX
    python3 -m bench.mesh table TRACE SCOPES --calls N --block B

`record` runs on four chips: it traces `--calls` inversions on the (2, 2)
mesh with the Pallas engine and leaf, the way the closed loop does, and
writes `PREFIX.xplane.pb` (only what the readers read: no HLO protos, no
per-op source stacks) and `PREFIX.scopes.json`. `table` prints device ms
per inversion by level and step, `gather` included, with the unscoped
share and the exposed collective time.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import tempfile

from bench import scopes, xtrace

RECORD_CONFIG = {"leaf_solver": "pallas", "engine": "pallas",
                 "mesh": {"shape": [2, 2], "axes": ["data", "model"]}}
STEP_ORDER = (scopes.LAYOUT, "split", "II", "III", "schur", "C12", "C21",
              "C11", "neg", "arrange", "leaf", "gather")


def mesh_shape(config: dict) -> tuple[int, ...] | None:
    """The configuration's mesh shape, or None for one chip."""
    spec = config.get("mesh")
    return tuple(int(s) for s in spec["shape"]) if spec else None


def replicated_levels(n: int, block_size: int, shape) -> list[int]:
    """Depths whose node's quadrants no longer divide the mesh (`shape`,
    rows over its first axis, columns over its last), so that every device
    computes their products whole; the leaf depth, whose single block every
    device inverts, is the last of them."""
    grid = n // block_size
    depth = grid.bit_length() - 1
    return [k for k in range(depth + 1)
            if (grid >> k) == 1
            or (grid >> (k + 1)) % shape[0] or (grid >> (k + 1)) % shape[-1]]


def exposed_collective_s(trace: xtrace.Trace, block: int) -> float | None:
    """Seconds in the traced window, mean over devices, in which a device
    ran a collective op (the `collective` class of `opclasses.json`) and no
    op of another class; None without device ops or a window span."""
    windows = [s for s in trace.spans if s.name == xtrace.WINDOW_SPAN]
    if not trace.ops or not windows:
        return None
    w = max(windows, key=lambda s: s.end_ns - s.start_ns)
    rules = xtrace.load_rules()
    exposed = []
    for ops in trace.ops.values():
        ops = sorted(ops, key=lambda o: o.start_ns)
        classes = xtrace.classify(ops, rules, block)
        coll = xtrace.union(((o.start_ns, o.end_ns) for o, c in
                             zip(ops, classes) if c == "collective"),
                            w.start_ns, w.end_ns)
        other = xtrace.union(((o.start_ns, o.end_ns) for o, c in
                              zip(ops, classes) if c != "collective"),
                             w.start_ns, w.end_ns)
        exposed.append((xtrace.total(coll)
                        - xtrace.intersect_total(coll, other)) / 1e9)
    return sum(exposed) / len(exposed)


def cell_trace(ctx) -> xtrace.Trace | None:
    """The traced window of a mesh cell's `--trace 1` run, or None: one
    chip, no trace, or a program that names no scopes."""
    if int(ctx.chips) < 2 or mesh_shape(ctx.config) is None:
        return None
    if scopes.cell_times(ctx) is None:
        return None
    found = xtrace.find_trace(ctx.cell.root / ".bench_out" / "trace")
    return xtrace.load(found) if found else None


# ---------------------------------------------------------------------------
# The recording and the table
# ---------------------------------------------------------------------------


def prune(src, dst) -> None:
    """Copy an `.xplane.pb` without the HLO protos (`/host:metadata`) and
    the per-op statistics (source stacks and the like) of the device
    planes, which no reader reads; every other field is kept as it is."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    f = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xplane_prune.proto",
                                            package="bench_prune")
    meta = fd.message_type.add(name="XEventMetadata")
    meta.field.add(name="stats", number=5, type=f.TYPE_BYTES,
                   label=f.LABEL_REPEATED)
    plane = fd.message_type.add(name="XPlane")
    plane.field.add(name="name", number=2, type=f.TYPE_STRING,
                    label=f.LABEL_OPTIONAL)
    entry = plane.nested_type.add(
        name="EventMetadataEntry",
        options=descriptor_pb2.MessageOptions(map_entry=True))
    entry.field.add(name="key", number=1, type=f.TYPE_INT64,
                    label=f.LABEL_OPTIONAL)
    entry.field.add(name="value", number=2, type=f.TYPE_MESSAGE,
                    label=f.LABEL_OPTIONAL,
                    type_name=".bench_prune.XEventMetadata")
    plane.field.add(name="event_metadata", number=4, type=f.TYPE_MESSAGE,
                    label=f.LABEL_REPEATED,
                    type_name=".bench_prune.XPlane.EventMetadataEntry")
    space = fd.message_type.add(name="XSpace")
    space.field.add(name="planes", number=1, type=f.TYPE_MESSAGE,
                    label=f.LABEL_REPEATED, type_name=".bench_prune.XPlane")
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    xspace = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_prune.XSpace"))()
    xspace.ParseFromString(pathlib.Path(src).read_bytes())
    keep = [p for p in xspace.planes if p.name != "/host:metadata"]
    del xspace.planes[:]
    xspace.planes.extend(keep)
    for p in xspace.planes:
        if xtrace.DEVICE_PLANE.match(p.name):
            for m in p.event_metadata.values():
                del m.stats[:]
    pathlib.Path(dst).write_bytes(xspace.SerializeToString())


def table(times: scopes.ScopeTimes, calls: int,
          exposed_s: float | None = None) -> str:
    """Device ms per inversion by level (rows) and step (columns), summed
    over devices, every step the trace holds included."""
    present = {st for _, st in times.scope_s if st is not None}
    steps = [s for s in STEP_ORDER if s in present] + sorted(
        present.difference(STEP_ORDER))
    levels = sorted({lv for lv, _ in times.scope_s if lv is not None})
    rows = [["level", *steps, "unscoped", "total"]]
    for lv in [None, *levels]:
        cells = [times.scope_s.get((lv, s), 0.0) for s in steps]
        cells.append(times.unscoped_s if lv is None else 0.0)
        if sum(cells) == 0:
            continue
        rows.append(["-" if lv is None else f"L{lv}",
                     *(f"{1000 * c / calls:.3f}" for c in cells),
                     f"{1000 * sum(cells) / calls:.3f}"])
    out = ["| " + " | ".join(r) + " |" for r in rows]
    out.insert(1, "|" + "---|" * len(rows[0]))
    dev = times.device_s
    out.append("")
    out.append(f"device {1000 * dev / calls:.3f} ms per inversion, summed "
               f"over devices; unscoped {100 * times.unscoped_s / dev:.4f}%")
    if times.dispatch_idle_s is not None:
        out.append(f"idle inside {scopes.ENTRY_SPAN}* (mean over devices): "
                   f"{1000 * times.dispatch_idle_s / calls:.4f} ms per "
                   "inversion")
    if exposed_s is not None:
        out.append(f"exposed collective (mean over devices): "
                   f"{1000 * exposed_s / calls:.4f} ms per inversion")
    if times.class_scope_s:
        out.append("opclasses vs scopes (ms per inversion): " + ", ".join(
            f"{c}/{k} {1000 * s / calls:.3f}"
            for (c, k), s in sorted(times.class_scope_s.items())))
    return "\n".join(out)


def _times(trace_path, scope_map, block):
    trace = xtrace.load(trace_path)
    return (scopes.reduce(trace, scopes.entry_spans(trace_path), scope_map,
                          block), exposed_collective_s(trace, block))


def record(n: int, block: int, out: str, calls: int = 2) -> None:
    """Trace `calls` inversions on four chips as the closed loop does, and
    write the pruned trace and the program's scope map next to each other."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from bench import data
    from bench.common import mesh_for, span
    from repro.compat import set_mesh
    from repro.core import spin_inverse_sharded

    config = dict(RECORD_CONFIG, n=n, block_size=block)
    chips = len(jax.devices())
    if chips < 4:
        raise SystemExit(f"record needs four chips, JAX found {chips}")
    mesh = mesh_for(config, jax.devices()[:4])
    a = data.spd_matrix(n, 0, 0, NamedSharding(
        mesh, PartitionSpec(*config["mesh"]["axes"])))

    def call():
        return spin_inverse_sharded(a, block, leaf_solver="pallas",
                                    engine="pallas")

    with set_mesh(mesh):
        call().block_until_ready()
        scope_map = scopes.program_scopes(config, 4)
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            with span(xtrace.WINDOW_SPAN):
                for _ in range(calls):
                    with span("bench.offline"):
                        call().block_until_ready()
            jax.profiler.stop_trace()
            found = xtrace.find_trace(tmp)
            shutil.copy(found, f"{out}.full.xplane.pb")
    prune(f"{out}.full.xplane.pb", f"{out}.xplane.pb")
    pathlib.Path(f"{out}.full.xplane.pb").unlink()
    pathlib.Path(f"{out}.scopes.json").write_text(json.dumps(
        {mod: {op: list(s) for op, s in ops.items()}
         for mod, ops in scope_map.items()}, separators=(",", ":")))
    times, exposed = _times(f"{out}.xplane.pb", scope_map, block)
    print(table(times, calls, exposed) if times
          else "the trace holds no device op")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = p.add_subparsers(dest="cmd", required=True)
    t = sub.add_parser("table")
    t.add_argument("trace")
    t.add_argument("scopes")
    t.add_argument("--calls", type=int, required=True)
    t.add_argument("--block", type=int, required=True)
    r = sub.add_parser("record")
    r.add_argument("--n", type=int, required=True)
    r.add_argument("--block", type=int, required=True)
    r.add_argument("--out", required=True)
    r.add_argument("--calls", type=int, default=2)
    args = p.parse_args(argv)
    if args.cmd == "table":
        times, exposed = _times(args.trace, scopes.load_scopes(args.scopes),
                                args.block)
        print(table(times, args.calls, exposed) if times
              else "the trace holds no device op")
    else:
        from bench import env

        env.setup(pathlib.Path(__file__).resolve().parents[1])
        record(args.n, args.block, args.out, args.calls)
    return 0


if __name__ == "__main__":
    sys.exit(main())
