"""Device time per matrix of what a stack adds around the per-matrix
recursion: the ops of the batched refresh that lie outside every
`spin.L<k>` level and leaf, under the scope `spin.batch` (the damping, the
scan's slicing and stacking) or `spin.layout` (each slice's block layout).
Read from the trace joined to the scope map of the batched programs
(`repro.core.solve.batched_op_scopes`); nothing where the program has no
such map. The scan's `while` op is left out of the map: its trace event
spans every op of its body, which are counted in their own scopes."""

from bench import scopes, xtrace

LAYER = "Entry points"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "inverse_s"
STEPS = ("spin.batch", scopes.LAYOUT)


def read(ctx):
    root = getattr(ctx.cell, "root", None)
    found = root and xtrace.find_trace(root / ".bench_out" / "trace")
    if not found or "stack" not in ctx.config:
        return None
    try:
        from repro.core.solve import batched_op_scopes
    except ImportError:
        return None
    import jax

    c = ctx.config
    scope_map = batched_op_scopes(
        int(c["stack"]), int(c["n"]), int(c["block_size"]), c["leaf_solver"],
        c["engine"],
        sharding=jax.sharding.SingleDeviceSharding(jax.devices()[0]))
    scope_map = {mod: {op: scope for op, scope in ops.items()
                       if not op.startswith("while")}
                 for mod, ops in scope_map.items()}
    times = scopes.reduce(xtrace.load(found), scopes.entry_spans(found),
                          scope_map)
    if times is None:
        return None
    return scopes.per_call_ms(ctx, sum(
        s for (level, step), s in times.scope_s.items()
        if level is None and step in STEPS))
