"""Device time per inversion, summed over devices, of the recursion levels
that run replicated on a mesh: ops whose innermost level scope is a depth
whose node's quadrants no longer divide the mesh, and the leaves
(`bench.mesh.replicated_levels`, from `n`, `block_size` and the mesh's
shape). All but one device's share of it is work the others repeat. Only
a mesh cell has it. Read from the trace joined to the program's scopes
(`bench/scopes.py`)."""

from bench import mesh, scopes

LAYER = "Mesh placement"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "inverse_s"


def read(ctx):
    shape = mesh.mesh_shape(ctx.config)
    if int(ctx.chips) < 2 or shape is None:
        return None
    times = scopes.cell_times(ctx)
    if times is None:
        return None
    levels = mesh.replicated_levels(int(ctx.config["n"]),
                                    int(ctx.config["block_size"]), shape)
    return scopes.per_call_ms(ctx, sum(times.level_s(k) for k in levels))
