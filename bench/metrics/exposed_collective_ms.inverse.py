"""Collective time that no compute hides, per inversion, as the mean over
devices: in the traced window, the time in which a device runs a
collective op (the `collective` class of `opclasses.json`: the SUMMA
gathers and what the partitioner adds between levels) and no op of
another class. Only a mesh cell has it. Read by `bench/mesh.py`."""

from bench import mesh, scopes

LAYER = "Mesh collectives"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "inverse_s"


def read(ctx):
    trace = mesh.cell_trace(ctx)
    return scopes.per_call_ms(ctx, trace and mesh.exposed_collective_s(
        trace, int(ctx.config["block_size"])))
