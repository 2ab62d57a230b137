"""Device time per inversion of the block layout, summed over devices: the
ops whose innermost step scope is `spin.layout` (the dense entry's
from_dense and to_dense), `split`, `arrange` or `neg`, at every level.
Read from the trace joined to the program's scopes (`bench/scopes.py`)."""

from bench import scopes

LAYER = "Block layout"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "inverse_s"


def read(ctx):
    times = scopes.cell_times(ctx)
    return scopes.per_call_ms(ctx, times and times.steps_s(
        scopes.LAYOUT_STEPS))
