"""Device time per inversion of the root node's own operations, summed over
devices: the ops whose innermost level scope is `spin.L0` (its six
products, two Schur updates, split, negation and arrange), not those of
its sub-inversions. Read from the trace joined to the program's scopes
(`bench/scopes.py`)."""

from bench import scopes

LAYER = "Recursion and multiply engines"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "inverse_s"


def read(ctx):
    times = scopes.cell_times(ctx)
    return scopes.per_call_ms(ctx, times and times.level_s(0))
