"""Device-idle time per inversion that lies inside the program's entry
span (`spin.inverse_dense` or `spin.inverse_sharded`: argument and
precision resolution and the jit dispatch), as the mean over devices: the
traced window's idle intervals intersected with the span's intervals.
Read by `bench/scopes.py`."""

from bench import scopes

LAYER = "Entry points"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "inverse_s"


def read(ctx):
    times = scopes.cell_times(ctx)
    return scopes.per_call_ms(ctx, times and times.dispatch_idle_s)
