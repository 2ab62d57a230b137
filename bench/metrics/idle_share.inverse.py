"""Share of the traced window in which the device ran no operation, as the
mean over the cell's devices."""

LAYER = "Device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "inverse_s"


def read(ctx):
    s = ctx.summary
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.mean_busy_s / s.window_s)
