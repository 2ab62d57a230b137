"""Device time of the leaf operations per inversion, summed over devices:
the XLA LU and triangular-solve operations of the `linalg` leaf, or the
Pallas Gauss-Jordan kernels of the `pallas` leaf."""

LAYER = "Leaf solvers"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "inverse_s"


def read(ctx):
    s, calls = ctx.summary, ctx.counters.get("calls_traced", 0)
    leaf_s = s.class_s.get("leaf", 0.0) if s else 0.0
    if not calls or leaf_s <= 0:
        return None
    return 1000.0 * leaf_s / calls
