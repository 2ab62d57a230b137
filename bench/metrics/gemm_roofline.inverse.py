"""The recursion's multiplies against their roofline: the least time of one
inversion's classical multiplies (6 per node, 2·m³ FLOPs each, over the
chip's bf16 peak; or their bytes over HBM bandwidth, whichever is larger)
over the device time of the GEMM operations per inversion, summed over
devices. f32 at HIGHEST takes about six bf16 passes, so today's path reads
at most about a sixth."""

from bench import work

LAYER = "Recursion and multiply engines"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "inverse_s"


def read(ctx):
    s, calls = ctx.summary, ctx.counters.get("calls_traced", 0)
    gemm_s = s.class_s.get("gemm", 0.0) if s else 0.0
    if not calls or gemm_s <= 0 or ctx.peaks is None:
        return None
    n, bs = ctx.config["n"], ctx.config["block_size"]
    least = max(work.inverse_gemm_flops(n, bs) / ctx.peaks["bf16_flops"],
                work.inverse_gemm_bytes(n, bs) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (gemm_s / calls)
