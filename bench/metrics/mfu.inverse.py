"""The whole inversion's share of the chips' peak: classical FLOPs of the
multiplies and leaves per inversion, times the inversions completed in the
traced window, over the window's length times the chips' bf16 peak. It
bounds every kernel's roofline share from above in what it can claim."""

from bench import work

LAYER = "Entry points"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "inverse_s"


def read(ctx):
    s, calls = ctx.summary, ctx.counters.get("calls_traced", 0)
    if s is None or not calls or ctx.peaks is None:
        return None
    flops = calls * work.inverse_flops(ctx.config["n"],
                                       ctx.config["block_size"])
    return 100.0 * flops / (s.window_s * ctx.chips * ctx.peaks["bf16_flops"])
