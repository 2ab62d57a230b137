"""Paper Table 3: per-method wall-clock breakdown of SPIN.

Under XLA everything fuses into one program, so in-situ per-method timing is
impossible; instead we time each method STANDALONE at the exact shapes and
invocation counts the recursion uses (from costmodel.spin_schedule) — the
same per-method accounting the paper instruments in Spark.

Standalone usage (the shared `--reduced --json` convention of common.py):

    PYTHONPATH=src python -m benchmarks.table3_breakdown --reduced \
        --json BENCH_table3.json
"""

from __future__ import annotations

import jax

from repro.core import BlockMatrix, multiply, testing
from repro.core.costmodel import spin_schedule

from .common import (bench_arg_parser, csv_row, emit_header, time_fn,
                     write_json_report)

N = 1024
BS = 128          # b = 8, 3 levels — the paper's Table 3 uses n=4096, b=8

REDUCED_N = 256
REDUCED_BS = 64   # b = 4, 2 levels: small enough for a CI smoke run


def run(emit, *, n=N, bs=BS, json_path: str | None = None) -> dict:
    key = jax.random.PRNGKey(0)
    sched = spin_schedule(n, bs)
    totals = {m: 0.0 for m in ("leafNode", "multiply", "subtract", "scalar",
                               "arrange", "breakMat", "xy")}

    for lvl in sched:
        grid = lvl["grid"]
        if grid == 1:
            blk = testing.make_spd(bs, key)
            bm = BlockMatrix.from_dense(blk, bs)
            t = time_fn(lambda x: x.leaf_inverse().blocks, bm)
            totals["leafNode"] += lvl["nodes"] * t
            continue
        half = grid // 2
        sub = testing.make_spd(half * bs, key)
        A = BlockMatrix.from_dense(sub, bs)
        t_mul = time_fn(lambda x: multiply(x, x).blocks, A)
        t_sub = time_fn(lambda x: x.subtract(x).blocks, A)
        t_scl = time_fn(lambda x: x.scalar_mul(-1.0).blocks, A)
        t_arr = time_fn(
            lambda x: BlockMatrix.arrange(x, x, x, x).blocks, A)
        nodes = lvl["nodes"]
        totals["multiply"] += nodes * lvl["multiplies"] * t_mul
        totals["subtract"] += nodes * lvl["subtracts"] * t_sub
        totals["scalar"] += nodes * lvl["scalar_muls"] * t_scl
        totals["arrange"] += nodes * lvl["arranges"] * t_arr
        # breakMat / xy are trace-time slicing on TPU — genuinely 0 runtime
        # (the paper's Spark pays a tag+filter pass; recorded as a win)

    for name, secs in totals.items():
        emit(csv_row(f"table3/{name}", secs))
    emit(csv_row("table3/total", sum(totals.values())))
    write_json_report({"benchmark": "table3_breakdown", "n": n,
                       "block_size": bs, "totals_s": totals,
                       "total_s": sum(totals.values())},
                      json_path, emit, "table3")
    return totals


def main() -> None:
    args = bench_arg_parser(__doc__).parse_args()
    emit_header()
    if args.reduced:
        run(print, n=REDUCED_N, bs=REDUCED_BS, json_path=args.json)
    else:
        run(print, json_path=args.json)


if __name__ == "__main__":
    main()
