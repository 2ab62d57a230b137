"""Shared benchmark utilities: timing, CSV/JSON emission, CLI plumbing.

Every figure module (and the unified `benchmarks.run` driver) goes through
these helpers instead of hand-rolling them: `time_fn` (warmup + best-of-k),
`csv_row`/`emit_header` (the `name,us_per_call,derived` row format), and
`write_json_report`/`bench_arg_parser` (the `--reduced --json PATH`
standalone-main convention the CI jobs drive).
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Callable

import jax

__all__ = ["time_fn", "csv_row", "emit_header", "write_json_report",
           "bench_arg_parser", "engine_choices"]

CSV_HEADER = "name,us_per_call,derived"


def engine_choices() -> tuple[str, ...]:
    """The registered multiply engines, straight from the dispatch table.

    Every CLI `--engine` flag derives its choices from here so a newly
    registered engine (core.multiply._ENGINES) is immediately selectable
    everywhere without touching each argparse definition.
    """
    from repro.core.multiply import _ENGINES

    return tuple(_ENGINES)


def time_fn(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds of fn(*args) after warmup (JIT compile excluded).

    Per-point timing for the figure modules. The perf-gate sweep in
    benchmarks/run.py does NOT use this: it interleaves all points
    round-robin and takes per-point minima, which needs the loop structure
    itself, not a per-call helper.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def csv_row(name: str, seconds: float, derived: str = "") -> str:
    return f"{name},{seconds * 1e6:.1f},{derived}"


def emit_header(emit=print) -> None:
    """Open a standalone run: turn on the persistent compilation cache
    ($JAX_COMPILATION_CACHE_DIR or the in-checkout default, see
    `repro.compat.enable_compilation_cache`) and print the CSV header."""
    from repro.compat import enable_compilation_cache

    enable_compilation_cache()
    emit(CSV_HEADER)


def write_json_report(report: dict, json_path: str | None, emit,
                      tag: str) -> None:
    """Write `report` to json_path (no-op when None) and log a CSV row."""
    if not json_path:
        return
    with open(json_path, "w") as f:
        json.dump(report, f, indent=1)
    emit(f"{tag}/json,0,wrote {json_path}")


def bench_arg_parser(doc: str | None, *,
                     engine_flag: bool = False) -> argparse.ArgumentParser:
    """The shared standalone-main CLI: `--reduced` + `--json PATH`.

    engine_flag=True adds `--engine` with choices derived from the live
    dispatch table (`engine_choices()`), defaulting to None = ambient.
    """
    ap = argparse.ArgumentParser(description=doc)
    ap.add_argument("--reduced", action="store_true",
                    help="small sizes for CI smoke-benching")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the machine-readable report JSON here")
    if engine_flag:
        ap.add_argument("--engine", default=None, choices=engine_choices(),
                        help="multiply engine (default: ambient context)")
    return ap
