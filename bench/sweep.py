#!/usr/bin/env python3
"""Find the knee of an open-loop service cell: the highest offered rate the
service sustains without a growing backlog.

    python3 bench/sweep.py --workload <name> --seeds 11,12 --seconds 20 --rates 10,20,40

For each rate and each seed, the process builds a fresh service from the
seed, warms it, and runs one window at that rate, so no rate inherits the
refactor state or the backlog of another. One JSON line per rate and seed:
the requests offered, the solve latency median and 99th percentile, the
seconds the backlog took to drain after the close, and the refactors and
paths taken. A rate is sustained when, on every seed, the drain takes a
small part of a second and the late half of the window is no slower than
the early half. Benchmark runs never run this; its result is written into
the mix as a fixed rate.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
from bench import env  # noqa: E402

env.setup(ROOT)

import numpy as np  # noqa: E402

from bench import data, harness  # noqa: E402
from bench.common import Window  # noqa: E402


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell(ROOT, args.workload)
    harness.require_chips(cell.chips)
    service = cell.loop()
    from repro import compat

    compat.enable_compilation_cache()
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in (int(s) for s in args.seeds.split(",")):
            print(json.dumps(window_at(cell, service, rate, seed,
                                       args.seconds)), flush=True)
    return 0


def window_at(cell, service, rate: float, seed: int, seconds: float) -> dict:
    svc, panels, factors = service.build_service(cell.config, cell.mix, seed)
    service.warm_service(svc, int(cell.config["service"]["slots"]),
                         int(cell.mix["tenants"]), panels, factors)
    mix = dict(cell.mix, rate_per_s=rate)
    schedule = data.open_loop_schedule(mix, seconds, seed)
    window = Window(seconds, None, 0.0)
    before = dict(svc.stats)
    t0_ns = int(window.open() * 1e9)
    sent = [service.Sent(plan=p, due_ns=t0_ns + int(p.due * 1e9), version=0)
            for p in schedule]
    service.drive(svc, sent, panels, factors, window, seconds)
    drained = (time.perf_counter_ns() - t0_ns) / 1e9 - seconds
    lat = service.latencies_ms(sent)
    half = len(lat) // 2
    paths = {}
    for s in sent:
        if s.plan.op == "solve":
            paths[s.req.path] = paths.get(s.req.path, 0) + 1
    return {
        "rate_per_s": rate, "seed": seed, "requests": len(sent),
        "p50_ms": float(np.percentile(lat, 50)),
        "p99_ms": float(np.percentile(lat, 99)),
        "p50_early_ms": float(np.percentile(lat[:half], 50)),
        "p50_late_ms": float(np.percentile(lat[half:], 50)),
        "drained_s": drained,
        "refactors": svc.stats["updates_refactor"]
        - before["updates_refactor"],
        "smw": svc.stats["updates_smw"] - before["updates_smw"],
        "paths": paths,
        "failed": sum(bool(s.req.failed or s.req.rejected) for s in sent),
    }


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
