#!/usr/bin/env python3
"""Readings that set a cell's limit, and the control run through the harness.

    python3 bench/control.py --workload <name> --seeds 11,12,13 [--precisions high,bf16]
    python3 bench/control.py --workload <name> --seeds 11,12,13 --in-place high [--seconds 10]

The control is the plain reference at a lower precision than the
configuration states: Newton–Schulz for an inverse. By default it is read
at the cell's own size, on the chip, once per seed, beside the program's
own reading. With `--in-place` the control is put in the program's place
(the loop's `control`) and the whole run goes through the harness, whose
own comparison has to come out `correct: false`. Benchmark runs never run
this. One JSON line per seed.
"""

import argparse
import contextlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
from bench import env  # noqa: E402

env.setup(ROOT)

from bench import common, data, harness, reference  # noqa: E402


def readings(cell, seed: int, devices, precisions) -> dict:
    """The program's residual and the control's at each precision, on
    matrix 0 of `seed`."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.compat import set_mesh
    from repro.core import spin_inverse_dense, spin_inverse_sharded

    c = cell.config
    n, bs = int(c["n"]), int(c["block_size"])
    mesh = common.mesh_for(c, devices)
    sharding = (jax.sharding.SingleDeviceSharding(devices[0]) if mesh is None
                else NamedSharding(mesh, PartitionSpec(*c["mesh"]["axes"])))
    a = data.spd_matrix(n, seed, 0, sharding)
    out = {}
    with set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        if mesh is None:
            x = spin_inverse_dense(a, bs, c["leaf_solver"], engine=c["engine"])
        else:
            x = spin_inverse_sharded(a, bs, leaf_solver=c["leaf_solver"],
                                     engine=c["engine"])
        out["program"] = float(reference.inverse_residual(a, x))
        del x
        for p in precisions:
            x = reference.newton_schulz_inverse(a, p)
            out[f"newton_schulz_{p}"] = float(reference.inverse_residual(a, x))
            del x
    return out


def in_place(cell, seed: int, seconds: float, precision: str) -> dict:
    """One harness run with the control answering in the program's place."""
    with cell.loop().control(precision):
        r = harness.run(["--workload", cell.name, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", "0"],
                        root=ROOT, t_start=time.perf_counter())
    return {"control": precision, "correct": r["correct"],
            "attempted": r["attempted"], "checks": r["checks"]}


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precisions", default="bf16,high,highest")
    ap.add_argument("--in-place", default=None,
                    help="put the control at this precision in the "
                         "program's place and run the harness")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = harness.Cell(ROOT, args.workload)
    devices = harness.require_chips(cell.chips)
    from repro import compat

    compat.enable_compilation_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        if args.in_place:
            r = in_place(cell, seed, args.seconds, args.in_place)
        else:
            r = readings(cell, seed, devices, args.precisions.split(","))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": time.perf_counter() - t, **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
