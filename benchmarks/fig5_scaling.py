"""Paper Fig. 5: scalability vs executors (forced host-device sweep).

Three views, all reported:
  (a) measured wall time of the DENSE-path recursion with 1/2/4/8 fake host
      devices (subprocesses — jax pins the device count at init).
  (b) measured wall time of the MESH-RESIDENT sharded recursion
      (`spin_inverse_sharded`, one pjit program with grid-over-mesh
      constraints at every level) on the same device counts.
  (c) the calibrated cost model's predicted scaling (the paper's ideal-line
      comparison), which is the meaningful scalability statement we can
      make from this container.

CAVEAT printed with the measured numbers: all fake devices share this
container's physical cores, so measured speedup reflects scheduling
overhead, not parallel speedup; the paper's 3-node cluster genuinely
parallelizes.

It is a CPU sweep by construction: each device count runs in a child
process that imports JAX on forced host devices. On a host whose default
backend is an accelerator it refuses to run — children would fight the
parent for the chip. Standalone usage (the CI distributed job):

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.fig5_scaling \
        --reduced --json BENCH_scaling.json
"""

from __future__ import annotations

import os
import subprocess
import sys

from repro.core.costmodel import CostParams, spin_cost
from .common import (bench_arg_parser, csv_row, emit_header,
                     write_json_report)

N = 1024
B = 8
DEVICES = (1, 2, 4, 8)

REDUCED_N = 256
REDUCED_B = 4
REDUCED_DEVICES = (1, 2, 4, 8)

_CHILD = r"""
import time, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.compat import AxisType, make_mesh, set_mesh
from repro.core import BlockMatrix, spin_inverse, spin_inverse_sharded, testing
from repro.parallel import ShardedBlockMatrix, inverse_program

n, bs, d = {n}, {bs}, {d}
dev = jax.devices()
shape = (d // 2, 2) if d >= 4 else (d, 1)
mesh = make_mesh(shape, ("data", "model"),
                 axis_types=(AxisType.Auto,) * 2, devices=dev[:d])
a = testing.make_spd(n, jax.random.PRNGKey(0))
A = BlockMatrix.from_dense(a, bs)


def best_of(f, x, iters=3):
    jax.block_until_ready(f(x))            # compile+warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


with set_mesh(mesh):
    sh = NamedSharding(mesh, P("data", "model", None, None))
    Ab = jax.device_put(A.blocks, sh)
    dense = best_of(jax.jit(lambda x: spin_inverse(BlockMatrix(x)).blocks), Ab)
    print("SECONDS dense", dense)
    sharded = best_of(
        lambda x: inverse_program(ShardedBlockMatrix(x)).blocks, Ab)
    print("SECONDS sharded", sharded)
"""


def _run_child(n: int, bs: int, d: int) -> dict[str, float]:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    code = _CHILD.format(n=n, bs=bs, d=d)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    out: dict[str, float] = {}
    for line in res.stdout.splitlines():
        if line.startswith("SECONDS"):
            _, path, secs = line.split()
            out[path] = float(secs)
    if res.returncode != 0 or not out:
        # keep whatever timings landed before the crash, plus the reason
        out["error"] = res.stderr[-300:] or f"exit {res.returncode}"
    return out


def _require_cpu_backend() -> None:
    import jax

    backend = jax.default_backend()
    if backend != "cpu":
        raise SystemExit(
            f"fig5_scaling is a forced-host-device CPU sweep that starts a "
            f"JAX child per device count; the default backend here is "
            f"{backend!r}, whose chip the children cannot share. Run it "
            f"with JAX_PLATFORMS=cpu.")


def run(emit, *, n: int = N, grid: int = B, devices=DEVICES,
        json_path: str | None = None) -> dict:
    _require_cpu_backend()
    measured: dict[str, dict[int, float]] = {"dense": {}, "sharded": {}}
    errors: dict[int, str] = {}
    for d in devices:
        child = _run_child(n, n // grid, d)
        if "error" in child:
            errors[d] = child["error"]
            emit(csv_row(f"fig5/measured/dev{d}", -1,
                         f"FAILED:{child['error'][-200:]}"))
        for path in ("dense", "sharded"):
            if path not in child:       # child may have died mid-sweep
                continue
            measured[path][d] = child[path]
            emit(csv_row(f"fig5/{path}/dev{d}", child[path],
                         "one-physical-core caveat"))

    # model-predicted scaling (cores = executors), normalized to 1 executor
    base = spin_cost(CostParams(n=n, b=grid, cores=1))["total"]
    model = {}
    for d in devices:
        pred = spin_cost(CostParams(n=n, b=grid, cores=d))["total"]
        model[d] = pred
        emit(csv_row(f"fig5/model/dev{d}", pred,
                     f"speedup={base / pred:.2f}x;ideal={d}x"))

    report = {
        "benchmark": "fig5_scaling",
        "n": n,
        "grid": grid,
        "devices": list(devices),
        "measured_s": {p: {str(d): t for d, t in by_d.items()}
                       for p, by_d in measured.items()},
        "errors": {str(d): e for d, e in errors.items()},
        "model_s": {str(d): t for d, t in model.items()},
        "model_speedup": {str(d): base / t for d, t in model.items()},
        "caveat": ("fake host devices share physical cores; measured times "
                   "show scheduling overhead, model_speedup is the paper's "
                   "ideal-line comparison"),
    }
    write_json_report(report, json_path, emit, "fig5")
    return report


def main() -> None:
    args = bench_arg_parser(__doc__).parse_args()
    emit_header()
    if args.reduced:
        report = run(print, n=REDUCED_N, grid=REDUCED_B,
                     devices=REDUCED_DEVICES, json_path=args.json)
    else:
        report = run(print, json_path=args.json)
    if not any(report["measured_s"].values()):
        # every child crashed: the sweep measured nothing — fail the CI step
        # loudly instead of uploading an empty artifact as success
        sys.exit(f"fig5_scaling: all children failed: {report['errors']}")


if __name__ == "__main__":
    main()
