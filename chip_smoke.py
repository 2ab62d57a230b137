#!/usr/bin/env python3
"""Drive SPIN's main path once on a TPU and check what comes out.

    python chip_smoke.py            # one chip: phases 0-4 at n=16384
    python chip_smoke.py --mesh4    # four chips: the mesh-resident phase only

One process, generated data (every matrix comes from --seed), no child
processes. Each phase holds its result to the f32 conformance bound
(`repro.core.verify.residual_tolerance`), with every residual product taken
at full f32 precision. Any exception or missed bound exits non-zero before
the last line, which is one JSON object and nothing else:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases (one chip):
  0  the device: a TPU or exit; versions and the compile-cache directory
  1  spin_inverse_dense at n=16384, block 4096 (grid 4: the recursion runs)
  2  spin_solve_dense with a (16384, 64) right-hand side
  3  the Pallas engine and Pallas leaf, compiled (tpu_custom_call present)
  4  SpinService: 32 solves with a rank-8 update after every 4, a probe
     solve against the current matrix, and a bf16 tenant (Pallas
     configuration) whose certified residual estimates are held to its
     policy bound
--mesh4: spin_inverse_sharded at n=32768 on a (2, 2) mesh, mesh-resident,
  and the n=16384 matrix inverted sharded and dense, compared. Both use
  the Pallas engine and leaf at the Pallas block size: with the XLA LU
  leaf at block 4096 the three programs take tens of minutes to compile
  for four chips, most of it in the 4096² LU expansions.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
N = 16384
BLOCK = 4096
RHS_COLS = 64
MESH_N = 32768


def log(*parts) -> None:
    print(*parts, flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def timed(fn):
    """(result, seconds) with the clock stopped after block_until_ready."""
    t0 = time.perf_counter()
    out = fn()
    out.block_until_ready()
    return out, time.perf_counter() - t0


def phase0_device():
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found platform "
                         f"{platform!r} ({len(devices)} device(s))")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("SPIN_PLAN_CACHE",
                          os.path.join(ROOT, ".plan_cache", "plans.json"))
    from importlib import metadata

    from repro import compat

    cache_dir = compat.enable_compilation_cache()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    log(f"[phase 0] device_kind={devices[0].device_kind!r} "
        f"count={len(devices)} jax={jax.__version__} libtpu={libtpu} "
        f"compile_cache={cache_dir}")
    return devices


def residual_fns():
    """Jitted residual norms at full f32 precision (no materialized I)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def inverse_residual(a, x):
        r = jnp.matmul(a, x, precision=hi) - jnp.eye(a.shape[0],
                                                      dtype=a.dtype)
        return jnp.linalg.norm(r) / math.sqrt(a.shape[0])

    @jax.jit
    def solve_residual(a, x, b):
        r = jnp.matmul(a, x, precision=hi) - b
        return jnp.linalg.norm(r) / jnp.linalg.norm(b)

    return inverse_residual, solve_residual


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def pallas_block() -> int:
    """The largest block at which every kernel of the Pallas path compiles
    (the blocked Gauss-Jordan leaf bounds it), capped to keep grid ≥ 4."""
    from repro.kernels.leaf_inverse.kernel import max_block_size

    return min(max_block_size("pallas"), N // 4)


def one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import spin_inverse_dense, spin_solve_dense, testing
    from repro.core.verify import residual_tolerance
    from repro.serving import SpinService

    tol = residual_tolerance(jnp.float32)
    inverse_residual, solve_residual = residual_fns()
    key_a, key_b, key_s = jax.random.split(jax.random.PRNGKey(seed), 3)
    a = testing.make_spd(N, key_a)

    # -- phase 1: the dense inverse ------------------------------------------
    def inverse():
        return spin_inverse_dense(a, BLOCK, "linalg", engine="einsum")

    x, cold_s = timed(inverse)
    x, warm_s = timed(inverse)
    res = float(inverse_residual(a, x))
    log(f"[phase 1] spin_inverse_dense n={N} block={BLOCK} grid={N // BLOCK}"
        f" compile_and_first_run_s={cold_s:.3f} warm_s={warm_s:.3f}"
        f" residual={res:.3e} tol={tol:.0e}")
    require(math.isfinite(res) and res <= tol,
            f"phase 1 residual {res} > {tol}")
    del x

    # -- phase 2: the multi-RHS solve ------------------------------------------
    b = jax.random.normal(key_b, (N, RHS_COLS), jnp.float32)

    def solve():
        return spin_solve_dense(a, b, BLOCK)

    xs, cold_s = timed(solve)
    xs, warm_s = timed(solve)
    res = float(solve_residual(a, xs, b))
    log(f"[phase 2] spin_solve_dense n={N} rhs={RHS_COLS} block={BLOCK}"
        f" compile_and_first_run_s={cold_s:.3f} warm_s={warm_s:.3f}"
        f" residual={res:.3e} tol={tol:.0e}")
    require(math.isfinite(res) and res <= tol,
            f"phase 2 residual {res} > {tol}")
    del xs

    # -- phase 3: the Pallas engine + Pallas leaf, compiled --------------------
    bs = pallas_block()
    text = jax.jit(lambda m: spin_inverse_dense(
        m, bs, "pallas", engine="pallas")).lower(a).as_text()
    kernels = text.count("tpu_custom_call")
    require(kernels > 0, "phase 3 program holds no tpu_custom_call")

    def pallas_inverse():
        return spin_inverse_dense(a, bs, "pallas", engine="pallas")

    x, cold_s = timed(pallas_inverse)
    x, warm_s = timed(pallas_inverse)
    res = float(inverse_residual(a, x))
    log(f"[phase 3] pallas engine+leaf n={N} block={bs} grid={N // bs}"
        f" tpu_custom_calls={kernels} compile_and_first_run_s={cold_s:.3f}"
        f" warm_s={warm_s:.3f} residual={res:.3e} tol={tol:.0e}")
    require(math.isfinite(res) and res <= tol,
            f"phase 3 residual {res} > {tol}")
    del x

    # -- phase 4: SpinService --------------------------------------------------
    t0 = time.perf_counter()
    svc = SpinService(slots=8)
    svc.add_matrix("a", a, block_size=BLOCK, leaf_solver="linalg",
                   engine="einsum")
    keys = iter(jax.random.split(key_s, 64))
    cols = RHS_COLS // 4              # 4 coalesced solves = one (n, 64) panel
    solves, updates = [], []
    for i in range(32):
        solves.append(svc.solve("a", jax.random.normal(
            next(keys), (N, cols), jnp.float32)))
        if i % 4 == 3:
            u = jax.random.normal(next(keys), (N, 8), jnp.float32) / N ** 0.5
            updates.append(svc.update("a", u))
    svc.run_until_done()
    bad = [r for r in solves + updates
           if not r.done or r.failed or r.rejected]
    require(not bad, f"phase 4 requests not done: "
            f"{[(r.uid, r.failed, r.rejected, r.error) for r in bad][:4]}")
    require(svc.stats["degraded_serves"] == 0,
            f"phase 4 degraded serves: {svc.stats['degraded_serves']}")
    probe_b = jax.random.normal(next(keys), (N, cols), jnp.float32)
    probe = svc.solve("a", probe_b)
    svc.run_until_done()
    require(probe.done and not probe.failed, f"probe failed: {probe.error}")
    res = float(solve_residual(svc.matrix("a").a, probe.x, probe_b))
    state = svc.matrix("a")
    log(f"[phase 4] SpinService exact tenant: {len(solves)} solves + "
        f"{len(updates)} rank-8 updates + probe, paths="
        f"{sorted({r.path for r in solves})} smw={svc.stats['updates_smw']}"
        f" refactors={svc.stats['updates_refactor']}"
        f" probe_residual={res:.3e} tol={tol:.0e}"
        f" wall_s={time.perf_counter() - t0:.3f}")
    require(math.isfinite(res) and res <= tol,
            f"phase 4 probe residual {res} > {tol}")
    del state

    t0 = time.perf_counter()
    svc.add_matrix("a_bf16", a, block_size=bs, leaf_solver="pallas",
                   engine="pallas", precision="bf16")
    low = svc.matrix("a_bf16")
    lowp = [svc.solve("a_bf16", jax.random.normal(
        next(keys), (N, cols), jnp.float32)) for _ in range(8)]
    svc.run_until_done()
    over = [(r.uid, r.residual_est) for r in lowp
            if not r.done or r.failed or r.residual_est is None
            or not r.residual_est <= low.serve_bound]
    require(not over, f"bf16 tenant over its bound {low.serve_bound}: "
            f"{over[:4]}")
    log(f"[phase 4] SpinService bf16 tenant: {len(lowp)} solves, store="
        f"{low.store_dtype} residual_est="
        f"{max(r.residual_est for r in lowp):.3e} bound={low.serve_bound:.0e}"
        f" block={bs} polish_triggers={low.polish_triggers}"
        f" wall_s={time.perf_counter() - t0:.3f}")
    require(svc.stats["degraded_serves"] == 0,
            f"phase 4 degraded serves: {svc.stats['degraded_serves']}")
    log(f"[memory] peak_bytes_in_use={peak_bytes(jax.devices()[0])}")


def mesh4(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compat import set_mesh
    from repro.core import spin_inverse_dense, spin_inverse_sharded, testing
    from repro.core.verify import residual_tolerance
    from repro.launch.mesh import make_worker_mesh
    from repro.parallel import assert_mesh_resident, record_specs

    devices = jax.devices()
    require(len(devices) == 4, f"--mesh4 needs 4 devices, found "
            f"{len(devices)}")
    tol = residual_tolerance(jnp.float32)
    inverse_residual, _ = residual_fns()
    mesh = make_worker_mesh((2, 2), ("data", "model"))
    grid_sharding = NamedSharding(mesh, P("data", "model"))
    key_big, key_small = jax.random.split(jax.random.PRNGKey(seed))
    bs = pallas_block()

    def sharded_inverse(m):
        return spin_inverse_sharded(m, bs, leaf_solver="pallas",
                                    engine="pallas")

    # n=32768: 4 GiB per operand, generated already sharded over the mesh.
    a = jax.jit(lambda k: testing.make_spd(MESH_N, k),
                out_shardings=grid_sharding)(key_big)
    with set_mesh(mesh):
        with record_specs() as recs:
            x, cold_s = timed(lambda: sharded_inverse(a))
        tally = assert_mesh_resident(recs, min_records=20)
        x, warm_s = timed(lambda: sharded_inverse(a))
        res = float(inverse_residual(a, x))
    log(f"[mesh4] spin_inverse_sharded n={MESH_N} block={bs} "
        f"grid={MESH_N // bs} engine=pallas leaf=pallas mesh=(2,2) "
        f"residency={tally}"
        f" compile_and_first_run_s={cold_s:.3f} warm_s={warm_s:.3f}"
        f" residual={res:.3e} tol={tol:.0e}")
    require(math.isfinite(res) and res <= tol,
            f"mesh4 n={MESH_N} residual {res} > {tol}")
    del a, x
    # A recursion that ran everything on device 0 would leave the other
    # three holding little more than their input shard: the peaks of the
    # four devices must be within a factor of two of each other.
    peaks = {d.id: peak_bytes(d) or 0 for d in devices}
    log(f"[memory] n={MESH_N} peak_bytes_in_use per device={peaks}")
    require(min(peaks.values()) >= max(peaks.values()) / 2,
            f"mesh4 n={MESH_N} bytes not spread over the 4 devices: {peaks}")

    # The phase-1 matrix: sharded on the mesh vs dense on device 0.
    a16 = jax.device_put(testing.make_spd(N, key_small), devices[0])
    x_dense = spin_inverse_dense(a16, bs, "pallas", engine="pallas")
    with set_mesh(mesh):
        x_sh = sharded_inverse(jax.device_put(a16, grid_sharding))
        x_sh.block_until_ready()
    diff = float(jnp.linalg.norm(jax.device_put(x_sh, devices[0]) - x_dense)
                 / jnp.linalg.norm(x_dense))
    log(f"[mesh4] n={N} sharded vs dense on device 0: rel_diff={diff:.3e}"
        f" tol={tol:.0e}")
    require(math.isfinite(diff) and diff <= tol,
            f"mesh4 sharded/dense disagree: {diff} > {tol}")
    log(f"[memory] end peak_bytes_in_use per device="
        f"{ {d.id: peak_bytes(d) for d in devices} }")


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--mesh4", action="store_true",
                        help="run only the four-chip mesh-resident phase")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    devices = phase0_device()
    if args.mesh4:
        mesh4(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
