# Distributed SPIN on a mesh built from the devices present (one chip, a
# four-chip 2x2 host, or fake CPU host devices), plus the TPU roofline
# projection for a production-scale inversion.
#
#     PYTHONPATH=src python examples/invert_at_scale.py --n 2048 --block 128
#
# On CPU, give it several devices to shard over:
#     XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
#         PYTHONPATH=src python examples/invert_at_scale.py --sharded

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import set_mesh
from repro.core import (BlockMatrix, multiply_engine, spin_inverse, testing)
from repro.core.costmodel import tpu_roofline_cost
from repro.launch.mesh import make_worker_mesh
from repro.parallel import ShardedBlockMatrix, inverse_program
from repro.planner import get_plan


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--block", type=int, default=None,
                    help="block size override (default: planner auto-tunes)")
    from repro.core.multiply import _ENGINES

    ap.add_argument("--engine", default=None, choices=list(_ENGINES),
                    help="multiply engine override (default: planner); "
                         "'pallas' is the fused-kernel engine (interpret "
                         "mode off-TPU), 'strassen' the recursive "
                         "7-multiply engine")
    ap.add_argument("--sharded", action="store_true",
                    help="mesh-resident recursion (spin_inverse_sharded): "
                         "every level's quadrants stay sharded over the "
                         "mesh, no inter-level gathers")
    args = ap.parse_args()

    mesh = make_worker_mesh()        # the squarest 2-axis mesh present
    # Plan INSIDE the mesh context: the signature then carries both the
    # device count — so with several devices the candidate space includes
    # the allgather/ring SUMMA engines — and the mesh topology, so the
    # cached plan is keyed to this mesh and never recalled for another.
    if args.block is None or args.engine is None:
        with set_mesh(mesh):
            plan = get_plan("inverse", args.n, jnp.float32,
                            placement="sharded" if args.sharded else "dense")
        block = args.block or plan.block_size
        engine = args.engine or plan.multiply_engine
        print(f"planner [{plan.source}]: block={plan.block_size} "
              f"engine={plan.multiply_engine} leaf={plan.leaf_solver}")
    else:
        block, engine = args.block, args.engine
    a = testing.make_spd(args.n, jax.random.PRNGKey(0))
    A = BlockMatrix.from_dense(a, block)
    print(f"n={args.n} grid={A.grid}x{A.grid} on mesh {dict(mesh.shape)} "
          f"engine={engine} path={'sharded' if args.sharded else 'dense'}")

    with set_mesh(mesh):
        sh = NamedSharding(mesh, P("data", "model", None, None))
        blocks = jax.device_put(A.blocks, sh)
        with multiply_engine(engine):
            if args.sharded:
                # one pjit program; quadrants stay mesh-resident per level
                f = lambda x: inverse_program(
                    ShardedBlockMatrix(x), engine=engine).blocks
            else:
                f = jax.jit(lambda x: spin_inverse(BlockMatrix(x)).blocks)
            jax.block_until_ready(f(blocks))      # compile
            t0 = time.perf_counter()
            inv = jax.block_until_ready(f(blocks))
            dt = time.perf_counter() - t0
    prod = jnp.matmul(a, BlockMatrix(inv).to_dense(),
                      precision=jax.lax.Precision.HIGHEST)
    resid = jnp.linalg.norm(prod - jnp.eye(args.n)) / args.n ** 0.5
    print(f"inverted in {dt * 1e3:.0f} ms  residual {float(resid):.2e}")

    # what this would cost on the production pod (roofline projection)
    for n, b, chips in [(2 ** 17, 16, 256), (2 ** 18, 16, 256)]:
        r = tpu_roofline_cost(n=n, b=b, chips=chips)
        print(f"roofline n={n} b={b} chips={chips}: "
              f"compute {r['t_compute'] * 1e3:.1f} ms, "
              f"memory {r['t_memory'] * 1e3:.1f} ms, "
              f"collective {r['t_collective'] * 1e3:.1f} ms "
              f"-> bound: {r['bottleneck']}")


if __name__ == "__main__":
    main()
