"""Pallas kernel packages + the shared interpret-mode policy.

Every kernel wrapper in this package resolves its `interpret=` argument
through `pallas_interpret_default()`, which follows the backend alone:

  * on a TPU backend the kernels always run compiled (Mosaic). Interpret
    mode there would hide the device behind the Python interpreter, so a
    set ``SPIN_PALLAS_INTERPRET`` on a TPU backend is an error;
  * everywhere else (CPU tests, CI) they run in interpret mode.

``SPIN_PALLAS_INTERPRET=1`` keeps one meaning, off-TPU only: the optional
Pallas routes — the Strassen engine's base case (`kernels/strassen`) —
are taken through the interpreted kernels too, so CI exercises that
composition on CPU runners (`pallas_routes_forced`).

The policy is read at trace time: already-compiled outer jit executables
keep the value they were traced with.

Every kernel that reckons its VMEM asks Mosaic for it through
`vmem_compiler_params`, under the one `VMEM_CAP_BYTES` cap.
"""


import jax

__all__ = ["pallas_interpret_default", "pallas_routes_forced",
           "PALLAS_INTERPRET_ENV", "mesh_safe", "VMEM_CAP_BYTES",
           "vmem_compiler_params"]

PALLAS_INTERPRET_ENV = "SPIN_PALLAS_INTERPRET"

# Physical VMEM is 128 MiB on v5e; leave room for Mosaic's own scratch.
VMEM_CAP_BYTES = 96 * 2**20


def vmem_compiler_params(vmem_bytes: int, semantics: tuple[str, ...]):
    """Mosaic compiler params asking for a kernel's reckoned VMEM plus a
    quarter (Mosaic scopes a kernel to 16 MiB unless it asks); refuses a
    reckoning over `VMEM_CAP_BYTES`."""
    from jax.experimental.pallas import tpu as pltpu

    if vmem_bytes > VMEM_CAP_BYTES:
        raise ValueError(
            f"kernel needs ~{vmem_bytes / 2**20:.0f} MiB of VMEM, over the "
            f"{VMEM_CAP_BYTES / 2**20:.0f} MiB cap: use smaller blocks or "
            "tiles")
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=int(vmem_bytes * 1.25))


def pallas_interpret_default() -> bool:
    """True when Pallas kernels must run in interpret mode (off-TPU).

    Raises on a TPU backend when ``SPIN_PALLAS_INTERPRET`` is set at all:
    no environment flag may send a kernel through the interpreter there.
    """
    from repro import envconfig

    if jax.default_backend() == "tpu":
        if envconfig.env_raw(PALLAS_INTERPRET_ENV) is not None:
            raise RuntimeError(
                f"{PALLAS_INTERPRET_ENV} is set on a TPU backend; Pallas "
                "kernels run compiled on TPU — unset it")
        return False
    return True


def pallas_routes_forced() -> bool:
    """Off-TPU, ``SPIN_PALLAS_INTERPRET=1`` also routes the optional Pallas
    paths through the interpreted kernels (always False on TPU)."""
    from repro import envconfig

    return (pallas_interpret_default()
            and envconfig.env_bool(PALLAS_INTERPRET_ENV))


def mesh_safe(call):
    """`call` (a Pallas kernel invocation), runnable under an ambient mesh.

    The TPU lowering refuses a Mosaic kernel in the automatically
    partitioned part of a mesh program ("Mosaic kernels cannot be
    automatically partitioned"). Outside any mesh, or inside a shard_map
    (every axis manual), the call runs as it is. Otherwise it runs
    replicated under a shard_map: each device computes the whole call on
    gathered operands — what the partitioner does for an op it cannot
    split, e.g. the one leaf block of the sharded recursion.
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.shape or mesh.are_all_axes_manual:
        return call
    return jax.shard_map(call, in_specs=P(), out_specs=P(), check_vma=False)
