"""repro.core — the paper's contribution: distributed block-recursive
Strassen matrix inversion (SPIN) + the LU baseline, on JAX meshes.

Importing note: ``from repro.core import multiply`` gives the multiply
FUNCTION, not the ``repro.core.multiply`` submodule (the package re-export
shadows the module attribute). The submodule's other public names —
``multiply_engine``, ``current_engine``, ``validate_engine`` — are
re-exported here so no caller needs the submodule object; if you really
want the module, ``import repro.core.multiply as m`` still works.
"""

from .blockmatrix import BlockMatrix, OpCounts, count_ops, block_sharding
from .multiply import (multiply, multiply_engine, current_engine,
                       validate_engine)
from .precision import (PrecisionPolicy, PRECISION_PRESETS,
                        resolve_precision)
from .strassen import (strassen_cutoff, strassen_matmul,
                       strassen_matmul_blocks)
from .spin import spin_inverse, spin_inverse_dense, spin_inverse_sharded
from .solve import (spin_solve, spin_solve_dense, spin_solve_sharded,
                    spin_inverse_batched, solve_grid_for,
                    SketchedInverse, sketched_approx_inverse)
from .lu_inverse import lu_inverse, lu_inverse_dense, block_lu
from .newton_schulz import newton_schulz_polish, residual_norm
from .solver_ckpt import CheckpointedSpin
from .matrix_io import load_blockmatrix, save_blockmatrix
from .update import (smw_update_inverse, smw_update_solve,
                     block_update_factors, apply_inverse, add_low_rank,
                     DriftTracker, estimate_inverse_residual)
from . import costmodel, testing, verify

__all__ = [
    "BlockMatrix", "OpCounts", "count_ops", "block_sharding",
    "multiply", "multiply_engine", "current_engine", "validate_engine",
    "PrecisionPolicy", "PRECISION_PRESETS", "resolve_precision",
    "strassen_cutoff", "strassen_matmul", "strassen_matmul_blocks",
    "spin_inverse", "spin_inverse_dense", "spin_inverse_sharded",
    "spin_solve", "spin_solve_dense", "spin_solve_sharded",
    "spin_inverse_batched", "solve_grid_for",
    "SketchedInverse", "sketched_approx_inverse",
    "lu_inverse", "lu_inverse_dense", "block_lu",
    "newton_schulz_polish", "residual_norm", "CheckpointedSpin",
    "smw_update_inverse", "smw_update_solve", "block_update_factors",
    "apply_inverse", "add_low_rank", "DriftTracker",
    "estimate_inverse_residual",
    "costmodel", "testing", "verify",
]
