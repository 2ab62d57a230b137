"""Checkpointed SPIN: fault-tolerant execution of Algorithm 2.

Spark gets solver fault tolerance for free from RDD lineage — a lost
executor recomputes only its partitions. XLA has no lineage, so for very
large inversions (minutes per solve, preemptible pods) we execute the
recursion as an explicit DAG of named intermediates
(``0/I``, ``0/II``, …, ``0/I/V`` …) and persist each completed node.
On restart, completed nodes load from disk and computation resumes at the
first missing one — the recompute unit is one distributed op, mirroring
Spark's partition-recompute granularity.

Granularity control: ``min_grid`` stops checkpointing below a grid size
(deep levels are cheap to recompute; checkpointing them would be all I/O).

The module also holds the ONLINE-SERVICE snapshot format
(`save_service_snapshot` / `load_service_snapshot`): one meta.json plus a
`matrix_io` block directory per (matrix, role) pair, so a restarted
`serving.SpinService` reloads its maintained inverses instead of paying a
re-factorization — the restart analogue of the mid-inversion resume above.
"""

from __future__ import annotations

import json
import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .blockmatrix import BlockMatrix
from .matrix_io import load_blockmatrix, save_blockmatrix
from .multiply import multiply

__all__ = ["CheckpointedSpin", "save_service_snapshot",
           "load_service_snapshot", "validate_snapshot_key",
           "save_matrix_spill", "load_matrix_spill"]


class CheckpointedSpin:
    def __init__(self, ckpt_dir: str, *, leaf_solver: str = "linalg",
                 min_grid: int = 2,
                 on_op: Optional[Callable[[str], None]] = None):
        self.dir = ckpt_dir
        self.leaf_solver = leaf_solver
        self.min_grid = min_grid
        self.on_op = on_op or (lambda name: None)
        self.loaded_ops = 0
        self.computed_ops = 0
        os.makedirs(ckpt_dir, exist_ok=True)
        self._mul = jax.jit(lambda a, b: multiply(
            BlockMatrix(a), BlockMatrix(b)).blocks)
        self._sub = jax.jit(lambda a, b: a - b)
        self._neg = jax.jit(lambda a: -a)
        self._leaf = jax.jit(lambda a: BlockMatrix(a).leaf_inverse(
            leaf_solver).blocks)

    # -- persistence --------------------------------------------------------
    def _path(self, name: str) -> str:
        return os.path.join(self.dir, name.replace("/", "_") + ".npy")

    def _have(self, name: str) -> bool:
        return os.path.exists(self._path(name))

    def _load(self, name: str) -> BlockMatrix:
        self.loaded_ops += 1
        return BlockMatrix(jnp.asarray(np.load(self._path(name))))

    def _store(self, name: str, value: BlockMatrix) -> BlockMatrix:
        tmp = self._path(name) + ".tmp"
        with open(tmp, "wb") as f:               # atomic: write-then-rename
            np.save(f, np.asarray(jax.device_get(value.blocks)))
        os.replace(tmp, self._path(name))
        return value

    def _memo(self, name: str, thunk: Callable[[], BlockMatrix],
              grid: int) -> BlockMatrix:
        if grid >= self.min_grid and self._have(name):
            return self._load(name)
        self.on_op(name)
        value = thunk()
        jax.block_until_ready(value.blocks)
        self.computed_ops += 1
        if grid >= self.min_grid:
            self._store(name, value)
        return value

    # -- the recursion (paper Algorithm 2, nodes named by DAG path) ----------
    def inverse(self, a: BlockMatrix, path: str = "0") -> BlockMatrix:
        g = a.grid
        if g >= self.min_grid and self._have(path):
            return self._load(path)
        if g == 1:
            return self._memo(path, lambda: BlockMatrix(
                self._leaf(a.blocks)), g)

        a11, a12, a21, a22 = a.split()
        mul = lambda x, y: BlockMatrix(self._mul(x.blocks, y.blocks))
        i_ = self.inverse(a11, path + "/I")
        ii = self._memo(path + "/II", lambda: mul(a21, i_), g)
        iii = self._memo(path + "/III", lambda: mul(i_, a12), g)
        iv = self._memo(path + "/IV", lambda: mul(a21, iii), g)
        v = self._memo(path + "/V", lambda: BlockMatrix(
            self._sub(iv.blocks, a22.blocks)), g)
        vi = self.inverse(v, path + "/VI")
        c12 = self._memo(path + "/C12", lambda: mul(iii, vi), g)
        c21 = self._memo(path + "/C21", lambda: mul(vi, ii), g)
        vii = self._memo(path + "/VII", lambda: mul(iii, c21), g)
        c11 = self._memo(path + "/C11", lambda: BlockMatrix(
            self._sub(i_.blocks, vii.blocks)), g)
        c22 = BlockMatrix(self._neg(vi.blocks))
        c = BlockMatrix.arrange(c11, c12, c21, c22)
        return self._memo(path, lambda: c, g)


# ---------------------------------------------------------------------------
# Online-service snapshots (serving.SpinService state)
# ---------------------------------------------------------------------------

_SNAPSHOT_VERSION = 1


def validate_snapshot_key(key: str) -> None:
    """Reject ids that would collide or escape in `<mid>__<name>` dirs.

    The block directory name is the plain join of matrix id and role, so
    ids containing the separator would collide ("m__a"/"inv" vs
    "m"/"a__inv") and path characters would nest or escape the snapshot
    directory. Enforced at save AND at `SpinService.add_matrix`, so a bad
    id fails at admission rather than at the first snapshot.
    """
    if (not key or "__" in key or "/" in key or "\\" in key
            or os.sep in key or key in (".", "..")):
        raise ValueError(
            f"snapshot key {key!r} must be non-empty and contain no "
            "'__', path separators, or dot-dirs")


def save_service_snapshot(directory: str, *, meta: dict,
                          matrices: dict[str, dict[str, BlockMatrix]]
                          ) -> None:
    """Persist service state: `meta` (JSON-serializable) + named block
    matrices per matrix id (e.g. {"ridge": {"a": bm, "inv": bm}}).

    Crash-safe under RE-snapshotting into the same directory: every save
    writes its blocks into a fresh nonce'd subdirectory
    (``blocks-<nonce>/<mid>__<name>``, via `matrix_io.save_blockmatrix` —
    atomic per-row writes, bf16-safe), then atomically swings meta.json to
    point at it, then garbage-collects older nonce dirs. A crash at ANY
    point leaves meta.json referencing a complete snapshot (the previous
    one until the swap, the new one after) — old and new block rows are
    never mixed under one meta.
    """
    import shutil
    import uuid

    os.makedirs(directory, exist_ok=True)
    nonce = f"blocks-{uuid.uuid4().hex[:12]}"
    arrays: dict[str, list[str]] = {}
    for mid, named in matrices.items():
        validate_snapshot_key(mid)
        arrays[mid] = sorted(named)
        for name, bm in named.items():
            validate_snapshot_key(name)
            if not isinstance(bm, BlockMatrix):
                raise TypeError(
                    f"snapshot matrix {mid!r}/{name!r} must be a "
                    f"BlockMatrix, got {type(bm).__name__}")
            save_blockmatrix(
                os.path.join(directory, nonce, f"{mid}__{name}"), bm)
    payload = {"version": _SNAPSHOT_VERSION, "meta": meta, "arrays": arrays,
               "blocks_dir": nonce}
    tmp = os.path.join(directory, "meta.json.tmp")
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, os.path.join(directory, "meta.json"))
    for entry in os.listdir(directory):         # GC superseded snapshots
        if entry.startswith("blocks-") and entry != nonce:
            shutil.rmtree(os.path.join(directory, entry),
                          ignore_errors=True)


def save_matrix_spill(directory: str, matrix_id: str, *, meta: dict,
                      pair: dict[str, BlockMatrix]) -> str:
    """Persist ONE matrix's serving state for residency eviction.

    The spill is a single-matrix service snapshot under
    ``directory/<matrix_id>`` — same meta.json + nonce'd block-dir format,
    same crash safety — so an evicted matrix's on-disk shape is exactly
    what `SpinService.restore` already knows how to read, and re-spilling
    the same matrix reuses the GC'd-nonce overwrite path. `meta` is the
    per-matrix entry (the service's snapshot `meta["matrices"][mid]`
    shape); returns the spill directory.
    """
    validate_snapshot_key(matrix_id)
    spill_dir = os.path.join(directory, matrix_id)
    save_service_snapshot(spill_dir,
                          meta={"matrices": {matrix_id: meta}},
                          matrices={matrix_id: pair})
    return spill_dir


def load_matrix_spill(directory: str, matrix_id: str
                      ) -> tuple[dict, dict[str, BlockMatrix]]:
    """Inverse of `save_matrix_spill`: (per-matrix meta, {name: bm})."""
    meta, matrices = load_service_snapshot(
        os.path.join(directory, matrix_id))
    return meta["matrices"][matrix_id], matrices[matrix_id]


def load_service_snapshot(directory: str
                          ) -> tuple[dict, dict[str, dict[str, BlockMatrix]]]:
    """Inverse of `save_service_snapshot`: (meta, {mid: {name: bm}})."""
    with open(os.path.join(directory, "meta.json")) as f:
        payload = json.load(f)
    if payload.get("version") != _SNAPSHOT_VERSION:
        raise ValueError(
            f"service snapshot version {payload.get('version')} != "
            f"{_SNAPSHOT_VERSION}")
    bdir = os.path.join(directory, payload["blocks_dir"])
    matrices = {
        mid: {name: load_blockmatrix(os.path.join(bdir, f"{mid}__{name}"))
              for name in names}
        for mid, names in payload["arrays"].items()}
    return payload["meta"], matrices
