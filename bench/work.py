"""Work counts and the chip's peaks: the denominators of every share.

Counts are the classical ones, whatever engine runs: a Strassen or fused
engine is judged on the same work as the plain one.
"""

from __future__ import annotations

import json
import math
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def levels(n: int, block_size: int) -> int:
    grid = n // block_size
    if n % block_size or grid & (grid - 1):
        raise ValueError(f"n={n} over block {block_size} is not a "
                         "power-of-two grid")
    return int(math.log2(grid))


def inverse_multiplies(n: int, block_size: int) -> int:
    """Half-size multiplies of one SPIN inversion: 6 per internal node."""
    return sum(6 * 2 ** i for i in range(levels(n, block_size)))


def inverse_gemm_flops(n: int, block_size: int) -> float:
    """Classical FLOPs of the recursion's multiplies: at level i, 2**i nodes
    each multiply 6 pairs of (n/2**(i+1))-square matrices at 2·m³ FLOPs."""
    return float(sum(2 ** i * 6 * 2 * (n // 2 ** (i + 1)) ** 3
                     for i in range(levels(n, block_size))))


def inverse_gemm_bytes(n: int, block_size: int, itemsize: int = 4) -> float:
    """Least HBM traffic of those multiplies: read two operands, write one."""
    return float(sum(2 ** i * 6 * 3 * (n // 2 ** (i + 1)) ** 2 * itemsize
                     for i in range(levels(n, block_size))))


def leaf_count(n: int, block_size: int) -> int:
    return n // block_size


def inverse_leaf_flops(n: int, block_size: int) -> float:
    """Classical FLOPs of the leaves: one dense inverse, 2·bs³, per leaf."""
    return float(leaf_count(n, block_size) * 2 * block_size ** 3)


def inverse_flops(n: int, block_size: int) -> float:
    return inverse_gemm_flops(n, block_size) + inverse_leaf_flops(
        n, block_size)


def peaks(device_kind: str) -> dict:
    """The peak row for `device_kind`; a kind not in the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name} (known: {sorted(table)})")
    return table[device_kind]
