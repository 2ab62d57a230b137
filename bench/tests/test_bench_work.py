"""Work counts and peaks against the recursion's own operation counters."""

import jax
import pytest

from bench import work


@pytest.mark.parametrize("n,bs", [(64, 32), (128, 32), (256, 32), (256, 64)])
def test_counts_match_the_recursions_counters(n, bs):
    from repro.core import testing
    from repro.core.blockmatrix import BlockMatrix, count_ops
    from repro.core.multiply import multiply_engine
    from repro.core.spin import spin_inverse

    a = testing.make_spd(n, jax.random.PRNGKey(0))
    with multiply_engine("einsum"), count_ops() as counts:
        spin_inverse(BlockMatrix.from_dense(a, bs))
    internal = sum(2 ** i for i in range(work.levels(n, bs)))
    assert counts.multiplies == work.inverse_multiplies(n, bs)
    assert counts.leaf_inversions == work.leaf_count(n, bs)
    assert counts.subtracts == 2 * internal
    assert counts.block_gemms * 2 * bs ** 3 == work.inverse_gemm_flops(n, bs)


def test_flop_counts_of_the_cells():
    # The classical 6-multiply count: 8.25e12 at n=16384, grid 4.
    assert work.inverse_gemm_flops(16384, 4096) == 6 * 2 * 8192 ** 3 + (
        2 * 6 * 2 * 4096 ** 3)
    assert work.inverse_leaf_flops(16384, 4096) == 4 * 2 * 4096 ** 3
    assert work.inverse_gemm_flops(32768, 1024) == pytest.approx(7.03e13,
                                                                 rel=1e-3)
    assert work.inverse_gemm_flops(16384, 1024) == pytest.approx(8.77e12,
                                                                 rel=1e-3)


def test_grid_must_be_a_power_of_two():
    with pytest.raises(ValueError):
        work.levels(96, 32)


def test_peaks_known_and_unknown_kinds():
    for kind in ("TPU v5 lite", "TPU v5e"):
        row = work.peaks(kind)
        assert row["bf16_flops"] == 197e12
        assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
