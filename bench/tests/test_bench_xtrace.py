"""The trace reduction: interval arithmetic, classification, and the numbers
it reads from a small trace recorded on the chip."""

import pathlib

import pytest

from bench import xtrace

TESTDATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"


def test_union_clips_and_merges():
    got = xtrace.union([(5, 9), (0, 2), (1, 3), (8, 12), (20, 30)], 1, 25)
    assert got == [(1, 3), (5, 12), (20, 25)]
    assert xtrace.total(got) == 14


def test_gaps_are_the_complement_in_the_window():
    busy = [(1, 3), (5, 12)]
    assert xtrace.gaps(busy, 0, 15) == [(0, 1), (3, 5), (12, 15)]
    assert xtrace.gaps([], 0, 4) == [(0, 4)]


def test_intersection_length():
    a = [(0, 4), (6, 10)]
    b = [(2, 7), (9, 20)]
    assert xtrace.intersect_total(a, b) == 2 + 1 + 1


def _op(text):
    return xtrace.Op(text, 0.0, 1.0)


def test_classification_of_ops_read_from_a_chip_trace():
    block = 4096
    ops = [
        # a recursion multiply: every shape holds two block dims
        "%fusion.541 = f32[2,2,4096,4096,1]{2,3,0,1,4:T(8,128)} fusion("
        "f32[2,2,4096,4096,1]{3,2,1,0,4:T(8,128)} %a, f32[2,2,4096,4096]"
        "{3,2,1,0:T(8,128)} %b), kind=kOutput, calls=%c",
        # the LU's own panel update: 128-wide operands, so not a multiply
        "%fusion.9 = f32[4096,4096]{1,0:T(8,128)} fusion(f32[4096,4096]"
        "{1,0:T(8,128)} %a, f32[3968,128]{1,0} %b, f32[128,3968]{1,0} %c), "
        "kind=kOutput, calls=%d",
        "%custom-call.423 = (f32[4096,128]{1,0}, s32[128]{0}) custom-call("
        "f32[4096,128]{1,0} %s), custom_call_target=\"LuDecompositionBlock\"",
        # a copy of the whole grid next to the leaf stays "other"
        "%copy.35 = f32[4,4096,4,4096]{3,2,1,0:T(4,128)} copy(f32[4,4096,4,"
        "4096]{3,1,2,0:T(8,128)} %bitcast.596)",
        "%fusion.544 = f32[2,4096,2,4096,1]{3,1,4,2,0:T(8,128)} fusion(f32"
        "[2,2,4096,4096,1]{3,2,1,0,4:T(8,128)} %a, f32[4,4,4096,4096]{3,2,1,"
        "0:T(8,128)} %b), kind=kOutput, calls=%c",
        "%pad.2 = f32[4,4,4096,4096]{3,2,1,0:T(8,128)} pad(f32[2,2,4096,4096]"
        "{3,2,1,0:T(8,128)} %b, f32[]{:T(128)} %c), padding=0_2x0_2x0_0x0_0",
        "%all-reduce.3 = f32[1024,1024]{1,0} all-reduce(f32[1024,1024]{1,0} "
        "%x), replica_groups={{0,1}}",
    ]
    got = xtrace.classify([_op(t) for t in ops], xtrace.load_rules(), block)
    assert got == ["gemm", "leaf", "leaf", "other", "gemm", "other",
                   "collective"]


def test_classification_of_pallas_kernels_and_async_collectives():
    """Texts as the compiled mesh program names them (v5e:2x2, block 1024):
    Pallas kernels carry their pallas_call's name."""
    ops = [
        "%matmul_pallas.35 = f32[1024,1024]{1,0:T(8,128)S(1)} custom-call("
        "%bitcast.134, %bitcast.132), custom_call_target=\"tpu_custom_call\"",
        "%fusion.3 = f32[1024,1024]{1,0} fusion(f32[1024,1024]{1,0} %a), "
        "kind=kLoop, calls=%f",
        "%blocked_leaf_inverse_pallas = f32[1,1024,1024]{2,1,0:T(8,128)S(1)} "
        "custom-call(%x), custom_call_target=\"tpu_custom_call\"",
        "%all-gather-start.1 = (f32[1024,1024]{1,0}, f32[2048,1024]{1,0}) "
        "all-gather-start(f32[1024,1024]{1,0} %y), dimensions={0}",
        "%collective-permute-done = f32[1024,1024]{1,0} "
        "collective-permute-done((f32[1024,1024]{1,0}) %z)",
    ]
    got = xtrace.classify([_op(t) for t in ops], xtrace.load_rules(), 1024)
    assert got == ["gemm", "leaf", "leaf", "collective", "collective"]


def test_op_labels_drop_operands_and_layouts():
    text = ("%fusion.541 = f32[2,2,4096,4096,1]{2,3,0,1,4:T(8,128)} fusion("
            "f32[2,2,4096,4096,1]{3,2,1,0,4:T(8,128)} %a), kind=kOutput")
    assert xtrace.op_label(_op(text), "jit_f") == (
        "jit_f/fusion.541 f32[2,2,4096,4096,1] fusion/kOutput")


TRACE = TESTDATA / "inverse-n1024-v5e.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    """One n=1024 (block 256) inversion traced on a TPU v5 lite chip by the
    harness's own tracing, with the LU leaves and the recursion's GEMMs."""
    return xtrace.summarize(xtrace.load(TRACE), block=256)


def test_reduction_of_a_recorded_chip_trace(recorded):
    s = recorded
    assert s.devices == [0]
    assert s.window_s == pytest.approx(0.00182378, rel=1e-9)
    assert s.busy_s[0] == pytest.approx(0.000680644, rel=1e-9)
    assert s.class_s == pytest.approx({"leaf": 0.000613842,
                                       "gemm": 5.8631e-05,
                                       "other": 2.1934e-05}, rel=1e-9)
    assert sum(s.class_s.values()) >= s.busy_s[0]
    assert s.module_runs == {"jit__spin_inverse_dense": 1}
    assert s.idle_gaps[0] == ("bench.offline", pytest.approx(0.001142917))
    assert s.top_ops[0] == ("jit__spin_inverse_dense/custom-call.38",
                            pytest.approx(4.9563e-05))
    assert len(xtrace.load(TRACE).ops[0]) == 260


def test_metric_readers_on_the_recorded_trace(recorded):
    from types import SimpleNamespace

    from bench import harness, work

    spec = harness.Cell(TESTDATA.parents[1], "inverse-n16384")
    cell = SimpleNamespace(config={"n": 1024, "block_size": 256},
                           mix={}, chips=1)
    ctx = harness.Context(cell, SimpleNamespace(
        counters={"calls_traced": 1}), recorded, work.peaks("TPU v5 lite"))
    got = {m["name"]: spec.metric_reader(m)(ctx) for m in spec.per_layer}
    # At this size the multiplies' bytes, not their FLOPs, bound them.
    least = max(work.inverse_gemm_flops(1024, 256) / 197e12,
                work.inverse_gemm_bytes(1024, 256) / 819e9)
    assert least == work.inverse_gemm_bytes(1024, 256) / 819e9
    assert got["gemm_roofline.inverse"] == pytest.approx(
        100 * least / 5.8631e-05)
    assert got["leaf_ms.inverse"] == pytest.approx(0.613842)
    assert got["idle_share.inverse"] == pytest.approx(
        100 * (1 - 0.000680644 / 0.00182378))
    assert got["mfu.inverse"] == pytest.approx(
        100 * work.inverse_flops(1024, 256) / (0.00182378 * 197e12))
    assert all(0 < v < 100 for v in got.values())
