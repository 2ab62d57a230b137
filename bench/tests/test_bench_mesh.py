"""The mesh cell's readers (`bench/mesh.py`, `exposed_collective_ms.inverse`,
`replicated_ms.inverse`) on a trace recorded on four TPU v5 lite chips
with its scope map, the existing readers on the same trace, what none of
them may read elsewhere, the replicated-level rule against the program's
own counter, and the pruning of a recording."""

import pathlib
import shutil
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import harness, mesh, scopes, work, xtrace

TESTDATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"
REPO = TESTDATA.parents[1]
CELL = "inverse-mesh4-n32768"
NEW = ("exposed_collective_ms.inverse", "replicated_ms.inverse")
# Written by `python3 -m bench.mesh record --n 4096 --block 512` on four
# chips: two inversions in the traced window.
STEM = "scopes-pallas-mesh4-n4096-v5e"
RECORDED = dict(mesh.RECORD_CONFIG, n=4096, block_size=512)
CALLS = 2


@pytest.fixture
def recorded_scopes(monkeypatch):
    """The join as the chip made it: the scope map stored with the trace in
    place of a compile here, whose instruction names are the CPU's."""
    monkeypatch.setattr(scopes, "program_scopes", lambda config, chips: (
        scopes.load_scopes(TESTDATA / f"{STEM}.scopes.json")))
    scopes._cell_times.cache_clear()
    yield
    scopes._cell_times.cache_clear()


def _context(tmp_path, config=RECORDED, chips=4, trace=True, summary=None,
             peaks=None):
    if trace:
        trace_dir = tmp_path / ".bench_out" / "trace" / "plugins" / "profile"
        trace_dir.mkdir(parents=True)
        shutil.copy(TESTDATA / f"{STEM}.xplane.pb", trace_dir)
    cell = SimpleNamespace(root=tmp_path, config=config, mix={}, chips=chips)
    return harness.Context(cell, SimpleNamespace(
        counters={"calls_traced": CALLS}), summary, peaks)


def _read(ctx, names=NEW):
    spec = harness.Cell(REPO, CELL)
    return {m["name"]: spec.metric_reader(m)(ctx) for m in spec.per_layer
            if m["name"] in names}


def _times():
    path = TESTDATA / f"{STEM}.xplane.pb"
    return scopes.reduce(xtrace.load(path), scopes.entry_spans(path),
                         scopes.load_scopes(TESTDATA / f"{STEM}.scopes.json"))


def test_recording_is_small_and_holds_four_devices():
    assert (TESTDATA / f"{STEM}.xplane.pb").stat().st_size < 1_000_000
    trace = xtrace.load(TESTDATA / f"{STEM}.xplane.pb")
    assert sorted(trace.ops) == [0, 1, 2, 3]
    assert len(scopes.entry_spans(TESTDATA / f"{STEM}.xplane.pb")) == CALLS


def test_new_readers_read_the_recording(tmp_path, recorded_scopes):
    got = _read(_context(tmp_path))
    times = _times()
    levels = mesh.replicated_levels(4096, 512, (2, 2))
    assert levels == [2, 3]
    assert got["replicated_ms.inverse"] == pytest.approx(
        1000 * sum(times.level_s(k) for k in levels) / CALLS, rel=1e-12)
    assert got["replicated_ms.inverse"] == pytest.approx(
        1000 * 0.02034043 / CALLS, rel=1e-9)
    # Mean over the four devices; the collectives there are synchronous,
    # so nearly all of their time is exposed.
    assert got["exposed_collective_ms.inverse"] == pytest.approx(
        1000 * 0.006776824 / CALLS, rel=1e-9)
    summary = xtrace.summarize(xtrace.load(TESTDATA / f"{STEM}.xplane.pb"),
                               512)
    per_device = summary.class_s["collective"] / 4
    exposed_s = got["exposed_collective_ms.inverse"] * CALLS / 1000
    assert 0.9 * per_device < exposed_s <= per_device


def test_exposed_collective_is_collective_time_no_other_op_covers():
    """Device 0: a gather [10, 40) under a GEMM [30, 60), a permute [70, 80)
    alone; device 1: nothing but a GEMM. Exposed: 20 + 10 ns on device 0,
    none on device 1, so 15 ns as the mean; the window is [0, 100)."""
    op = xtrace.Op
    gather = "%all-gather.1 = f32[8,8]{1,0} all-gather(f32[4,8]{1,0} %p)"
    permute = ("%collective-permute.2 = f32[8,8]{1,0} "
               "collective-permute(f32[8,8]{1,0} %q)")
    gemm = "%matmul_pallas.3 = f32[8,8]{1,0} custom-call(%a, %b)"
    trace = xtrace.Trace(
        ops={0: [op(gather, 10, 40), op(gemm, 30, 60), op(permute, 70, 80)],
             1: [op(gemm, 0, 50)]},
        modules={}, spans=[op(xtrace.WINDOW_SPAN, 0, 100)])
    assert mesh.exposed_collective_s(trace, 8) == pytest.approx(15e-9)
    assert mesh.exposed_collective_s(xtrace.Trace({}, {}, []), 8) is None


@pytest.mark.parametrize("case", ["no_trace", "one_chip", "no_scopes"])
def test_new_readers_read_nothing_off_the_mesh(tmp_path, monkeypatch,
                                              recorded_scopes, case):
    """No trace, a one-chip cell's configuration (here with the mesh trace
    in place), and a program older than the names: no value, no error."""
    if case == "no_trace":
        ctx = _context(tmp_path, trace=False)
    elif case == "one_chip":
        one = {k: v for k, v in RECORDED.items() if k != "mesh"}
        ctx = _context(tmp_path, config=one, chips=1)
    else:
        monkeypatch.setattr(scopes, "program_scopes",
                            lambda config, chips: None)
        ctx = _context(tmp_path)
    assert _read(ctx) == dict.fromkeys(NEW)


EXISTING = ("idle_share.inverse", "mfu.inverse", "gemm_roofline.inverse",
            "leaf_ms.inverse", "top_level_ms.inverse", "layout_ms.inverse",
            "dispatch_idle_ms.inverse")


def test_listed_readers_read_a_four_device_trace(tmp_path, recorded_scopes):
    """Every existing per-layer metric that lists the mesh cell gives a
    value on a four-chip trace of its program."""
    summary = xtrace.summarize(xtrace.load(TESTDATA / f"{STEM}.xplane.pb"),
                               512)
    ctx = _context(tmp_path, summary=summary, peaks=work.peaks(
        "TPU v5 lite"))
    got = _read(ctx, EXISTING)
    assert set(got) == set(EXISTING)
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert 0 < got["idle_share.inverse"] < 100
    assert 0 < got["gemm_roofline.inverse"] < 100
    assert got["top_level_ms.inverse"] > 0 and got["leaf_ms.inverse"] > 0


def _program_replicated_gemms(n, bs):
    """The program's own `replicated_block_gemms`, counted while tracing
    the recursion under a (2, 2) mesh with no device behind it."""
    from jax.sharding import AbstractMesh, AxisType

    from repro.core import count_ops
    from repro.core.multiply import multiply_engine
    from repro.parallel import ShardedBlockMatrix, sharded_spin_inverse

    grid = n // bs

    def inverse(blocks):
        with multiply_engine("pallas"):
            return sharded_spin_inverse(
                ShardedBlockMatrix(blocks).constrain(), "pallas").blocks

    abstract = AbstractMesh((2, 2), ("data", "model"),
                            axis_types=(AxisType.Auto,) * 2)
    with jax.sharding.use_abstract_mesh(abstract), count_ops() as counts:
        jax.eval_shape(inverse, jax.ShapeDtypeStruct((grid, grid, bs, bs),
                                                     jnp.float32))
    return counts.replicated_block_gemms, counts.replicated_leaves


@pytest.mark.parametrize("n,bs", [(4096, 512), (16384, 1024),
                                  (32768, 1024)])
def test_replicated_rule_agrees_with_the_programs_counter(n, bs):
    """Six products of h-block quadrants, h³ GEMMs each, at every node of a
    replicated depth above the leaves; the leaf depth is the last one."""
    grid = n // bs
    levels = mesh.replicated_levels(n, bs, (2, 2))
    gemms, leaves = _program_replicated_gemms(n, bs)
    assert gemms == sum(2 ** k * 6 * (grid >> (k + 1)) ** 3
                        for k in levels[:-1])
    assert leaves == grid
    assert levels[-1] == grid.bit_length() - 1


def test_recorded_gathers_lie_outside_the_replicated_levels():
    """In the chip's scope map, the levels with a `gather` step are the
    internal levels the rule does not call replicated."""
    (ops,) = scopes.load_scopes(TESTDATA / f"{STEM}.scopes.json").values()
    gathered = {lv for lv, st in ops.values() if st == "gather"}
    internal = range((4096 // 512).bit_length() - 1)
    assert gathered == set(internal) - set(
        mesh.replicated_levels(4096, 512, (2, 2)))


def test_table_command_shows_the_gather_step(capsys):
    assert mesh.main(["table", str(TESTDATA / f"{STEM}.xplane.pb"),
                      str(TESTDATA / f"{STEM}.scopes.json"), "--calls",
                      str(CALLS), "--block", "512"]) == 0
    out = capsys.readouterr().out
    assert "| level |" in out and "gather" in out.splitlines()[0]
    assert "| L0 |" in out and "| L3 |" in out
    assert "exposed collective" in out


def test_pruned_recording_reads_as_the_whole(tmp_path):
    """Pruning drops the HLO protos and per-op statistics and keeps every
    op, span and window the readers read."""
    whole = TESTDATA / "scopes-pallas-n2048-v5e.xplane.pb"
    pruned = tmp_path / "pruned.xplane.pb"
    mesh.prune(whole, pruned)
    assert pruned.stat().st_size < whole.stat().st_size / 4
    a, b = xtrace.load(whole), xtrace.load(pruned)
    assert a == b
    assert scopes.entry_spans(whole) == scopes.entry_spans(pruned)
