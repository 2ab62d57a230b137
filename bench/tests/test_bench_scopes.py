"""The trace joined to the program's named scopes (`bench/scopes.py`): the
per-level and layout sums and the unscoped share on two small traces
recorded on a TPU v5 lite chip with their scope maps, the idle time inside
the program's entry span on a constructed trace, and the three readers
through the harness's `Context`."""

import pathlib
import shutil
from types import SimpleNamespace

import pytest

from bench import harness, scopes, xtrace

TESTDATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"
REPO = TESTDATA.parents[1]
# Written by `python3 -m bench.scopes record` on the chip: two inversions in
# the traced window (the einsum trace caught one program run of the two).
RECORDED = {
    "einsum": ("scopes-einsum-n1024-v5e", {
        "n": 1024, "block_size": 256, "leaf_solver": "linalg",
        "engine": "einsum"}),
    "pallas": ("scopes-pallas-n2048-v5e", {
        "n": 2048, "block_size": 256, "leaf_solver": "pallas",
        "engine": "pallas"}),
}


def _times(engine, block=None):
    stem, config = RECORDED[engine]
    path = TESTDATA / f"{stem}.xplane.pb"
    return scopes.reduce(xtrace.load(path), scopes.entry_spans(path),
                         scopes.load_scopes(TESTDATA / f"{stem}.scopes.json"),
                         block)


@pytest.mark.parametrize("engine,levels,layout_s,gemm_s,leaf_s", [
    ("einsum", [5.7368e-05, 1.6396e-05, 0.000623336], 2.8169e-05, 7.2024e-05,
     0.000623336),
    ("pallas", [0.002426169, 0.00095632, 0.000125564, 0.001082443],
     0.000903602, 0.002698269, 0.001082443),
])
def test_level_and_layout_sums_of_a_recorded_trace(engine, levels, layout_s,
                                                   gemm_s, leaf_s):
    t = _times(engine)
    assert [t.level_s(i) for i in range(len(levels))] == pytest.approx(
        levels, rel=1e-9)
    assert t.steps_s(scopes.LAYOUT_STEPS) == pytest.approx(layout_s,
                                                           rel=1e-9)
    assert t.steps_s(scopes.GEMM_STEPS) == pytest.approx(gemm_s, rel=1e-9)
    assert t.steps_s(("leaf",)) == pytest.approx(leaf_s, rel=1e-9)
    # The leaves are the deepest level and nothing else is there.
    assert t.level_s(len(levels) - 1) == pytest.approx(leaf_s, rel=1e-12)
    # Every op's time lands under exactly one scope.
    summary = xtrace.summarize(
        xtrace.load(TESTDATA / f"{RECORDED[engine][0]}.xplane.pb"), 256)
    assert t.device_s == pytest.approx(sum(summary.class_s.values()),
                                       rel=1e-9)


@pytest.mark.parametrize("engine", ["einsum", "pallas"])
def test_unscoped_device_time_under_one_percent(engine):
    t = _times(engine)
    assert t.device_s > 0
    assert t.unscoped_s / t.device_s < 0.01
    assert t.scope_s.get((None, scopes.LAYOUT), 0.0) > 0


@pytest.mark.parametrize("engine,gemm_s,leaf_s", [
    ("einsum", 5.8632e-05, 0.000634219),
    ("pallas", 0.002528931, 0.001084962),
])
def test_scopes_against_opclasses(engine, gemm_s, leaf_s):
    """The classes of `opclasses.json` and the scopes, crossed: what each
    calls a GEMM or a leaf, and where they part."""
    t = _times(engine, block=256)
    by_class = {}
    for (cls, _), s in t.class_scope_s.items():
        by_class[cls] = by_class.get(cls, 0.0) + s
    assert by_class["gemm"] == pytest.approx(gemm_s, rel=1e-9)
    assert by_class["leaf"] == pytest.approx(leaf_s, rel=1e-9)
    # Every op the classes call a GEMM is in a product step...
    assert t.class_scope_s["gemm", "gemm"] == pytest.approx(gemm_s,
                                                            rel=1e-9)
    # ...and nearly all of the classes' leaf time is in a leaf scope.
    assert t.class_scope_s["leaf", "leaf"] > 0.98 * leaf_s


def _op(name, start, end):
    return xtrace.Op(name, float(start), float(end))


def test_dispatch_idle_is_idle_time_inside_the_entry_span():
    """Device busy [10, 40) and [50, 90) in a window [0, 100); the entry
    span covers [5, 15) and [45, 52): of the idle [0, 10), [40, 50) and
    [90, 100), the span holds 5 + 5 ns."""
    trace = xtrace.Trace(
        ops={0: [_op("%fusion.1 = f32[4]{0} fusion()", 10, 40),
                 _op("%fusion.2 = f32[4]{0} fusion()", 50, 90)]},
        modules={0: [_op("jit_f(1)", 10, 90)]},
        spans=[_op(xtrace.WINDOW_SPAN, 0, 100)])
    spans = [_op("spin.inverse_dense", 5, 15), _op("spin.inverse_dense", 45,
                                                   52)]
    got = {"jit_f": {"fusion.1": (0, "II"), "fusion.2": (None, "spin.layout")}}
    t = scopes.reduce(trace, spans, got)
    assert t.dispatch_idle_s == pytest.approx(10e-9)
    assert t.scope_s == {(0, "II"): pytest.approx(30e-9),
                         (None, "spin.layout"): pytest.approx(40e-9)}
    # Without the program's span there is nothing to read.
    assert scopes.reduce(trace, [], got).dispatch_idle_s is None


NEW = ("top_level_ms.inverse", "layout_ms.inverse",
       "dispatch_idle_ms.inverse")


def _context(tmp_path, engine, calls, config=None):
    stem, recorded = RECORDED[engine]
    trace_dir = tmp_path / ".bench_out" / "trace" / "plugins" / "profile"
    trace_dir.mkdir(parents=True)
    shutil.copy(TESTDATA / f"{stem}.xplane.pb", trace_dir)
    cell = SimpleNamespace(root=tmp_path, config=config or recorded, mix={},
                           chips=1)
    return harness.Context(cell, SimpleNamespace(
        counters={"calls_traced": calls}), None, None)


@pytest.fixture
def recorded_scopes(monkeypatch):
    """The join as the chip made it: the scope map stored with the trace
    in place of a compile here, whose instruction names are the CPU's."""
    def program_scopes(config, chips):
        stem = next(s for s, c in RECORDED.values() if c == config)
        return scopes.load_scopes(TESTDATA / f"{stem}.scopes.json")

    monkeypatch.setattr(scopes, "program_scopes", program_scopes)
    scopes._cell_times.cache_clear()
    yield
    scopes._cell_times.cache_clear()


def _read(ctx):
    spec = harness.Cell(REPO, "inverse-pallas-n16384")
    return {m["name"]: spec.metric_reader(m)(ctx) for m in spec.per_layer
            if m["name"] in NEW}


def test_readers_through_the_harness_context(tmp_path, recorded_scopes):
    got = _read(_context(tmp_path, "pallas", calls=2))
    assert set(got) == set(NEW)
    assert got["top_level_ms.inverse"] == pytest.approx(
        1000 * 0.002426169 / 2, rel=1e-9)
    assert got["layout_ms.inverse"] == pytest.approx(1000 * 0.000903602 / 2,
                                                     rel=1e-9)
    assert got["dispatch_idle_ms.inverse"] == pytest.approx(
        1000 * 8.9e-08 / 2, rel=1e-6)


def test_readers_read_nothing_without_scopes_or_trace(tmp_path,
                                                      monkeypatch):
    """A program older than the names (the join finds no function) and a
    run without a trace both read as no value, not as an error."""
    from repro.core import spin

    scopes._cell_times.cache_clear()
    monkeypatch.delattr(spin, "inverse_op_scopes")
    assert set(_read(_context(tmp_path, "pallas", calls=2)).values()) == {
        None}
    scopes._cell_times.cache_clear()
    ctx = harness.Context(SimpleNamespace(root=tmp_path / "none", config={},
                                          mix={}, chips=1),
                          SimpleNamespace(counters={}), None, None)
    assert set(_read(ctx).values()) == {None}


def test_table_command_on_a_recorded_trace(capsys):
    stem = RECORDED["pallas"][0]
    assert scopes.main(["table", str(TESTDATA / f"{stem}.xplane.pb"),
                        str(TESTDATA / f"{stem}.scopes.json"), "--calls",
                        "2", "--block", "256"]) == 0
    out = capsys.readouterr().out
    assert "| L0 |" in out and "| L3 |" in out
    assert "unscoped 0.0000%" in out
