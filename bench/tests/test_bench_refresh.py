"""The K-FAC refresh cell's loop through the harness on the CPU: a tiny stack
of damped Gram factors comes out correct with the program, and incorrect
with the control in its place; its scope metric reads nothing without a
trace."""

import copy
import json
import shutil
import time
import types

import pytest

from bench import harness
from bench.tests import tiny

CELL = "refresh-kfac-granite8b-n4096x36"
TINY = "tiny-refresh"
# The CPU's f32 recursion reads 1.3e-2 to 1.7e-2 on these factors (n 256,
# κ ≈ 5.8e3, PRNGKey seeds 0-2, any plan), above the chip cell's limit that
# the chip's 9.5e-3 meets; a bf16 control reads above 1.
TINY_RESIDUAL_MAX = 3e-2


def _tree(tmp_path, **plan):
    """A checkout-like tree whose one cell is the refresh cell at n = 256,
    a stack of 3, rank n/2 and block 64, under the real cell's mix and a
    limit for the CPU."""
    root = tmp_path / "tree"
    shutil.copytree(tiny.REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    (root / "src").symlink_to(tiny.REPO / "src")
    spec = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    config.update(name=TINY, n=256, stack=3, rank=128, block_size=64, grid=4,
                  **{"leaf_solver": "linalg", "engine": "einsum", **plan})
    config["guarantee"]["limits"]["inverse_residual_max"] = TINY_RESIDUAL_MAX
    (root / "bench/configs/tiny-refresh.json").write_text(json.dumps(config))
    out = copy.deepcopy(spec)
    out["configs"] = [dict(entry, name=TINY,
                           file="bench/configs/tiny-refresh.json")]
    out["workloads"] = [dict(cell, name=TINY, config=TINY)]
    for group in ("end_to_end", "per_layer"):
        out[group] = [dict(m, workloads=[TINY]) if "workloads" in m else m
                      for m in spec[group]
                      if CELL in m.get("workloads", [CELL])]
    (root / "BENCHMARK.json").write_text(json.dumps(out, indent=1))
    return root


def _run(root, seconds=1.0, seed=2 ** 33 + 19):
    return harness.run(["--workload", TINY, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", "0"],
                       root=root, t_start=time.perf_counter(),
                       devices_for=harness.any_devices)


def test_the_cell_reports_what_the_benchmark_lists():
    cell = harness.Cell(tiny.REPO, CELL)
    assert cell.mix["loop"] == "closed_refresh"
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "inverse_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "idle_share.inverse", "mfu.inverse", "gemm_roofline.inverse",
        "leaf_ms.inverse", "stack_overhead_ms.inverse"}
    c = cell.config
    assert (c["n"], c["stack"], c["rank"]) == (4096, 36, 2048)
    assert c["n"] // c["block_size"] == c["grid"]


@pytest.mark.parametrize("plan", [
    {}, {"leaf_solver": "pallas", "engine": "pallas"}],
    ids=["einsum-linalg", "pallas-pallas"])
def test_refresh_cell_runs_correct(tmp_path, plan):
    out = _run(_tree(tmp_path, **plan))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["window_compiles"] == 0
    counters = out["counters"]
    assert out["attempted"] == counters["matrices"] == 3 * counters["calls"]
    assert counters["matrices"] > 0
    assert out["checks"]["answers_checked"]["value"] == 2
    assert set(out["metrics"]) == {"setup_s", "inverse_s"}


def test_control_in_the_programs_place_makes_the_run_incorrect(tmp_path):
    """The loop's control answers every refresh in the program's place, and
    the harness's own comparison fails it. On the chip the control is HIGH;
    a CPU computes f32 exactly at any precision, so one bf16 pass stands
    in, as for the other cells."""
    root = _tree(tmp_path)
    cell = harness.Cell(root, TINY)
    with cell.loop().control("bf16"):
        out = _run(root)
    assert not out["correct"]
    assert out["attempted"] > 0
    with cell.loop().control("highest"):
        assert _run(root)["correct"]


def test_stack_overhead_reads_nothing_without_a_trace(tmp_path):
    root = _tree(tmp_path)
    cell = harness.Cell(root, TINY)
    (entry,) = [m for m in cell.per_layer
                if m["name"] == "stack_overhead_ms.inverse"]
    ctx = types.SimpleNamespace(cell=cell, config=cell.config, chips=1,
                                counters={"calls_traced": 3}, summary=None,
                                peaks=None)
    assert cell.metric_reader(entry)(ctx) is None
    ctx.cell = types.SimpleNamespace()        # no checkout to look in
    assert cell.metric_reader(entry)(ctx) is None
