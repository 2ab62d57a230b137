"""The harness finds cells, mixes and metrics by name, refuses to run
without a chip, and runs tiny cells end to end on the CPU."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests import tiny

REPO = tiny.REPO


def _run(root, workload, trace=0, seconds=1.0, seed=2 ** 32 + 5):
    return harness.run(["--workload", workload, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", str(trace)],
                       root=root, t_start=time.perf_counter(),
                       devices_for=harness.any_devices)


def test_benchmark_entries_resolve_to_files():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.Cell(REPO, w["name"])
        assert cell.config["n"] % cell.config["block_size"] == 0
        assert callable(cell.loop().run)
        assert cell.per_layer, w["name"]
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert callable(cell.metric_reader(m))
    files = {c["file"] for c in spec["configs"]}
    assert len(files) == len(spec["configs"])


NEW_LOOP = '''"""A closed loop of squarings A·A, checked against NumPy."""

import jax
import jax.numpy as jnp
import numpy as np

from bench import data
from bench.common import Check, Outcome, Window


def run(config, mix, *, seed, seconds, trace_dir, devices, t_start, limits):
    a = data.spd_matrix(int(config["n"]), seed, 0)
    square = jax.jit(lambda a: jnp.matmul(a, a, precision="highest"))
    square(a).block_until_ready()
    window = Window(seconds, trace_dir, float(mix["trace_seconds"]))
    t0, calls = window.open(), 0
    while window.elapsed() < seconds:
        window.poll()
        x = square(a)
        x.block_until_ready()
        calls += 1
    window.stop_trace()
    t_end = window.close()
    a64 = np.asarray(a, np.float64)
    err = float(np.abs(np.asarray(x) - a64 @ a64).max())
    return Outcome(metrics={"setup_s": t0 - t_start,
                            "square_s": (t_end - t0) / calls},
                   attempted=calls, failed=0,
                   checks=[Check("square_error_max", err, "<=", 1e-3,
                                 err <= 1e-3)],
                   counters={"calls": calls}, memory_peak_bytes=0,
                   window_compiles=window.compiles)
'''


def test_a_new_cell_mix_and_metric_are_found_by_name(tmp_path):
    """What a later change adds: a configuration file, a mix file, a metric
    file and their entries; no file that was there is edited."""
    root = tiny.make_tree(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    mix = json.loads((root / "bench/traffic/inverse-closed.json").read_text())
    mix.update(matrices=3, sample_count=3)
    (root / "bench/traffic/three-closed.json").write_text(json.dumps(mix))
    (root / "bench/metrics/calls.tiny.py").write_text(
        'LAYER = "Entry points"\nUNIT = "calls"\nSOURCE = "program_counter"\n'
        'MOVES = "inverse_s"\n\n\ndef read(ctx):\n'
        '    return float(ctx.counters["calls"])\n')
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny-three", "config": "tiny-f32",
                              "traffic": "three-closed", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "inverse_s":
            m["workloads"].append("tiny-three")
    spec["per_layer"].append({"name": "calls.tiny", "unit": "calls",
                              "better": "higher", "source": "program_counter",
                              "layer": "Entry points", "moves": "inverse_s",
                              "workloads": ["tiny-three"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = _run(root, "tiny-three", trace=1, seconds=2.0)
    assert out["correct"]
    assert out["metrics"]["calls.tiny"]["value"] == out["attempted"]
    assert out["checks"]["answers_checked"]["value"] == min(
        3, out["attempted"])
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_new_loop_kind_is_found_by_name(tmp_path):
    """A traffic mix that names a loop no file had yet: the loop is a new
    file in bench/loops/, with its own end-to-end metric; nothing that was
    there is edited."""
    root = tiny.make_tree(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench/loops/closed_square.py").write_text(NEW_LOOP)
    (root / "bench/traffic/square-closed.json").write_text(
        json.dumps({"loop": "closed_square", "trace_seconds": 0.5}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny-square", "config": "tiny-f32",
                              "traffic": "square-closed", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "square_s", "unit": "s",
                               "better": "lower", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["tiny-square"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = _run(root, "tiny-square", seconds=0.5)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "square_s"}
    assert out["attempted"] == out["counters"]["calls"] > 0
    assert all(p.read_bytes() == b for p, b in before.items())


@pytest.mark.parametrize("workload", ["tiny-inverse", "tiny-serve"])
def test_tiny_cells_run_correct(tmp_path, workload):
    root = tiny.make_tree(tmp_path)
    out = _run(root, workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    names = {"tiny-inverse": {"setup_s", "inverse_s"},
             "tiny-serve": {"setup_s", "solve_p50_ms", "solve_p99_ms"}}
    assert set(out["metrics"]) == names[workload]
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["count"] == 1


def test_metric_entry_and_file_must_agree(tmp_path):
    root = tiny.make_tree(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    entry = next(m for m in spec["per_layer"] if m["name"] == "mfu.inverse")
    cell = harness.Cell(root, "tiny-inverse")
    with pytest.raises(ValueError, match="UNIT"):
        cell.metric_reader(dict(entry, unit="ms"))


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ("--workload", "inverse-n16384", "--seed", "1", "--seconds", "1",
        "--trace", "0")


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _cli(REPO, *ARGS)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _cli(tmp_path, *ARGS)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert not pathlib.Path(tmp_path / "src").exists()
