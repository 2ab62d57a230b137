"""Tiny cells for CPU tests: a copy of the benchmark tree whose
`BENCHMARK.json` names small configurations, written next to the real ones
the way a later change would add a cell."""

from __future__ import annotations

import copy
import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]

TINY_CONFIG = {"n": 256, "block_size": 64, "grid": 4, "reduced": ["n"]}
# A service over the tiny configuration: YCSB workload B's read/update
# ratio and zipfian skew at a rate a CPU keeps up with.
TINY_SERVICE = {"slots": 4, "drift_probes": 2}
SOLVE_RESIDUAL_MAX = 6e-6
TINY_SERVICE_MIX = {"loop": "open_service", "tenants": 3,
                    "zipf_theta": 0.99, "update_share": 0.05,
                    "solve_cols": 16, "update_rank": 8, "rate_per_s": 60.0,
                    "panel_pool": 8, "factor_pool": 8, "check_share": 0.5,
                    "trace_seconds": 0.5}


def _loop(root: pathlib.Path, spec: dict, cell: str) -> str:
    w = next(w for w in spec["workloads"] if w["name"] == cell)
    mix = root / "bench/traffic" / f"{w['traffic']}.json"
    return json.loads(mix.read_text())["loop"]


def make_tree(tmp: pathlib.Path, *, mesh: bool = False) -> pathlib.Path:
    """A checkout-like tree under `tmp` with the cells `tiny-inverse` and
    `tiny-serve` (and `tiny-mesh` on a (2, 2) mesh when `mesh`). Each metric
    of a real inverse cell is listed for the tiny inverse cells; the service
    cell gets the service's latency metrics."""
    root = tmp / "tree"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    (root / "src").symlink_to(REPO / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    base = json.loads((root / "bench/configs/spd-n16384-f32.json").read_text())
    tiny = dict(copy.deepcopy(base), name="tiny-f32", service=TINY_SERVICE,
                **TINY_CONFIG)
    tiny["guarantee"]["limits"]["solve_residual_max"] = SOLVE_RESIDUAL_MAX
    (root / "bench/configs/tiny-f32.json").write_text(json.dumps(tiny))
    configs = [{"name": "tiny-f32", "source": "https://arxiv.org/abs/1801.04723",
                "file": "bench/configs/tiny-f32.json", "reduced": ["n"],
                "why": "CPU test size"}]
    (root / "bench/traffic/tiny-service.json").write_text(
        json.dumps(TINY_SERVICE_MIX))
    cells = [
        {"name": "tiny-inverse", "config": "tiny-f32",
         "traffic": "inverse-closed", "chips": 1, "why": "test"},
        {"name": "tiny-serve", "config": "tiny-f32",
         "traffic": "tiny-service", "chips": 1, "why": "test"}]
    inverse_cells = ["tiny-inverse"]
    if mesh:
        big = json.loads(
            (root / "bench/configs/spd-n32768-f32-mesh4.json").read_text())
        small = dict(big, name="tiny-mesh", n=512, block_size=64, grid=8,
                     leaf_solver="linalg", engine="einsum")
        (root / "bench/configs/tiny-mesh.json").write_text(json.dumps(small))
        configs.append(dict(configs[0], name="tiny-mesh",
                            file="bench/configs/tiny-mesh.json"))
        cells.append({"name": "tiny-mesh", "config": "tiny-mesh",
                      "traffic": "inverse-closed", "chips": 4,
                      "why": "test"})
        inverse_cells.append("tiny-mesh")
    loops = {w["name"]: _loop(root, spec, w["name"])
             for w in spec["workloads"]}
    tiny_spec = copy.deepcopy(spec)
    for group in ("end_to_end", "per_layer"):
        for m in tiny_spec[group]:
            if "workloads" in m:
                assert {loops[w] for w in m["workloads"]} == {
                    "closed_inverse"}, m["name"]
                m["workloads"] = list(inverse_cells)
    tiny_spec["end_to_end"] += [
        {"name": name, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": ["tiny-serve"]}
        for name in ("solve_p50_ms", "solve_p99_ms")]
    tiny_spec["configs"] = configs
    tiny_spec["workloads"] = cells
    (root / "BENCHMARK.json").write_text(json.dumps(tiny_spec, indent=1))
    return root
