"""Run one cell of `BENCHMARK.json` once and print its result line.

Everything is found by name: the cell's configuration in the file its
`configs` entry names, its traffic mix in `bench/traffic/<traffic>.json`,
the loop that mix names (its `"loop"` key) in `bench/loops/<loop>.py`, and
each per-layer metric in `bench/metrics/<metric>.py`. A later cell, mix,
loop or metric is a new file and a new entry; no file here changes.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import shutil
import sys

from . import work, xtrace
from .common import Outcome


def load_file(path: pathlib.Path):
    """The module in `path`, loaded by its file name."""
    name = f"bench_{path.parent.name}_{path.stem}".replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload with its configuration, mix and metric definitions."""

    def __init__(self, root: pathlib.Path, name: str):
        self.root = pathlib.Path(root)
        self.bench = self.root / "bench"
        spec = json.loads((self.root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r} (known: "
                             f"{sorted(cells)})")
        self.workload = cells[name]
        self.name = name
        entry = {c["name"]: c for c in spec["configs"]}[
            self.workload["config"]]
        self.config = json.loads((self.root / entry["file"]).read_text())
        self.mix = json.loads((self.bench / "traffic" /
                               f"{self.workload['traffic']}.json").read_text())
        self.chips = int(self.workload["chips"])
        self.end_to_end = [m for m in spec["end_to_end"] if self._mine(m)]
        self.per_layer = [m for m in spec["per_layer"] if self._mine(m)
                          and any(e["name"] == m["moves"]
                                  for e in self.end_to_end)]

    def _mine(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def loop(self):
        """The loop module in bench/loops/<loop>.py that the mix names."""
        return load_file(self.bench / "loops" / f"{self.mix['loop']}.py")

    def metric_reader(self, metric: dict):
        """The reader in bench/metrics/<name>.py, checked against its entry."""
        path = self.bench / "metrics" / f"{metric['name']}.py"
        mod = load_file(path)
        for key in ("layer", "unit", "source", "moves"):
            if getattr(mod, key.upper()) != metric[key]:
                raise ValueError(f"{path.name}: {key.upper()}="
                                 f"{getattr(mod, key.upper())!r} but "
                                 f"BENCHMARK.json says {metric[key]!r}")
        return mod.read


def require_chips(chips: int):
    """The devices a cell runs on: TPUs only, at least `chips` of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform!r} "
                         f"({len(devices)} device(s)); the benchmark never "
                         "falls back to another platform")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def any_devices(chips: int):
    """For tests on the CPU: the first `chips` devices, whatever they are."""
    import jax

    return jax.devices()[:chips]


class Context:
    """What a per-layer metric reads: the trace summary (or None), the
    loop's counters, and the cell's configuration, mix and peaks."""

    def __init__(self, cell: Cell, outcome: Outcome, summary,
                 peaks: dict | None):
        self.cell = cell
        self.config = cell.config
        self.mix = cell.mix
        self.chips = cell.chips
        self.counters = outcome.counters
        self.summary = summary
        self.peaks = peaks


def parse(argv):
    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def run(argv, *, root, t_start: float, devices_for=require_chips) -> dict:
    """Run the cell; return the result object (also printed by `main`)."""
    from repro import compat

    args = parse(argv)
    cell = Cell(root, args.workload)
    devices = devices_for(cell.chips)
    compat.enable_compilation_cache()
    trace_dir = None
    if args.trace:
        trace_dir = cell.root / ".bench_out" / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
    outcome = cell.loop().run(
        cell.config, cell.mix, seed=args.seed, seconds=args.seconds,
        trace_dir=trace_dir, devices=devices, t_start=t_start,
        limits=cell.config["guarantee"]["limits"])
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    result = {"correct": all(c.ok for c in outcome.checks),
              "attempted": outcome.attempted, "failed": outcome.failed}
    if args.trace:
        found = xtrace.find_trace(trace_dir)
        summary = (xtrace.summarize(xtrace.load(found),
                                     int(cell.config["block_size"]))
                   if found else None)
        peaks = work.peaks(kind) if devices[0].platform == "tpu" else None
        ctx = Context(cell, outcome, summary, peaks)
        metrics = {}
        for m in cell.per_layer:
            value = cell.metric_reader(m)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if summary is not None:
            device["busy_s"] = summary.mean_busy_s
            device["window_s"] = summary.window_s
            result["breakdown"] = {
                "device_ops": [list(kv) for kv in summary.top_ops],
                "idle_gaps": [list(kv) for kv in summary.idle_gaps]}
    else:
        result["metrics"] = {
            m["name"]: {"value": outcome.metrics[m["name"]],
                        "unit": m["unit"]} for m in cell.end_to_end}
    result["device"] = device
    result["window_compiles"] = outcome.window_compiles
    result["counters"] = {k: v for k, v in outcome.counters.items()
                          if isinstance(v, (int, float, dict))}
    result["checks"] = {c.name: {"value": c.value, "relation": c.relation,
                                 "limit": c.limit} for c in outcome.checks}
    return result


def main(argv, *, root, t_start: float, devices_for=require_chips) -> int:
    result = run(argv, root=root, t_start=t_start, devices_for=devices_for)
    checks = result["checks"]
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} {c['relation']} "
              f"{c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
