"""Reduce one profiler trace (`.xplane.pb`) to the numbers the metrics read.

A device plane (`/device:TPU:<k>`) holds one line of XLA operations; the
union of their intervals is the time the device was busy. Each operation
falls in one class (gemm, leaf, collective, other) by the rules of
`opclasses.json`, written from traces read by hand. The benchmark's own
host spans (`bench.*`, `jax.profiler.TraceAnnotation`) lie on the same
clock; the traced window is the span `bench.window`, and every idle gap
is labelled with the innermost benchmark span open when it began.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import math
import pathlib
import re
from collections import defaultdict

CLASSES_FILE = pathlib.Path(__file__).resolve().parent / "opclasses.json"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Op:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    """What one trace holds, clipped to nothing yet."""

    ops: dict[int, list[Op]]          # device ordinal -> XLA ops
    modules: dict[int, list[Op]]      # device ordinal -> program runs
    spans: list[Op]                   # the benchmark's host spans


def _events(line) -> list[Op]:
    return [Op(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: dict[int, list[Op]] = {}
    modules: dict[int, list[Op]] = {}
    spans: list[Op] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(2))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[dev] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e.name.startswith("bench.")]
    return Trace(ops=ops, modules=modules, spans=spans)


def find_trace(directory) -> pathlib.Path | None:
    found = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))
    return found[-1] if found else None


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def intersect_total(a, b) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = 0
    acc = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            acc += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return acc


# ---------------------------------------------------------------------------
# Classification and the summary the metrics read
# ---------------------------------------------------------------------------

SHAPE = re.compile(r"\b(?:f64|f32|bf16|f16|s32|u32|s8|u8|pred)\[([0-9,]*)\]")


@dataclasses.dataclass
class Rules:
    gemm: list[tuple[re.Pattern, bool]]
    collective: list[re.Pattern]
    leaf_marker: list[re.Pattern]


def load_rules(path=CLASSES_FILE) -> Rules:
    spec = json.loads(pathlib.Path(path).read_text())
    return Rules(
        gemm=[(re.compile(r["pattern"]), bool(r.get("block_shapes")))
              for r in spec["gemm"]],
        collective=[re.compile(r["pattern"]) for r in spec["collective"]],
        leaf_marker=[re.compile(r["pattern"]) for r in spec["leaf_marker"]])


def _dims(text: str) -> list[list[int]]:
    return [[int(d) for d in m.split(",") if d] for m in SHAPE.findall(text)]


def _block_shaped(text: str, block: int) -> bool:
    shapes = _dims(text)
    return bool(shapes) and all(s.count(block) >= 2 for s in shapes)


def _output_elems(text: str) -> int:
    shapes = _dims(text.split(" = ", 1)[-1].split("(", 1)[0])
    return max((math.prod(s) for s in shapes), default=0)


def classify(ops: list[Op], rules: Rules, block: int) -> list[str]:
    """One class per op of one device, in time order."""
    out: list[str | None] = []
    for o in ops:
        if any(p.search(o.name) for p in rules.collective):
            out.append("collective")
        elif any(p.search(o.name) and (not blocked
                                       or _block_shaped(o.name, block))
                 for p, blocked in rules.gemm):
            out.append("gemm")
        else:
            out.append(None)
    i = 0
    while i < len(ops):
        if out[i] is not None:
            i += 1
            continue
        j = i
        while j < len(ops) and out[j] is None:
            j += 1
        leafy = any(p.search(ops[k].name) for k in range(i, j)
                    for p in rules.leaf_marker)
        for k in range(i, j):
            out[k] = ("leaf" if leafy and _output_elems(ops[k].name)
                      <= block * block else "other")
        i = j
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    devices: list[int]
    busy_s: dict[int, float]                 # per device, in the window
    class_s: dict[str, float]                # summed over devices
    module_runs: dict[str, int]              # program runs, all devices
    module_s: dict[str, float]               # summed over devices
    top_ops: list[tuple[str, float]]         # summed over devices
    idle_gaps: list[tuple[str, float]]       # longest, labelled
    busy: dict[int, list[tuple[float, float]]]
    window: tuple[float, float]

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)


def _label(t: float, spans: list[Op]) -> str:
    open_ = [s for s in spans if s.start_ns <= t < s.end_ns
             and s.name != WINDOW_SPAN]
    return max(open_, key=lambda s: s.start_ns).name if open_ else "bench.none"


def op_label(op: Op, module: str) -> str:
    """`module/name type[shape] opcode` from an op's text (operands cut)."""
    head = re.sub(r"\{[^}]*\}", "", op.name).split("(", 1)[0]
    head = head.lstrip("%").replace(" = ", " ").strip()
    kind = re.search(r"kind=(k\w+)", op.name)
    return f"{module}/{head}" + (f"/{kind.group(1)}" if kind else "")


def _module_of(runs: list[Op]):
    """The program (an `XLA Modules` run) each op time falls in."""
    starts = [m.start_ns for m in runs]

    def find(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < runs[i].end_ns:
            return re.sub(r"\(\d+\)$", "", runs[i].name)
        return "?"

    return find


def summarize(trace: Trace, block: int, rules=None,
              top: int = 10) -> Summary | None:
    """None when the trace holds no device operation or no window span."""
    rules = load_rules() if rules is None else rules
    windows = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if not trace.ops or not windows:
        return None
    w = max(windows, key=lambda s: s.end_ns - s.start_ns)
    lo, hi = w.start_ns, w.end_ns
    busy, busy_s = {}, {}
    class_s: dict[str, float] = defaultdict(float)
    op_s: dict[str, float] = defaultdict(float)
    labelled: list[tuple[str, float]] = []
    for dev, ops in sorted(trace.ops.items()):
        ops = sorted(ops, key=lambda o: o.start_ns)
        module = _module_of(sorted(trace.modules.get(dev, []),
                                   key=lambda m: m.start_ns))
        busy[dev] = union(((o.start_ns, o.end_ns) for o in ops), lo, hi)
        busy_s[dev] = total(busy[dev]) / 1e9
        for o, cls in zip(ops, classify(ops, rules, block)):
            d = min(o.end_ns, hi) - max(o.start_ns, lo)
            if d > 0:
                class_s[cls] += d / 1e9
                op_s[op_label(o, module(o.start_ns))] += d / 1e9
        suffix = f"@{dev}" if len(trace.ops) > 1 else ""
        labelled += [(_label(s, trace.spans) + suffix, (e - s) / 1e9)
                     for s, e in gaps(busy[dev], lo, hi)]
    module_runs: dict[str, int] = defaultdict(int)
    module_s: dict[str, float] = defaultdict(float)
    for runs in trace.modules.values():
        for m in runs:
            d = min(m.end_ns, hi) - max(m.start_ns, lo)
            if d > 0:
                name = re.sub(r"\(\d+\)$", "", m.name)
                module_runs[name] += 1
                module_s[name] += d / 1e9
    return Summary(
        window_s=(hi - lo) / 1e9, devices=sorted(trace.ops), busy_s=busy_s,
        class_s=dict(class_s), module_runs=dict(module_runs),
        module_s=dict(module_s),
        top_ops=sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(labelled, key=lambda kv: -kv[1])[:top],
        busy=busy, window=(lo, hi))
