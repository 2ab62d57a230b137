"""What every loop shares: the checks it reports, its outcome, the measured
window with the profiler inside it, host spans, and the device's peak
memory.

A loop is a file `bench/loops/<name>.py` with a function

    run(config, mix, *, seed, seconds, trace_dir, devices, t_start,
        limits) -> Outcome

that builds its inputs from the seed, warms every shape its window uses
(set-up), measures for `seconds`, reads the device's peak memory, frees
the program's state, and only then runs the reference comparison. Host
spans (`bench.*`) mark what the host was doing, for the trace; with the
profiler off they cost a microsecond each.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Check:
    """One number compared with its limit."""

    name: str
    value: float | None
    relation: str        # how the value must stand to the limit
    limit: float
    ok: bool


@dataclasses.dataclass
class Outcome:
    metrics: dict[str, float]            # end-to-end values, by name
    attempted: int
    failed: int
    checks: list[Check]
    counters: dict
    memory_peak_bytes: int
    window_compiles: int


class Window:
    """The measured window, the profiler inside it, and compile counting.

    With tracing on, the profiler runs over the window's last
    `trace_seconds`, started at a point where the loop is between calls; the
    span `bench.window` marks exactly the traced part, and its start on the
    host clock ties host timestamps to the trace's clock.
    """

    def __init__(self, seconds: float, trace_dir, trace_seconds: float):
        import jax

        self.seconds = seconds
        self.trace_dir = trace_dir
        self.trace_from = max(0.0, seconds - trace_seconds)
        self.t0 = self.t_end = 0.0
        self.tracing = False
        self.traced = False
        self.span = None
        self.span_start_ns = 0
        self.compiles = 0
        self._open = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, _secs: float, **_kw) -> None:
        if self._open and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def open(self) -> float:
        self.t0 = time.perf_counter()
        self._open = True
        return self.t0

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def poll(self) -> None:
        """Start the profiler once the traced part of the window begins."""
        import jax

        if (self.trace_dir is None or self.traced or self.t_end
                or self.elapsed() < self.trace_from):
            return
        jax.profiler.start_trace(str(self.trace_dir))
        self.tracing = self.traced = True
        self.span = jax.profiler.TraceAnnotation("bench.window")
        self.span_start_ns = time.perf_counter_ns()
        self.span.__enter__()

    def stop_trace(self) -> None:
        import jax

        if self.tracing:
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.tracing = False

    def close(self) -> float:
        self._open = False
        self.t_end = time.perf_counter()
        return self.t_end


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def mesh_for(config: dict, devices):
    """The configuration's mesh over `devices`, or None for one chip."""
    from jax.sharding import AxisType, Mesh

    spec = config.get("mesh")
    if not spec:
        return None
    axes = tuple(spec["axes"])
    return Mesh(np.asarray(devices).reshape(tuple(spec["shape"])), axes,
                axis_types=(AxisType.Auto,) * len(axes))
