"""Closed loop: one caller inverting back to back.

`spin_inverse_dense` (one chip) or `spin_inverse_sharded` (a mesh) is
called on `mix["matrices"]` seeded matrices in turn, each call ending in
`block_until_ready`. A sample of `sample_count` answers, drawn from the
seed uniformly over every call the window makes (reservoir sampling), is
kept and judged after the window by its residual.
"""

from __future__ import annotations

import contextlib
import gc

import numpy as np

from bench import data, reference
from bench.common import (Check, Outcome, Window, mesh_for, peak_bytes,
                          span)


@contextlib.contextmanager
def control(precision: str):
    """The plain reference in the program's place: Newton–Schulz at
    `precision` answers every call the window makes."""
    import repro.core

    saved = repro.core.spin_inverse_dense, repro.core.spin_inverse_sharded

    def plain(a, *_args, **_kwargs):
        return reference.newton_schulz_inverse(a, precision)

    repro.core.spin_inverse_dense = repro.core.spin_inverse_sharded = plain
    try:
        yield
    finally:
        repro.core.spin_inverse_dense, repro.core.spin_inverse_sharded = saved


class Reservoir:
    """A uniform sample of `size` items from a stream of unknown length,
    drawn from the seed."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.rng = np.random.default_rng(seed)
        self.slots: dict[int, tuple[int, object]] = {}

    def offer(self, index: int, item) -> None:
        slot = index if index < self.size else int(
            self.rng.integers(0, index + 1))
        if slot < self.size:
            self.slots[slot] = (index, item)

    def items(self) -> list[tuple[int, object]]:
        return sorted(self.slots.values(), key=lambda kv: kv[0])


def run(config: dict, mix: dict, *, seed: int, seconds: float, trace_dir,
        devices, t_start: float, limits: dict) -> Outcome:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    # Looked up at each run, so that a control or a planted fault put in
    # the program's place is the one the window calls.
    from repro.compat import set_mesh
    from repro.core import spin_inverse_dense, spin_inverse_sharded

    n, bs = int(config["n"]), int(config["block_size"])
    leaf, engine = config["leaf_solver"], config["engine"]
    mesh = mesh_for(config, devices)
    if mesh is None:
        sharding = jax.sharding.SingleDeviceSharding(devices[0])
        ctx = contextlib.nullcontext()

        def call(a):
            return spin_inverse_dense(a, bs, leaf, engine=engine)
    else:
        sharding = NamedSharding(mesh, PartitionSpec(*config["mesh"]["axes"]))
        ctx = set_mesh(mesh)

        def call(a):
            return spin_inverse_sharded(a, bs, leaf_solver=leaf,
                                        engine=engine)

    count = int(mix["matrices"])
    with span("bench.generate"):
        mats = [data.spd_matrix(n, seed, i, sharding) for i in range(count)]
        jax.block_until_ready(mats)
    window = Window(seconds, trace_dir, float(mix["trace_seconds"]))
    kept = Reservoir(int(mix["sample_count"]), seed)
    calls = traced_calls = 0
    with ctx:
        with span("bench.warmup"):
            jax.block_until_ready(call(mats[0]))
        t0 = window.open()
        while True:
            window.poll()
            with span("bench.offline"):
                x = call(mats[calls % count])
                x.block_until_ready()
            kept.offer(calls, x)
            del x
            calls += 1
            traced_calls += window.tracing
            if window.elapsed() >= seconds:
                break
        window.stop_trace()
        t_end = window.close()
    peak = peak_bytes(devices)
    answers = kept.items()
    del kept
    gc.collect()
    with ctx:
        residuals = [float(reference.inverse_residual(mats[i % count], x))
                     for i, x in answers]
    checked = [i for i, _ in answers]
    del answers, mats
    limit = float(limits["inverse_residual_max"])
    checks = [Check("answers_checked", float(len(residuals)), ">=", 1.0,
                    len(residuals) >= 1),
              Check("inverse_residual_max", max(residuals, default=None),
                    "<=", limit, bool(residuals) and max(residuals) <= limit)]
    return Outcome(
        metrics={"setup_s": t0 - t_start, "inverse_s": (t_end - t0) / calls},
        attempted=calls, failed=0, checks=checks,
        counters={"calls": calls, "calls_traced": traced_calls,
                  "calls_checked": checked, "residuals": residuals},
        memory_peak_bytes=peak, window_compiles=window.compiles)
