"""Closed loop: one caller refreshing stacks of Kronecker factors back to back.

The optimizer's own entry, `repro.optim.spin_shampoo.invert_spd`, is called
with the configuration's plan on `mix["stacks"]` seeded stacks of
`config["stack"]` Gram factors in turn, each call ending in
`block_until_ready`. Factor i of a stack is G = B Bᵀ/rank with B standard
normal (n, rank), formed at HIGHEST precision: a gradient Gram matrix of
low effective rank, which the program damps by `config["damping"]` of its
mean eigenvalue. `inverse_s` counts matrices, not calls. A sample of
`sample_count` single inverses, drawn from the seed uniformly over every
matrix the window inverts, is copied out of its stack and judged after the
window against the benchmark's own damped copy of that factor.
"""

from __future__ import annotations

import contextlib
import functools
import gc

import jax
import jax.numpy as jnp

from bench import data, reference
from bench.common import Check, Outcome, Window, peak_bytes, span
from bench.loops.closed_inverse import Reservoir

# Newton–Schulz sweeps of the control. From X₀ = I/‖A‖_F the error on the
# smallest eigenvalue starts at 1 − λ_min/‖A‖_F, about 1 − 1e-5 for the
# damped rank-n/2 factor (κ ≈ 5.8e3, ‖A‖_F ≈ 18 λ_max), and squares each
# sweep: about 17 sweeps bring it under 1/2, five more to the fixed point.
CONTROL_SWEEPS = 30


def gram_stack(n: int, rank: int, depth: int, key):
    """(depth, n, n) factors B Bᵀ/rank, B standard normal (n, rank), each
    formed at HIGHEST precision from its own key."""
    def one(k):
        b = jax.random.normal(k, (n, rank), dtype=jnp.float32)
        return jnp.matmul(b, b.T, precision=jax.lax.Precision.HIGHEST) / rank

    return jax.lax.map(one, jax.random.split(key, depth))


@functools.lru_cache(maxsize=None)
def _stack_program(n: int, rank: int, depth: int, sharding):
    return jax.jit(functools.partial(gram_stack, n, rank, depth),
                   out_shardings=sharding)


def damped(g, damping: float):
    """The benchmark's own damped factor: G + damping·(tr G/n)·I."""
    n = g.shape[-1]
    lam = damping * jnp.trace(g, axis1=-2, axis2=-1) / n
    return g + lam[..., None, None] * jnp.eye(n, dtype=g.dtype)


@jax.jit
def _take(stack, i):
    """Matrix `i` of a stack, copied out of it (one program for every i)."""
    return jax.lax.dynamic_index_in_dim(stack, i, keepdims=False)


@functools.partial(jax.jit, static_argnames=("damping",))
def _residual(g, x, damping: float):
    return reference.inverse_residual(damped(g, damping), x)


@functools.partial(jax.jit,
                   static_argnames=("damping", "precision", "sweeps"))
def plain_inverse(stack, damping: float, precision: str,
                  sweeps: int = CONTROL_SWEEPS):
    """Newton–Schulz at `precision` on each damped factor of the stack,
    X ← X(2I − A X) from X₀ = I/‖A‖_F."""
    def one(a):
        x = jnp.eye(a.shape[0], dtype=jnp.float32) / jnp.linalg.norm(a)
        for _ in range(sweeps):
            x = 2.0 * x - reference.matmul(
                x, reference.matmul(a, x, precision), precision)
        return x

    return jax.lax.map(one, damped(stack, damping))


@contextlib.contextmanager
def control(precision: str):
    """The plain reference in the program's place: Newton–Schulz at
    `precision` answers every refresh the window makes."""
    import repro.optim.spin_shampoo as shampoo

    saved = shampoo.invert_spd

    def plain(stack, damping, **_plan):
        return plain_inverse(stack, float(damping), precision)

    shampoo.invert_spd = plain
    try:
        yield
    finally:
        shampoo.invert_spd = saved


def run(config: dict, mix: dict, *, seed: int, seconds: float, trace_dir,
        devices, t_start: float, limits: dict) -> Outcome:
    # Looked up at each run, so that a control or a planted fault put in
    # the program's place is the one the window calls.
    from repro.optim.spin_shampoo import invert_spd

    n, depth, rank = int(config["n"]), int(config["stack"]), int(
        config["rank"])
    damping = float(config["damping"])
    plan = {"block_size": int(config["block_size"]),
            "leaf_solver": config["leaf_solver"], "engine": config["engine"]}
    sharding = jax.sharding.SingleDeviceSharding(devices[0])
    make = _stack_program(n, rank, depth, sharding)
    count = int(mix["stacks"])
    with span("bench.generate"):
        stacks = [make(data.device_key(seed, data.STREAM_MATRIX + s))
                  for s in range(count)]
        jax.block_until_ready(stacks)

    def call(stack):
        return invert_spd(stack, damping, **plan)

    window = Window(seconds, trace_dir, float(mix["trace_seconds"]))
    kept = Reservoir(int(mix["sample_count"]), seed)
    index = jnp.zeros((), jnp.int32)
    calls = traced_calls = 0
    with span("bench.warmup"):
        x = call(stacks[0])
        jax.block_until_ready(_take(x, index))
        del x
    t0 = window.open()
    while True:
        window.poll()
        with span("bench.offline"):
            x = call(stacks[calls % count])
            x.block_until_ready()
        first = calls * depth
        for j in range(depth):
            kept.offer(first + j, None)
        # Only the slices the sample keeps are copied out of the stack.
        for slot, (i, item) in kept.slots.items():
            if item is None:
                kept.slots[slot] = (i, _take(x, jnp.int32(i - first)))
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
    matrices = calls * depth
    residuals = []
    for i, x in answers:
        g = _take(stacks[(i // depth) % count], jnp.int32(i % depth))
        residuals.append(float(_residual(g, x, damping)))
    checked = [i for i, _ in answers]
    del answers, stacks
    limit = float(limits["inverse_residual_max"])
    checks = [Check("answers_checked", float(len(residuals)), ">=", 1.0,
                    len(residuals) >= 1),
              Check("inverse_residual_max", max(residuals, default=None),
                    "<=", limit, bool(residuals) and max(residuals) <= limit)]
    return Outcome(
        metrics={"setup_s": t0 - t_start, "inverse_s": (t_end - t0) / matrices},
        attempted=matrices, failed=0, checks=checks,
        counters={"calls": calls, "matrices": matrices,
                  "calls_traced": traced_calls * depth,
                  "calls_checked": checked, "residuals": residuals},
        memory_peak_bytes=peak, window_compiles=window.compiles)
