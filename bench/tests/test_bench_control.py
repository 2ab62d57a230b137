"""The comparison that decides `correct` fails what it must: the control (the
plain reference in the program's place at a lower precision) and each
fault a cell can have, planted under a tiny run that skips only the look
for a chip."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import data, harness, reference
from bench.tests import tiny

LIMITS = dict(json.loads((tiny.REPO / "bench/configs/spd-n16384-f32.json")
                         .read_text())["guarantee"]["limits"],
              solve_residual_max=tiny.SOLVE_RESIDUAL_MAX)


@pytest.mark.parametrize("n", [256, 512])
def test_control_fails_and_reference_passes(n):
    """The control in the program's place fails the configured limits. On the
    chip the control is HIGH (three passes); a CPU computes f32 exactly at
    any precision, so here the rung below it, one bf16 pass, stands in."""
    a = data.spd_matrix(n, 11, 0)
    b = jax.random.normal(jax.random.PRNGKey(3), (n, 16), jnp.float32)
    inv, sol = LIMITS["inverse_residual_max"], LIMITS["solve_residual_max"]
    x_low = reference.newton_schulz_inverse(a, "bf16")
    x_ref = reference.newton_schulz_inverse(a, "highest")
    assert float(reference.inverse_residual(a, x_low)) > 3 * inv
    assert float(reference.inverse_residual(a, x_ref)) < inv / 10
    s_low = reference.cg_solve(a, b, "bf16")
    s_ref = reference.cg_solve(a, b, "highest")
    assert float(reference.solve_residual(a, s_low, b)) > 3 * sol
    assert float(reference.solve_residual(a, s_ref, b)) < sol / 10


def _run(root, workload, seconds=1.0):
    return harness.run(["--workload", workload, "--seed", "987654321012",
                        "--seconds", str(seconds), "--trace", "0"],
                       root=root, t_start=time.perf_counter(),
                       devices_for=harness.any_devices)


def test_control_in_the_programs_place_makes_the_run_incorrect(tmp_path):
    """The loop's control answers every call of the window in the program's
    place, and the harness's own comparison fails it (bf16 standing in for
    HIGH, as above); the same run with the program comes out correct."""
    root = tiny.make_tree(tmp_path)
    cell = harness.Cell(root, "tiny-inverse")
    assert _run(root, "tiny-inverse")["correct"]
    with cell.loop().control("bf16"):
        out = _run(root, "tiny-inverse")
    assert not out["correct"]
    c = out["checks"]["inverse_residual_max"]
    assert c["value"] > 3 * c["limit"]
    with cell.loop().control("highest"):
        assert _run(root, "tiny-inverse")["correct"]


def _alter_inverse(mp):
    import repro.core

    real = repro.core.spin_inverse_dense
    mp.setattr(repro.core, "spin_inverse_dense",
               lambda *a, **k: real(*a, **k).at[0, 0].add(1.0))


def _drop_updates(mp):
    from repro.serving import spin_service

    mp.setattr(spin_service, "smw_update_inverse", lambda inv, u, v: inv)


def _half_batch(mp):
    from repro.serving import spin_service

    real = spin_service.apply_inverse

    def half(inv, rhs, **kw):
        x = real(inv, rhs, **kw)
        keep = jnp.arange(x.shape[-1]) < max(1, x.shape[-1] // 2)
        return jnp.where(keep, x, 0.0)

    mp.setattr(spin_service, "apply_inverse", half)


def _alter_answer(mp):
    from repro.serving import spin_service

    real = spin_service.apply_inverse
    mp.setattr(spin_service, "apply_inverse",
               lambda inv, rhs, **kw: real(inv, rhs, **kw) * 1.01)


FAULTS = {
    "inverse_answer_altered": ("tiny-inverse", _alter_inverse,
                               "inverse_residual_max"),
    "update_leaves_state_unchanged": ("tiny-serve", _drop_updates,
                                      "solve_residual_max"),
    "half_the_batch_left_out": ("tiny-serve", _half_batch,
                                "solve_residual_max"),
    "served_answer_altered": ("tiny-serve", _alter_answer,
                              "solve_residual_max"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(tmp_path, monkeypatch, fault):
    workload, plant, check = FAULTS[fault]
    root = tiny.make_tree(tmp_path)
    assert _run(root, workload)["correct"]
    plant(monkeypatch)
    out = _run(root, workload)
    assert not out["correct"]
    c = out["checks"][check]
    assert c["value"] > c["limit"]


MESH_SCRIPT = """
import json, pathlib, sys, time
sys.path[:0] = [{repo!r}, {src!r}]
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from bench import harness
from bench.tests import tiny
import repro.core

root = tiny.make_tree(pathlib.Path(sys.argv[1]), mesh=True)
args = ["--workload", "tiny-mesh", "--seed", "31337", "--seconds", "1",
        "--trace", "0"]

def run():
    return harness.run(args, root=root, t_start=time.perf_counter(),
                       devices_for=harness.any_devices)

sound = run()

def no_exchange(a, *args, **kw):
    # Each device inverts the tile it holds, and nothing moves between them.
    mesh = jax.sharding.get_abstract_mesh()
    return jax.shard_map(jnp.linalg.inv, mesh=mesh,
                         in_specs=P("data", "model"),
                         out_specs=P("data", "model"))(a)

repro.core.spin_inverse_sharded = no_exchange
broken = run()
print(json.dumps({{"sound": sound, "broken": broken}}))
"""


def test_mesh_without_its_exchange_is_incorrect(tmp_path):
    script = MESH_SCRIPT.format(repo=str(tiny.REPO),
                                src=str(tiny.REPO / "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"], out["sound"]["checks"]
    assert out["sound"]["device"]["count"] == 4
    assert not out["broken"]["correct"]
    c = out["broken"]["checks"]["inverse_residual_max"]
    assert c["value"] > c["limit"]
