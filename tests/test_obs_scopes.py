"""The recursion's always-on named scopes, their join to compiled instruction
names, and the span tracer's clock (repro.obs.trace).

Every operation of the SPIN recursion runs under `spin.L<k>` and one step
scope; the compiled program keeps them as `op_name` metadata, which
`hlo_op_scopes` reads back per instruction. The names are metadata only:
with the metadata removed, the compiled program is the same with
`SPIN_TRACE` on and off.
"""

import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core.blockmatrix import BlockMatrix
from repro.core.spin import _spin_inverse_dense, spin_inverse
from repro.obs.trace import (LAYOUT, STEPS, TRACER, hlo_op_scopes, op_scope,
                             tracing)
from tests.mesh_harness import run_mesh

PRODUCTS = {"II", "III", "schur", "C12", "C21", "C11"}
NODE_LAYOUT = {"split", "neg", "arrange"}
# Instructions that are no device work: a trace shows none of them.
_TRIVIAL = re.compile(r"= .*? (parameter|constant|tuple|get-tuple-element|"
                      r"bitcast)\(|copy\(%constant")


def _event_level(text: str):
    """(name, line) of every instruction a device trace can show: those of
    computations that are not fusion bodies, less the trivial ones."""
    fused = set(re.findall(r"calls=%([\w.-]+)", text))
    comp = None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if m:
            comp = m.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%(\S+) = ", line)
        if m and comp not in fused and not _TRIVIAL.search(line):
            yield m.group(1), line


def _assert_named(text: str, module: str, levels: int):
    (mod, ops), = hlo_op_scopes(text).items()
    assert mod == module
    seen = set()
    for name, line in _event_level(text):
        level, step = ops[name]
        assert level is not None or step == LAYOUT, line[:200]
        assert step in STEPS, line[:200]
        seen.add((level, step))
    leaf = {(lv, st) for lv, st in seen if st == "leaf"}
    assert leaf == {(levels, "leaf")}
    for lv in range(levels):
        steps = {st for level, st in seen if level == lv}
        assert PRODUCTS <= steps, (lv, steps)
        assert {"split", "arrange"} <= steps, (lv, steps)
    return ops


def test_compiled_recursion_carries_level_and_step_on_every_instruction():
    """n=64 at block 4: a 16x16 grid, four levels of nodes, leaves at L4."""
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    text = _spin_inverse_dense.lower(x, 4, "linalg", "einsum").compile(
        ).as_text()
    ops = _assert_named(text, "jit__spin_inverse_dense", levels=4)
    assert (None, LAYOUT) in set(ops.values())
    assert ("jit(_spin_inverse_dense)/spin.L0/spin.L1/spin.L2/spin.L3/"
            "spin.L4/leaf/") in text
    assert "jit(_spin_inverse_dense)/spin.L0/schur/" in text


def test_sharded_recursion_carries_the_same_scopes_on_four_devices():
    out = run_mesh("""
        import re
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, Mesh
        from repro.core.spin import inverse_op_scopes
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        scopes = inverse_op_scopes(64, 8, "linalg", "einsum", mesh=mesh)
        (module, ops), = scopes.items()
        emit_result({"module": module,
                     "scopes": sorted({repr(s) for s in ops.values()})})
    """, devices=4)
    (got,) = out
    assert got["module"] == "jit__inverse_program"
    scopes = {eval(s) for s in got["scopes"]}
    assert {(3, "leaf")} == {s for s in scopes if s[1] == "leaf"}
    for lv in range(3):
        steps = {st for level, st in scopes if level == lv}
        assert PRODUCTS | NODE_LAYOUT <= steps, (lv, steps)


def test_join_reuses_the_executable_the_caller_ran():
    """For an operand committed to a device, `inverse_op_scopes` with that
    operand's sharding compiles nothing: the scope map costs no compile."""
    from jax.sharding import SingleDeviceSharding

    from repro.core.spin import inverse_op_scopes, spin_inverse_dense

    a = jax.device_put(jnp.eye(40, dtype=jnp.float32) * 3.0,
                       SingleDeviceSharding(jax.devices()[0]))
    spin_inverse_dense(a, 10, "linalg", engine="einsum").block_until_ready()
    compiles, listening = [], [True]

    def on_event(event, _secs, **_kw):
        if listening[0] and event.endswith("backend_compile_duration"):
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        scopes = inverse_op_scopes(40, 10, "linalg", "einsum",
                                   sharding=a.sharding)
    finally:
        listening[0] = False
    assert compiles == []
    (ops,) = scopes.values()
    assert (2, "leaf") in set(ops.values())


def _strip_metadata(text: str) -> str:
    return re.sub(r",? metadata=\{[^}]*\}", "", text)


def test_compiled_program_is_the_same_with_span_records_on_and_off():
    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)

    texts = {}
    for enabled in (False, True):
        with tracing(enabled, clear=True):
            # A fresh function each time, so that each traces anew; one
            # call site, so that the source locations agree.
            fn = jax.jit(lambda a: spin_inverse(
                BlockMatrix.from_dense(a, 8)).to_dense())
            texts[enabled] = fn.lower(x).compile().as_text()
            assert bool(TRACER.spans(kind="recursion_level")) == enabled
    assert "spin.L0/II/" in texts[False]
    assert _strip_metadata(texts[True]) == _strip_metadata(texts[False])


def test_op_scope_reads_the_innermost_level_and_step():
    assert op_scope("jit(f)/spin.L0/spin.L1/leaf/jit(inv)/lu") == (1, "leaf")
    assert op_scope("jit(f)/spin.L0/II/dot_general") == (0, "II")
    assert op_scope("jit(f)/spin.L0/spin.L1/dot_general") == (1, None)
    # The last component is the primitive, never a scope.
    assert op_scope("jit(f)/spin.L2/C11/split") == (2, "C11")
    assert op_scope("jit(f)/spin.layout/transpose") == (None, LAYOUT)
    assert op_scope("jit(solve)/lu") == (None, None)


HLO = """\
HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %one = s32[] constant(1)
  %next = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8]{0}) tuple(%next, %x)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %copy.1 = f32[8]{0:T(8)} copy(%a)
  %fusion.2 = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/spin.L0/II/mul"}
  %copy.3 = f32[8]{0} copy(%fusion.2)
  %init = (s32[], f32[8]{0}) tuple(%zero, %copy.3)
  %while.4 = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(f)/spin.L1/leaf/while"}
  ROOT %out = f32[8]{0} get-tuple-element(%while.4), index=1
}
"""


def test_instructions_without_metadata_take_a_scope_from_users_operands_callers():
    (ops,) = hlo_op_scopes(HLO).values()
    assert ops["fusion.2"] == (0, "II")
    assert ops["copy.1"] == (0, "II")          # its user
    assert ops["copy.3"] == (1, "leaf")        # its user's user
    assert ops["next"] == (1, "leaf")          # the loop that calls it
    assert ops["out"] == (1, "leaf")           # its operand


def test_span_records_lie_on_the_profiler_trace_clock(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing(True, clear=True):
            with TRACER.span("test.clock", "test"):
                time.sleep(0.02)
            (span,) = TRACER.spans(name="test.clock")
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    # Event times count from the session's start, which the trace gives on
    # the host's clock.
    start_ns = dict(data.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    (event,) = [e for plane in data.planes for line in plane.lines
                for e in line.events if e.name == "test.clock"]
    assert (start_ns + event.start_ns) / 1e9 == pytest.approx(span.t0,
                                                              abs=1e-3)
    assert (start_ns + event.end_ns) / 1e9 == pytest.approx(span.t1,
                                                            abs=1e-3)
