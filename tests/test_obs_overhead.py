"""Zero-overhead-when-disabled proof for the span tracer.

The hard requirement on `repro.obs.trace` (DESIGN.md §13): with
`SPIN_TRACE` off, instrumentation must not change the compiled program —
no extra equations, no callbacks, no host syncs. With it on, only
host-side span records appear (the recursion's named scopes are always
on and metadata-only), so the program STILL must not gain equations.
"""

import jax
import jax.numpy as jnp

from repro.core.blockmatrix import BlockMatrix
from repro.core.spin import spin_inverse
from repro.obs.trace import TRACER, tracing

# Primitives that would mean the tracer leaked host work into the program.
_FORBIDDEN = {"pure_callback", "io_callback", "debug_callback", "callback"}


def _recursion_jaxpr(n=16, bs=4):
    a = jnp.eye(n, dtype=jnp.float32) * 2.0

    def fn(x):
        return spin_inverse(BlockMatrix.from_dense(x, bs)).to_dense()

    return jax.make_jaxpr(fn)(a)


def _primitives(jaxpr) -> list:
    out = []

    def walk(jx):
        for eqn in jx.eqns:
            out.append(eqn.primitive.name)
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    walk(sub.jaxpr)
    walk(jaxpr.jaxpr)
    return out


def test_traced_program_identical_to_untraced():
    with tracing(False):
        off = _primitives(_recursion_jaxpr())
    with tracing(True, clear=True):
        on = _primitives(_recursion_jaxpr())
        # the instrumentation DID fire at trace time...
        assert TRACER.spans(kind="recursion_level")
    # ...but the program is equation-for-equation identical
    assert on == off
    assert not _FORBIDDEN & set(on)


def test_disabled_tracer_records_nothing_from_recursion():
    TRACER.clear()
    with tracing(False):
        a = BlockMatrix.from_dense(jnp.eye(8, dtype=jnp.float32) * 3.0, 2)
        spin_inverse(a)
    assert TRACER.spans() == []


def test_disabled_guard_is_single_attribute_read():
    """The disabled path must not build spans, dicts, or contexts: event()
    returns before touching its kwargs, span() yields None immediately."""
    with tracing(False):
        assert TRACER.event("x", "k") is None
        with TRACER.span("x", "k", big_attr=list(range(3))) as s:
            assert s is None
    assert TRACER.spans() == []
