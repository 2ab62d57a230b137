"""Names on the SPIN recursion, and the structured span tracer (`$SPIN_TRACE`).

Two kinds of instrumentation live here.

  * **Always-on names.** The inversion recursion (`core.recursion.invert`,
    on one device and on a mesh alike) opens a
    `jax.named_scope` per internal node, `spin.L<k>` at depth k, and inside
    it one per step (`STEPS`: `split`, `II`, `III`, `schur`, `C12`, `C21`,
    `C11`, `neg`, `arrange`); a leaf opens `spin.L<k>/leaf`, and the dense
    entry's block layout opens `spin.layout` outside any level. On a mesh
    each SUMMA all-gather opens `gather` inside its product step, so it
    reads as `spin.L<k>/<step>/gather` and `op_scope` gives it the step
    `gather`. A named scope is HLO metadata only (`op_name`), so the names
    cost nothing at run time and change no instruction; `op_scope` reads
    the level and the step back from an `op_name`, `hlo_op_scopes` from a
    compiled module's text. The entry points open one `jax.profiler.TraceAnnotation` each
    (`host_span`), which costs about a microsecond with no profiler
    session. None of this is gated by `SPIN_TRACE`.
  * **Span records, gated by `SPIN_TRACE`.** The recursion, the planner,
    the worker pool, and the serving tick loop emit *spans* —
    `{name, kind, t0, t1, attrs, thread}` records — into one
    process-global `SpanTracer`. Every site is guarded by a single
    attribute read (`if TRACER.enabled:`); with `SPIN_TRACE` unset no span
    object is built and, the hard requirement, no host sync is inserted on
    the jitted hot path. `tests/test_obs_overhead.py` proves the compiled
    program is identical with tracing on and off. `t0`/`t1` are stamped on
    the profiler's host clock (`profiler_clock`), so a span dump lines up
    with a `jax.profiler` trace, and a timed span also opens a
    `TraceAnnotation` of its name. The recursion compiles into ONE XLA
    program, so its `spin.level`/`spin.leaf` records are point events
    emitted while JAX traces it (once per jit cache entry): their
    structure (level, grid, engine) is the recursion's, which is what the
    op-count-oracle tests check; device time per level comes from the
    named scopes above.

Every span is also mirrored into the flight recorder's ring buffer
(`repro.obs.flight`) so a post-mortem dump carries the trace tail.
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
import threading
import time
from typing import Any, Iterator, Optional

from repro import envconfig

__all__ = ["Span", "SpanTracer", "TRACER", "tracer", "trace_enabled",
           "tracing", "refresh", "TRACE_ENV", "TRACE_DIR_ENV", "STEPS",
           "LAYOUT", "level_scope", "step_scope", "host_span", "op_scope",
           "hlo_op_scopes", "profiler_clock"]

TRACE_ENV = "SPIN_TRACE"
TRACE_DIR_ENV = "SPIN_TRACE_DIR"


# ---------------------------------------------------------------------------
# Always-on names of the recursion (HLO metadata, not gated by SPIN_TRACE)
# ---------------------------------------------------------------------------

LEVEL_PREFIX = "spin.L"
LAYOUT = "spin.layout"
# Steps of one internal node in Algorithm-2 order (`schur` is IV and V
# fused, `C11` is VII and the subtract fused), the leaf, the SUMMA gathers
# inside a product step (mesh only), and the layout.
STEPS = ("split", "II", "III", "schur", "C12", "C21", "C11", "neg",
         "arrange", "leaf", "gather", LAYOUT)
_LEVEL = re.compile(re.escape(LEVEL_PREFIX) + r"(\d+)")


def level_scope(level: int):
    """The named scope of one recursion node at depth `level`."""
    import jax

    return jax.named_scope(f"{LEVEL_PREFIX}{level}")


def step_scope(step: str):
    """The named scope of one step of a node (one of `STEPS`)."""
    import jax

    if step not in STEPS:
        raise ValueError(f"unknown recursion step {step!r} (known: {STEPS})")
    return jax.named_scope(step)


def host_span(name: str):
    """A host span on the profiler's clock (`jax.profiler.TraceAnnotation`);
    about a microsecond when no profiler session is open."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def op_scope(op_name: str) -> tuple[int | None, str | None]:
    """(level, step) of an HLO `op_name`: the innermost `spin.L<k>` and the
    innermost step scope inside it; None where there is none. The last
    component is the primitive, not a scope, and is not read."""
    level = step = None
    for part in op_name.split("/")[:-1]:
        m = _LEVEL.fullmatch(part)
        if m:
            level, step = int(m.group(1)), None
        elif part in STEPS:
            step = part
    return level, step


_MODULE = re.compile(r"HloModule ([^\s,]+)")
_COMPUTATION = re.compile(r"(?:ENTRY )?%(\S+) .*\{$")
_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = .*? [\w-]+\(([^)]*)\)")
_OPERAND = re.compile(r"%([^\s,)]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEES = re.compile(r"(?:body|condition|to_apply|calls|true_computation|"
                      r"false_computation)=%([^\s,)}]+)"
                      r"|branch_computations=\{([^}]*)\}")
_UNSCOPED = (None, None)


def hlo_op_scopes(text: str) -> dict[str, dict[str, tuple]]:
    """{module: {instruction: (level, step)}} from a compiled module's text
    (`Compiled.as_text()`), named as a profiler trace names them.

    A fusion carries its root's `op_name`, so it is put down to its root's
    scope. The compiler adds instructions with no scope of their own (the
    copies of layout assignment, loop bookkeeping); such an instruction
    takes the scope of its first scoped user (a layout copy exists for the
    op that reads it), else of its first scoped operand, else of the
    instruction that calls its computation (a loop's body, a branch).
    """
    computations: list[tuple[str, str, list]] = []
    module = ""
    for line in text.splitlines():
        m = _MODULE.match(line)
        if m:
            module = m.group(1)
            continue
        m = _COMPUTATION.match(line)
        if m:
            computations.append((module, m.group(1), []))
            continue
        m = _INSTR.match(line)
        if m and computations:
            name = _OP_NAME.search(line)
            callees = [c for one, many in _CALLEES.findall(line)
                       for c in ([one] if one else
                                 [x.strip().lstrip("%")
                                  for x in many.split(",")])]
            computations[-1][2].append(
                (m.group(1), op_scope(name.group(1)) if name else _UNSCOPED,
                 _OPERAND.findall(m.group(2)), callees))
    # Operands are printed before their users, and called computations
    # before the instruction that calls them.
    out: dict[str, dict[str, tuple]] = {}
    inherited: dict[tuple[str, str], tuple] = {}
    for module, comp, instructions in reversed(computations):
        scope = {name: s for name, s, _, _ in instructions}
        for name, _, operands, _ in reversed(instructions):
            for o in operands:
                if scope.get(o) == _UNSCOPED:
                    scope[o] = scope[name]
        default = inherited.get((module, comp), _UNSCOPED)
        ops = out.setdefault(module, {})
        for name, _, operands, callees in instructions:
            if scope[name] == _UNSCOPED:
                scope[name] = next((scope[o] for o in operands
                                    if scope.get(o, _UNSCOPED) != _UNSCOPED),
                                   default)
            ops[name] = scope[name]
            for callee in callees:
                inherited.setdefault((module, callee), scope[name])
    return out


def profiler_clock() -> float:
    """Seconds on the profiler's host clock. An event of a `jax.profiler`
    trace that starts `start_ns` into the session lies at
    `profile_start_time + start_ns` nanoseconds on it (the trace's
    `Task Environment` plane holds `profile_start_time`)."""
    return time.time_ns() * 1e-9


@dataclasses.dataclass
class Span:
    """One structured event. Point events have t1 == t0."""

    name: str
    kind: str                 # "recursion_level" | "planner_decision" | ...
    t0: float
    t1: float
    attrs: dict[str, Any]
    thread: int

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0

    def to_dict(self) -> dict:
        return {"name": self.name, "kind": self.kind, "t0": self.t0,
                "t1": self.t1, "duration_s": self.duration_s,
                "thread": self.thread, **self.attrs}


class SpanTracer:
    """Bounded in-memory span store with an `enabled` fast-path guard.

    `enabled` is a plain attribute, not a property: the disabled-path cost
    at every instrumentation site is one LOAD_ATTR. Flipping it is done via
    `tracing(...)` (tests) or `refresh()` (env changes mid-process).
    """

    def __init__(self, *, enabled: bool | None = None, capacity: int = 65536):
        self.enabled = (envconfig.env_bool(TRACE_ENV)
                        if enabled is None else bool(enabled))
        self.capacity = capacity
        self._lock = threading.Lock()
        self._spans: list[Span] = []
        self._dropped = 0

    # -- recording -----------------------------------------------------------

    def _store(self, span: Span) -> None:
        with self._lock:
            if len(self._spans) >= self.capacity:
                self._dropped += 1
                return
            self._spans.append(span)
        # Mirror into the flight recorder so crash dumps carry the tail.
        # Merged dict, attrs last: an event that carries its own name or
        # duration_s attr (e.g. worker.done's shard duration) must override
        # the span-level value, not raise a duplicate-kwarg TypeError.
        from . import flight

        flight.recorder().record(span.kind, **{
            "name": span.name, "duration_s": span.duration_s, **span.attrs})

    def event(self, name: str, kind: str, **attrs) -> Optional[Span]:
        """Record a point event (no duration). No-op when disabled."""
        if not self.enabled:
            return None
        now = profiler_clock()
        span = Span(name=name, kind=kind, t0=now, t1=now, attrs=attrs,
                    thread=threading.get_ident())
        self._store(span)
        return span

    @contextlib.contextmanager
    def span(self, name: str, kind: str, **attrs
             ) -> Iterator[Optional[Span]]:
        """Timed host span context, also opened as a
        `jax.profiler.TraceAnnotation` of the same name. Call sites must
        still guard with `if TRACER.enabled:` — entering a contextmanager
        is NOT free."""
        if not self.enabled:
            yield None
            return
        t0 = profiler_clock()
        span = Span(name=name, kind=kind, t0=t0, t1=t0, attrs=attrs,
                    thread=threading.get_ident())
        try:
            with host_span(name):
                yield span
        finally:
            span.t1 = profiler_clock()
            self._store(span)

    # -- reading -------------------------------------------------------------

    def spans(self, kind: str | None = None, name: str | None = None
              ) -> list[Span]:
        with self._lock:
            out = list(self._spans)
        if kind is not None:
            out = [s for s in out if s.kind == kind]
        if name is not None:
            out = [s for s in out if s.name == name]
        return out

    @property
    def dropped(self) -> int:
        return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._dropped = 0

    def refresh(self) -> bool:
        """Re-read $SPIN_TRACE (for processes that flip it mid-run)."""
        self.enabled = envconfig.env_bool(TRACE_ENV)
        return self.enabled


# The process-global tracer every subsystem guards on. Import-time env read
# only — no jax import, no side effects.
TRACER = SpanTracer()


def tracer() -> SpanTracer:
    return TRACER


def trace_enabled() -> bool:
    return TRACER.enabled


def refresh() -> bool:
    return TRACER.refresh()


@contextlib.contextmanager
def tracing(enabled: bool = True, *, clear: bool = False) -> Iterator[SpanTracer]:
    """Temporarily flip the global tracer (tests, benchmark sections).

    `clear=True` empties the span store on entry so assertions see only the
    spans of the guarded region. The previous enabled state is restored.
    """
    prev = TRACER.enabled
    if clear:
        TRACER.clear()
    TRACER.enabled = bool(enabled)
    try:
        yield TRACER
    finally:
        TRACER.enabled = prev
