"""One documented table for every `SPIN_*` environment knob.

Before this module each subsystem invented its own env var and parsed it in
place — eight knobs scattered over seven files, none discoverable without
grepping. Every knob now has exactly one `EnvVar` row here (name, default,
type, one-line doc) and the owning modules read it through the typed
accessors below. The table is the authority:

  * `tests/test_obs.py` greps the source tree and fails if any
    `os.environ`-visible `SPIN_*` name is missing from the table, so a new
    knob cannot ship undocumented;
  * README's "Environment variables" section is this table, rendered
    (`env_table_markdown()` regenerates it).

Reads are deliberately NOT cached: several tests (and the serving layer's
hermetic conftest) monkeypatch these variables per-test, and a knob like
`SPIN_STRASSEN_CUTOFF` documents its own trace-time caveat instead of this
layer adding another. This module must stay import-light (no jax): it is
imported by `repro.kernels` and `repro.launch` before jax configuration.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Optional

__all__ = ["EnvVar", "SPIN_ENV_VARS", "registered_names", "spec",
           "env_raw", "env_str", "env_int", "env_float", "env_bool",
           "env_table_markdown"]


@dataclasses.dataclass(frozen=True)
class EnvVar:
    """One documented knob: its name, type, default, and what it does."""

    name: str
    kind: str            # "str" | "int" | "float" | "bool" | "path" | "json"
    default: Optional[str]   # rendered default (None = unset/disabled)
    description: str
    owner: str           # module that consumes it


SPIN_ENV_VARS: tuple[EnvVar, ...] = (
    EnvVar("SPIN_TRACE", "bool", None,
           "Enable the structured span tracer (repro.obs.trace). Off by "
           "default; when off the instrumentation is a single attribute "
           "check and inserts no host syncs.",
           "repro.obs.trace"),
    EnvVar("SPIN_TRACE_DIR", "path", None,
           "Directory for flight-recorder JSONL dumps and trace exports. "
           "Unset disables dumping (events still ring-buffer in memory).",
           "repro.obs.flight"),
    EnvVar("SPIN_FLIGHT_CAPACITY", "int", "512",
           "Ring-buffer capacity (events) of the default flight recorder.",
           "repro.obs.flight"),
    EnvVar("SPIN_PLAN_CACHE", "path", "~/.cache/repro_spin/plans.json",
           "Plan-cache JSON path (plans + fitted calibration constants).",
           "repro.planner.cache"),
    EnvVar("SPIN_FAULT_PLAN", "json", None,
           "Serialized FaultPlan (scripted stragglers/failures) picked up "
           "by coded execution and subprocess mesh harnesses.",
           "repro.parallel.straggler"),
    EnvVar("SPIN_PALLAS_INTERPRET", "bool", None,
           "Off-TPU only: also route the optional Pallas paths (Strassen "
           "base case) through the interpreted kernels (CPU CI). Kernels "
           "run compiled on TPU, where setting it is an error.",
           "repro.kernels"),
    EnvVar("SPIN_STRASSEN_CUTOFF", "int", "512",
           "Operand size at/below which Strassen goes classical. Read at "
           "trace time — cached jit executables keep their old cutoff.",
           "repro.core.strassen"),
    EnvVar("SPIN_PRECISION", "str", None,
           "Default PrecisionPolicy preset (e.g. 'bf16') for entry points "
           "called without an explicit policy. Unset = exact.",
           "repro.core.precision"),
    EnvVar("SPIN_PRECISION_POLISH_SWEEPS", "int", None,
           "Override a policy's Newton-Schulz polish sweep count.",
           "repro.core.precision"),
    EnvVar("SPIN_PRECISION_MAX_POLISH_SWEEPS", "int", None,
           "Cap on serve-time certification polish sweeps.",
           "repro.core.precision"),
    EnvVar("SPIN_PRECISION_TOL", "float", None,
           "Override a policy's certified residual tolerance.",
           "repro.core.precision"),
    EnvVar("SPIN_COORDINATOR", "str", None,
           "Multi-process JAX coordinator address (host:port).",
           "repro.launch.mesh"),
    EnvVar("SPIN_NUM_PROCS", "int", "1",
           "Multi-process JAX process count.",
           "repro.launch.mesh"),
    EnvVar("SPIN_PROC_ID", "int", "0",
           "This process's index under SPIN_COORDINATOR.",
           "repro.launch.mesh"),
)

_BY_NAME = {v.name: v for v in SPIN_ENV_VARS}

# Parsings accepted as boolean true, matching repro.kernels' historical set.
_TRUE = {"1", "true", "yes", "on"}
_FALSE = {"0", "false", "no", "off", ""}


def registered_names() -> frozenset[str]:
    return frozenset(_BY_NAME)


def spec(name: str) -> EnvVar:
    return _BY_NAME[name]


def _check(name: str) -> None:
    if name not in _BY_NAME:
        raise KeyError(
            f"{name} is not in the SPIN_ENV_VARS table (envconfig.py) — "
            f"register new knobs there so they stay documented")


def env_raw(name: str) -> Optional[str]:
    """The raw value, or None when unset. `name` must be registered."""
    _check(name)
    return os.environ.get(name)


def env_str(name: str, default: Optional[str] = None) -> Optional[str]:
    _check(name)
    v = os.environ.get(name)
    return default if v is None or not v.strip() else v


def env_int(name: str, default: Optional[int] = None) -> Optional[int]:
    _check(name)
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {raw!r}")


def env_float(name: str, default: Optional[float] = None) -> Optional[float]:
    _check(name)
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        raise ValueError(f"{name} must be a number, got {raw!r}")


def env_bool(name: str, default: bool = False,
             *, unset: Optional[bool] = None) -> bool:
    """Tri-state boolean: unset → `unset` if given else `default`;
    "1/true/yes/on" → True; "0/false/no/off/''" → False; anything else
    raises (a typo'd SPIN_TRACE=yess must not silently disable tracing)."""
    _check(name)
    raw = os.environ.get(name)
    if raw is None:
        return default if unset is None else unset
    v = raw.strip().lower()
    if v in _TRUE:
        return True
    if v in _FALSE:
        return False
    raise ValueError(f"{name} must be boolean-ish (1/0/true/false), "
                     f"got {raw!r}")


def env_table_markdown() -> str:
    """The README 'Environment variables' table, rendered from the specs."""
    rows = ["| Variable | Type | Default | Purpose |",
            "|---|---|---|---|"]
    for v in SPIN_ENV_VARS:
        default = "*(unset)*" if v.default is None else f"`{v.default}`"
        rows.append(f"| `{v.name}` | {v.kind} | {default} | "
                    f"{v.description} |")
    return "\n".join(rows)


# Convenience probe used by call sites that want "is this knob set at all"
# without re-stating the name-check boilerplate.
def is_set(name: str) -> bool:
    _check(name)
    return bool(os.environ.get(name, "").strip())
