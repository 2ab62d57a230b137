"""Unified precision policy: one object for every dtype knob (DESIGN.md §12).

Before this module, precision lived in scattered kwargs: the planner's
`Plan.compute_dtype`, ad-hoc `dtype=` arguments, and the serving path always
running at the matrix's storage dtype. `PrecisionPolicy` consolidates them:

  * store dtype    — what the maintained inverse lives in (HBM bytes; bf16
                     halves the memory-bound `apply_inverse` roofline);
  * compute dtype  — what the recursion / serve GEMMs run in;
  * accum dtype    — the accumulator the kernels flush from (the Pallas
                     GEMMs keep f32 VMEM accumulators regardless of input);
  * polish         — Newton–Schulz sweeps that certify the low-precision
                     inverse back under the policy's residual bound, fired
                     only when a probe residual exceeds it;
  * tolerance      — the certified serve bound; defaults to the conformance
                     harness's dtype-aware `residual_tolerance`.

Policies resolve from three sources, strongest first: an explicit
`PrecisionPolicy`, a preset string ("bf16", "fp8", "auto", "exact"), or the
``SPIN_PRECISION`` environment variable (HomebrewNLP dtype-policy style:
one env knob selects the policy, per-field env knobs override its numbers).
`descriptor()` round-trips a policy through a compact string — the form the
planner's `ProblemSignature.precision` axis and service snapshots carry.

The "fp8" preset is a *storage hook*: it is only constructible where
`compat.supports_float8()` detects a usable float8_e4m3fn, and it computes
in bf16 (fp8 GEMMs need per-tensor scaling this repo does not implement) —
the point is that the storage axis, cache keys, and cost model already
price 1-byte elements, so enabling real fp8 math later is a kernel change,
not an API change.
"""

from __future__ import annotations

import dataclasses
import warnings

__all__ = ["PrecisionPolicy", "PRECISION_PRESETS", "resolve_precision",
           "DEFAULT_PRECISION_ENV", "dot_precision"]

# The one env knob selecting the default policy (preset name or descriptor).
DEFAULT_PRECISION_ENV = "SPIN_PRECISION"

# Per-field numeric overrides, applied on top of env/preset-string
# resolution (never on top of an explicitly constructed policy — an object
# the caller built is taken verbatim).
_FIELD_ENV = {
    "polish_sweeps": "SPIN_PRECISION_POLISH_SWEEPS",
    "max_polish_sweeps": "SPIN_PRECISION_MAX_POLISH_SWEEPS",
    "tolerance": "SPIN_PRECISION_TOL",
}

_STORE_DTYPES = ("bfloat16", "float16", "float32", "float64",
                 "float8_e4m3fn")


def dot_precision(*dtypes):
    """The `precision=` for a GEMM over operands of `dtypes`.

    f32 operands get `Precision.HIGHEST`: a TPU runs an f32 dot at the
    default precision as one bf16 pass, which would give every f32 result
    bf16 rounding. Any other dtype keeps the default, so bf16 math is
    reached only by storing or computing in bf16 — i.e. through a
    `PrecisionPolicy`, never by accident. CPU f32 dots are exact either way.
    """
    import jax
    import jax.numpy as jnp

    if all(jnp.dtype(d) == jnp.float32 for d in dtypes):
        return jax.lax.Precision.HIGHEST
    return None


def _valid_dtype(name: str) -> bool:
    return name in _STORE_DTYPES


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Everything the engine/planner/service may vary about precision.

    `store_dtype=None` means "the operand's own dtype" (exact storage);
    `compute_dtype=None` follows the store dtype. `auto_store=True` hands
    the store-dtype choice to the planner (the `auto=True` path prices
    bf16 storage against exact and picks per signature). `tolerance=None`
    defaults to the conformance harness's `residual_tolerance` for the
    policy's weakest resolved dtype — the certified serve bound.
    """

    name: str = "exact"
    store_dtype: str | None = None
    compute_dtype: str | None = None
    accum_dtype: str = "float32"
    auto_store: bool = False
    polish_sweeps: int = 1        # NS sweeps per polish firing
    max_polish_sweeps: int = 8    # give-up bound per certification
    tolerance: float | None = None

    def __post_init__(self):
        for field in ("store_dtype", "compute_dtype"):
            v = getattr(self, field)
            if v is not None and not _valid_dtype(v):
                raise ValueError(f"{field}={v!r} is not a supported dtype "
                                 f"(one of {_STORE_DTYPES})")
        if self.accum_dtype not in ("float32", "float64"):
            raise ValueError(f"accum_dtype must be float32/float64, got "
                             f"{self.accum_dtype!r}")
        if (self.store_dtype or "").startswith("float8"):
            from repro import compat

            if not compat.supports_float8():
                raise ValueError(
                    "store_dtype=float8 requested but this jax build has no "
                    "usable float8_e4m3fn (compat.supports_float8() is "
                    "False); use the 'bf16' preset instead")
        if self.polish_sweeps < 0 or self.max_polish_sweeps < 0:
            raise ValueError("polish sweep counts must be >= 0")

    # -- resolution ---------------------------------------------------------
    @property
    def is_exact(self) -> bool:
        """True when the policy changes nothing about the default path."""
        return (self.store_dtype is None and self.compute_dtype is None
                and not self.auto_store)

    def resolve_store(self, operand_dtype) -> str:
        return self.store_dtype or _dtype_name(operand_dtype)

    def resolve_compute(self, operand_dtype) -> str:
        return (self.compute_dtype or self.store_dtype
                or _dtype_name(operand_dtype))

    def bound(self, operand_dtype) -> float:
        """Certified residual bound for serving under this policy."""
        if self.tolerance is not None:
            return self.tolerance
        from repro.core.verify import residual_tolerance  # late: no cycle

        return max(residual_tolerance(self.resolve_store(operand_dtype)),
                   residual_tolerance(self.resolve_compute(operand_dtype)))

    def candidate_store_dtypes(self, operand_dtype) -> tuple[str, ...]:
        """Store dtypes the planner may price for this policy."""
        op = _dtype_name(operand_dtype)
        if self.store_dtype:
            return (self.store_dtype,)
        if self.auto_store:
            # bf16 is the portable low-precision store; fp8 stays opt-in
            # (explicit "fp8" policy) until real scaled-fp8 GEMMs exist.
            return (op, "bfloat16") if op in ("float32", "float64") else (op,)
        return (op,)

    # -- serialization ------------------------------------------------------
    def descriptor(self) -> str:
        """Compact round-trippable string (the planner/snapshot form)."""
        for key, preset in PRECISION_PRESETS.items():
            if preset == self:
                return key
        parts = [f"n={self.name}",
                 f"s={self.store_dtype or '-'}",
                 f"c={self.compute_dtype or '-'}",
                 f"a={self.accum_dtype}",
                 f"auto={int(self.auto_store)}",
                 f"ps={self.polish_sweeps}",
                 f"mps={self.max_polish_sweeps}",
                 f"tol={'-' if self.tolerance is None else repr(self.tolerance)}"]
        return ";".join(parts)

    @classmethod
    def from_descriptor(cls, text: str) -> "PrecisionPolicy":
        if text in PRECISION_PRESETS:
            return PRECISION_PRESETS[text]
        if "=" not in text:
            raise ValueError(f"unknown precision preset {text!r} "
                             f"(known: {sorted(PRECISION_PRESETS)})")
        fields = dict(part.split("=", 1) for part in text.split(";"))
        try:
            return cls(
                name=fields.get("n", "custom"),
                store_dtype=None if fields.get("s", "-") == "-" else fields["s"],
                compute_dtype=(None if fields.get("c", "-") == "-"
                               else fields["c"]),
                accum_dtype=fields.get("a", "float32"),
                auto_store=bool(int(fields.get("auto", "0"))),
                polish_sweeps=int(fields.get("ps", "1")),
                max_polish_sweeps=int(fields.get("mps", "8")),
                tolerance=(None if fields.get("tol", "-") == "-"
                           else float(fields["tol"])))
        except (KeyError, ValueError) as e:
            raise ValueError(f"malformed precision descriptor {text!r}: {e}")

    @classmethod
    def resolve(cls, precision) -> "PrecisionPolicy":
        """None -> $SPIN_PRECISION or exact; str -> preset/descriptor;
        PrecisionPolicy -> itself (verbatim, no env overrides)."""
        if isinstance(precision, cls):
            return precision
        if precision is None:
            from repro import envconfig

            env = envconfig.env_str(DEFAULT_PRECISION_ENV)
            if env is None:
                return PRECISION_PRESETS["exact"]
            precision = env
        if not isinstance(precision, str):
            raise TypeError(f"precision must be a PrecisionPolicy, preset "
                            f"string, or None; got {type(precision).__name__}")
        policy = cls.from_descriptor(precision)
        return _apply_field_env(policy)


def _apply_field_env(policy: PrecisionPolicy) -> PrecisionPolicy:
    from repro import envconfig

    overrides = {}
    for field, var in _FIELD_ENV.items():
        raw = envconfig.env_raw(var)
        if raw is None:
            continue
        overrides[field] = (float(raw) if field == "tolerance"
                           else int(raw))
    return dataclasses.replace(policy, **overrides) if overrides else policy


def _dtype_name(dtype) -> str:
    if isinstance(dtype, str):
        return dtype
    import jax.numpy as jnp

    return jnp.dtype(dtype).name


def _make_presets() -> dict[str, PrecisionPolicy]:
    presets = {
        "exact": PrecisionPolicy(name="exact"),
        "bf16": PrecisionPolicy(name="bf16", store_dtype="bfloat16",
                                compute_dtype="bfloat16"),
        "auto": PrecisionPolicy(name="auto", auto_store=True),
    }
    presets["f32"] = presets["exact"]
    presets["float32"] = presets["exact"]
    presets["bfloat16"] = presets["bf16"]
    # fp8 storage hook: only registered where the capability probe passes,
    # so `resolve("fp8")` fails loudly (unknown preset) elsewhere instead
    # of minting un-executable policies.
    from repro import compat

    if compat.supports_float8():
        presets["fp8"] = PrecisionPolicy(name="fp8",
                                         store_dtype="float8_e4m3fn",
                                         compute_dtype="bfloat16",
                                         polish_sweeps=2,
                                         max_polish_sweeps=12)
    return presets


PRECISION_PRESETS = _make_presets()


def resolve_precision(precision) -> PrecisionPolicy:
    """Module-level alias for `PrecisionPolicy.resolve` (the common call)."""
    return PrecisionPolicy.resolve(precision)


# ---------------------------------------------------------------------------
# Deprecation shims for the pre-policy dtype kwargs
# ---------------------------------------------------------------------------

_WARNED_SITES: set[str] = set()


def warn_deprecated_dtype_kwarg(site: str, kwarg: str = "compute_dtype"
                                ) -> None:
    """One DeprecationWarning per call site per process, then silence."""
    if site in _WARNED_SITES:
        return
    _WARNED_SITES.add(site)
    warnings.warn(
        f"{site}({kwarg}=...) is deprecated; pass "
        f"precision=PrecisionPolicy({kwarg}=...) or a preset string "
        f"like precision='bf16'", DeprecationWarning, stacklevel=3)


def policy_from_compute_dtype(dtype) -> PrecisionPolicy:
    """The policy a legacy `compute_dtype=` kwarg forwards to: compute in
    the requested dtype, return at the operand dtype, no polish — bitwise
    what the old cast-in/cast-out path did."""
    return PrecisionPolicy(name="legacy", compute_dtype=_dtype_name(dtype),
                           polish_sweeps=0)
