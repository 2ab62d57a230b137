import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (arch × shape) cell on the
# production meshes and extract the roofline inputs (FLOPs, bytes, per-device
# memory, collective traffic) from the compiled artifact. No arrays are ever
# allocated — inputs are ShapeDtypeStructs.
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b \
#       --shape train_4k --mesh both --out experiments/dryrun
#
# The two lines above MUST run before any other import (jax locks the device
# count on first init); do not move them.

import argparse
import json
import re
import time
import traceback

import jax
from jax.sharding import PartitionSpec as P

from repro.compat import set_mesh
from repro.configs import SHAPES, cell_status, get_arch, list_archs
from repro.configs.registry import ArchConfig
from repro.configs.shapes import ShapeConfig
from repro.data.synthetic import input_specs
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.optim.adamw import AdamWState
from repro.parallel.sharding import (DEFAULT_RULES, ShardingRules,
                                     logical_spec)
from repro.runtime.trainer import (TrainConfig, TrainState, abstract_state,
                                   make_train_step)

# Named sharding-rule variants (hillclimb knobs; §Perf references these).
RULES_VARIANTS: dict[str, ShardingRules] = {
    "default": DEFAULT_RULES,
    "sp": ShardingRules(seq="model"),                   # Megatron-style SP
    "dp_only": ShardingRules(heads=None, kv_heads=None, ffn=None,
                             vocab=None, experts=None, ssm_inner=None,
                             embed_w=("data", "model")),
    "fsdp_both": ShardingRules(embed_w=("data", "model"), seq="model"),
    "ssd_cp": ShardingRules(ssm_chunk="model"),
    "sp_ssd_cp": ShardingRules(seq="model", ssm_chunk="model"),
}

# Named config transforms (hillclimb knobs on model-math parameters).
def _hymba_tuned(cfg: ArchConfig) -> ArchConfig:
    import dataclasses as _dc
    # chunk sizes sized to the SWA window / tiny SSD state (see §Perf)
    return _dc.replace(cfg, attn_q_chunk=512, attn_kv_chunk=512,
                       ssm=_dc.replace(cfg.ssm, chunk=64))


def _hymba_tuned2(cfg: ArchConfig) -> ArchConfig:
    import dataclasses as _dc
    return _dc.replace(cfg, attn_q_chunk=512, attn_kv_chunk=512,
                       ssm=_dc.replace(cfg.ssm, chunk=32))


def _ssd_chunk(q: int):
    def f(cfg: ArchConfig) -> ArchConfig:
        import dataclasses as _dc
        return _dc.replace(cfg, ssm=_dc.replace(cfg.ssm, chunk=q))
    return f


def _attn_chunk(q: int):
    def f(cfg: ArchConfig) -> ArchConfig:
        import dataclasses as _dc
        return _dc.replace(cfg, attn_q_chunk=q, attn_kv_chunk=q)
    return f


CFG_VARIANTS = {
    "base": lambda cfg: cfg,
    "hymba_tuned": _hymba_tuned,
    "hymba_tuned2": _hymba_tuned2,
    "ssd_chunk_64": _ssd_chunk(64),
    "ssd_chunk_128": _ssd_chunk(128),
    "attn_chunk_512": _attn_chunk(512),
    "attn_chunk_1024": _attn_chunk(1024),
}

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
                "c128": 16, "token": 0}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DEF_RE = re.compile(
    r"%([\w.\-]+)\s*=\s*\(?([a-z]+[0-9]*(?:e[0-9]+m[0-9]+)?)\[([0-9,]*)\]")
_NAME_RE = re.compile(r"%([\w.\-]+)")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_stats(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in (partitioned) HLO text.

    HLO prints operands as bare `%name` references, so pass 1 builds a
    name -> bytes map from instruction definitions; pass 2 walks collective
    ops and sums their operands' bytes. NOTE: ops inside `while` bodies
    appear once regardless of trip count — callers scale by depth via the
    linear (L, M) extrapolation in `run_cell`.
    """
    sizes: dict[str, int] = {}
    for m in _DEF_RE.finditer(hlo_text):
        sizes[m.group(1)] = _shape_bytes(m.group(2), m.group(3))

    stats: dict[str, dict] = {c: {"count": 0, "operand_bytes": 0}
                              for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        # result type may be a tuple "(f32[..], f32[..])" for -start ops
        m = re.search(r"=\s+(?:\([^)]*\)|\S+)\s+([a-z][a-z0-9\-]*)\(", line)
        if not m:
            continue
        op = m.group(1)
        base = op[:-6] if op.endswith("-start") else op
        if base not in _COLLECTIVES or op.endswith("-done"):
            continue
        call = line[m.end():]
        depth, end = 1, len(call)
        for i, ch in enumerate(call):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operand_names = _NAME_RE.findall(call[:end])
        total = sum(sizes.get(nm, 0) for nm in operand_names)
        stats[base]["count"] += 1
        stats[base]["operand_bytes"] += total
    stats["total_operand_bytes"] = sum(
        v["operand_bytes"] for k, v in stats.items() if isinstance(v, dict))
    return stats


# ---------------------------------------------------------------------------
# Cell builders: (fn, abstract args, in_shardings, out_shardings, donate)
# ---------------------------------------------------------------------------


def _param_state_specs(cfg: ArchConfig, rules: ShardingRules, mesh):
    pspecs = T.param_specs(cfg, rules, mesh,
                           model_size_hint=mesh.shape.get("model", 16))
    opt_specs = AdamWState(step=P(), master=pspecs, m=pspecs, v=pspecs)
    return TrainState(params=pspecs, opt=opt_specs, step=P())


def _batch_specs(batch_abs: dict, rules: ShardingRules, mesh) -> dict:
    return {k: logical_spec(v.shape, ("batch",) + (None,) * (v.ndim - 1),
                            rules, mesh)
            for k, v in batch_abs.items()}


_REMAT_POLICY = "full"      # set by --remat-policy; threaded via module state


def build_train(cfg: ArchConfig, shape: ShapeConfig, mesh,
                rules: ShardingRules, microbatches: int):
    tcfg = TrainConfig(microbatches=microbatches,
                       remat_policy=_REMAT_POLICY)
    state_abs = abstract_state(cfg, tcfg,
                               model_size_hint=mesh.shape.get("model", 16))
    batch_abs = input_specs(cfg, shape)
    state_specs = _param_state_specs(cfg, rules, mesh)
    batch_specs = _batch_specs(batch_abs, rules, mesh)
    fn = make_train_step(cfg, tcfg, rules)
    return (fn, (state_abs, batch_abs), (state_specs, batch_specs),
            (state_specs, None), (0,))


def build_prefill(cfg: ArchConfig, shape: ShapeConfig, mesh,
                  rules: ShardingRules):
    params_abs = T.abstract_params(
        cfg, model_size_hint=mesh.shape.get("model", 16))
    batch_abs = input_specs(cfg, shape)
    pspecs = T.param_specs(cfg, rules, mesh,
                           model_size_hint=mesh.shape.get("model", 16))
    batch_specs = _batch_specs(batch_abs, rules, mesh)

    def fn(params, batch):
        logits, aux, z, cache = T.prefill(params, batch, cfg, rules)
        return logits, cache

    return fn, (params_abs, batch_abs), (pspecs, batch_specs), None, ()


def build_decode(cfg: ArchConfig, shape: ShapeConfig, mesh,
                 rules: ShardingRules):
    hint = mesh.shape.get("model", 16)
    params_abs = T.abstract_params(cfg, model_size_hint=hint)
    cache_abs = T.abstract_cache(cfg, shape.global_batch, shape.seq_len)
    tokens_abs = jax.ShapeDtypeStruct((shape.global_batch,), jax.numpy.int32)
    pspecs = T.param_specs(cfg, rules, mesh, model_size_hint=hint)
    cspecs = T.cache_specs(cfg, shape.global_batch, shape.seq_len, rules,
                           mesh)
    tspec = logical_spec(tokens_abs.shape, ("batch",), rules, mesh)

    def fn(params, cache, tokens):
        return T.decode_step(params, cache, tokens, cfg, rules)

    return (fn, (params_abs, cache_abs, tokens_abs),
            (pspecs, cspecs, tspec), None, (1,))


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------


def _compile_cell(cfg: ArchConfig, shape: ShapeConfig, mesh,
                  rules: ShardingRules, microbatches: int):
    """Lower + compile one configuration; return (compiled, timings)."""
    if shape.kind == "train":
        built = build_train(cfg, shape, mesh, rules, microbatches)
    elif shape.kind == "prefill":
        built = build_prefill(cfg, shape, mesh, rules)
    else:
        built = build_decode(cfg, shape, mesh, rules)
    fn, args, in_sh, out_sh, donate = built
    t0 = time.time()
    jitted = jax.jit(fn, in_shardings=in_sh,
                     out_shardings=out_sh,
                     donate_argnums=donate)
    lowered = jitted.lower(*args)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    return compiled, round(t1 - t0, 2), round(t2 - t1, 2)


def _measure(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    coll = collective_stats(compiled.as_text())
    return dict(flops=cost.get("flops", 0.0) or 0.0,
                bytes_accessed=cost.get("bytes accessed", 0.0) or 0.0,
                coll_bytes=float(coll["total_operand_bytes"]),
                coll=coll)


def _extrapolate(f1: float, f2: float, n_layers: int) -> float:
    """XLA cost_analysis counts while-loop bodies ONCE, so probe at L∈{1,2}
    with a single microbatch and scale the per-layer delta analytically
    (exact for homogeneous scans). Total work is microbatch-count-invariant,
    so probing at M=1 covers the M=8 production step too. The per-layer
    delta is clamped at 0: for tiny cells (e.g. 130M decode) fusion noise
    between the two probes can exceed the real per-layer cost."""
    c = max(f2 - f1, 0.0)
    return f1 + (n_layers - 1) * c


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
             rules_name: str = "default", variant: str = "base",
             microbatches: int = 8, verbose: bool = True) -> dict:
    cfg = CFG_VARIANTS[variant](get_arch(arch_name))
    shape = SHAPES[shape_name]
    mesh_label = "2x16x16" if multi_pod else "16x16"
    rec: dict = dict(arch=arch_name, shape=shape_name, mesh=mesh_label,
                     rules=rules_name, variant=variant, kind=shape.kind,
                     microbatches=microbatches if shape.kind == "train"
                     else None)
    runnable, reason = cell_status(cfg, shape)
    if not runnable:
        rec.update(runnable=False, skip_reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = RULES_VARIANTS[rules_name]
    chips = mesh.devices.size
    rec.update(runnable=True, chips=chips)

    with set_mesh(mesh):
        # 1) the deliverable: the FULL config lowers + compiles
        compiled, lower_s, compile_s = _compile_cell(
            cfg, shape, mesh, rules, microbatches)
        mem = compiled.memory_analysis()
        full = _measure(compiled)

        # 2) roofline inputs: XLA counts while-loop bodies once, so probe at
        # L∈{1,2} (single microbatch) with ALL scans unrolled — attention kv
        # chunks, SSD chunks, layer stack become straight-line HLO that
        # cost_analysis counts exactly — then extrapolate linearly in L.
        import dataclasses as _dc
        from repro.models.scan_util import unroll_scans
        cfg1 = _dc.replace(cfg, n_layers=1)
        cfg2 = _dc.replace(cfg, n_layers=2)
        with unroll_scans():
            m1 = _measure(_compile_cell(cfg1, shape, mesh, rules, 1)[0])
            m2 = _measure(_compile_cell(cfg2, shape, mesh, rules, 1)[0])

        def extrap(key):
            return _extrapolate(m1[key], m2[key], cfg.n_layers)

        rec.update(
            lower_s=lower_s, compile_s=compile_s,
            per_device=dict(
                argument_bytes=getattr(mem, "argument_size_in_bytes", None),
                output_bytes=getattr(mem, "output_size_in_bytes", None),
                temp_bytes=getattr(mem, "temp_size_in_bytes", None),
                alias_bytes=getattr(mem, "alias_size_in_bytes", None),
            ),
            cost_raw=dict(flops=full["flops"],
                          bytes_accessed=full["bytes_accessed"],
                          coll_bytes=full["coll_bytes"]),
            cost=dict(flops=extrap("flops"),
                      bytes_accessed=extrap("bytes_accessed"),
                      coll_bytes=extrap("coll_bytes")),
            collectives_once=full["coll"],
        )
    if verbose:
        tb = rec["per_device"]["temp_bytes"] or 0
        print(f"[{arch_name} × {shape_name} × {mesh_label} × {rules_name} × "
              f"{variant}] compile {compile_s}s  temp/dev {tb/2**30:.2f}GiB  "
              f"flops/dev {rec['cost']['flops']:.3e}  "
              f"coll/dev {rec['cost']['coll_bytes']/2**20:.1f}MiB  "
              f"mem/dev(bytes_accessed) "
              f"{rec['cost']['bytes_accessed']/2**30:.1f}GiB")
    return rec


def run_solver_cell(n: int, block_size: int, *, multi_pod: bool,
                    engine: str = "einsum", dtype: str = "float32",
                    algo: str = "spin", out_dir: str | None = None,
                    verbose: bool = True) -> dict:
    """Dry-run the paper's technique itself: distributed inversion on the
    production mesh. Same measurement pipeline as the LM cells (the solver
    has no layer scan, so no extrapolation is needed — its recursion is
    fully inlined HLO and cost_analysis counts it exactly)."""
    import jax.numpy as jnp
    from repro.core import BlockMatrix, lu_inverse, multiply_engine, \
        spin_inverse

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_label = "2x16x16" if multi_pod else "16x16"
    grid = n // block_size
    dt = getattr(jnp, dtype)
    rec = dict(arch=f"solver-{algo}", shape=f"n{n}_b{grid}_{dtype}_{engine}",
               mesh=mesh_label, rules=engine, kind="solver", runnable=True,
               chips=mesh.devices.size, n=n, grid=grid,
               block_size=block_size)

    fn_algo = spin_inverse if algo == "spin" else lu_inverse

    def invert(blocks):
        return fn_algo(BlockMatrix(blocks)).blocks

    abs_blocks = jax.ShapeDtypeStruct((grid, grid, block_size, block_size),
                                      dt)
    with set_mesh(mesh):
        with multiply_engine(engine):
            t0 = time.time()
            spec = P("data", "model", None, None)
            lowered = jax.jit(
                invert,
                in_shardings=spec,
                out_shardings=spec,
            ).lower(abs_blocks)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
        mem = compiled.memory_analysis()
        m = _measure(compiled)
    rec.update(
        lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2),
        per_device=dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", None),
            output_bytes=getattr(mem, "output_size_in_bytes", None),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            alias_bytes=getattr(mem, "alias_size_in_bytes", None)),
        cost=dict(flops=m["flops"], bytes_accessed=m["bytes_accessed"],
                  coll_bytes=m["coll_bytes"]),
        cost_raw=dict(flops=m["flops"], bytes_accessed=m["bytes_accessed"],
                      coll_bytes=m["coll_bytes"]),
        collectives_once=m["coll"],
    )
    if verbose:
        tb = rec["per_device"]["temp_bytes"] or 0
        print(f"[solver-{algo} n={n} grid={grid} {dtype} {engine} × "
              f"{mesh_label}] compile {rec['compile_s']}s  "
              f"temp/dev {tb / 2**30:.2f}GiB  flops/dev {m['flops']:.3e}  "
              f"coll/dev {m['coll_bytes'] / 2**20:.1f}MiB")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        label = f"solver-{algo}__{rec['shape']}__{mesh_label}"
        with open(os.path.join(out_dir, label + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--solver", action="store_true",
                    help="dry-run the SPIN solver itself instead of LM cells")
    ap.add_argument("--solver-n", type=int, default=65536)
    ap.add_argument("--solver-block", type=int, default=4096)
    ap.add_argument("--solver-engine", default="einsum",
                    choices=["einsum", "allgather", "ring"])
    ap.add_argument("--solver-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--solver-algo", default="spin", choices=["spin", "lu"])
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all' (see configs/)")
    ap.add_argument("--shape", default="all", help="shape id or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--rules", default="default",
                    choices=sorted(RULES_VARIANTS))
    ap.add_argument("--variant", default="base", choices=sorted(CFG_VARIANTS))
    ap.add_argument("--remat-policy", default="full",
                    choices=["full", "dots"])
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    global _REMAT_POLICY
    _REMAT_POLICY = args.remat_policy

    if args.solver:
        for mp in {"single": [False], "multi": [True],
                   "both": [False, True]}[args.mesh]:
            run_solver_cell(args.solver_n, args.solver_block, multi_pod=mp,
                            engine=args.solver_engine,
                            dtype=args.solver_dtype, algo=args.solver_algo,
                            out_dir=args.out)
        return

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                label = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}" \
                    f"__{args.rules}"
                if args.variant != "base":
                    label += f"__{args.variant}"
                path = os.path.join(args.out, label + ".json")
                try:
                    rec = run_cell(arch, shape, multi_pod=mp,
                                   rules_name=args.rules,
                                   variant=args.variant,
                                   microbatches=args.microbatches)
                except Exception as e:  # noqa: BLE001 — record, keep going
                    rec = dict(arch=arch, shape=shape,
                               mesh="2x16x16" if mp else "16x16",
                               rules=args.rules, runnable=True,
                               error=f"{type(e).__name__}: {e}")
                    failures.append(label)
                    print(f"[{label}] FAILED: {e}")
                    traceback.print_exc()
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
    if failures:
        print(f"\n{len(failures)} FAILED cells: {failures}")
        raise SystemExit(1)
    print("\nall requested cells lowered + compiled OK")


if __name__ == "__main__":
    main()
