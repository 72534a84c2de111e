import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

The two lines above MUST run before any jax import (jax locks the device
count at first init); 512 host devices back both the 256-chip single-pod
mesh and the 512-chip multi-pod mesh.

Per combination this driver:
  1. builds the model + ShapeDtypeStruct inputs (no allocation),
  2. assigns in_shardings (params HSDP, batch over data, caches per shape),
  3. ``jit(step).lower(...).compile()`` under the target mesh,
  4. records memory_analysis / cost_analysis / per-collective bytes parsed
     from the optimized HLO into results/dryrun/<arch>.<shape>.<mesh>.json.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape decode_32k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]
"""

import argparse
import functools
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import opt as opt_flags
from repro.configs import ASSIGNED_ARCHS, get_config, get_shape, SHAPES
from repro.configs.base import ModelConfig
from repro.configs.shapes import InputShape
from repro.launch import shardings as shd
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.models.build import build_model
from repro.sharding import use_mesh
from repro.training import optimizer
from repro.training.optimizer import OptimizerConfig
from repro.training.train_loop import make_train_step

# long-context policy (DESIGN.md §5): whisper skips long_500k; dense /
# full-attention archs run it through the sliding-window serving variant.
LONG_SKIP = {"whisper-base"}
LONG_WINDOW = {
    "yi-9b": 4096, "command-r-plus-104b": 4096, "mistral-large-123b": 4096,
    "qwen3-moe-235b-a22b": 4096, "llama-3.2-vision-11b": 4096,
    # native/window-free long-context archs:
    "h2o-danube-1.8b": None,      # native SWA already in config
    "rwkv6-1.6b": None, "zamba2-2.7b": None, "deepseek-v3-671b": None,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(%[\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\(")


def _type_bytes(type_str: str) -> int:
    """Bytes of an HLO type string (handles tuples by summing)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes per collective kind from optimized HLO text.

    Builds a symbol table of instruction result sizes, then for each
    collective sums the sizes of its named operands."""
    sizes: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            sizes[m.group(1)] = _type_bytes(m.group(2))
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        op = m.group(3)
        kind = None
        for c in _COLLECTIVES:
            if op == c or op.startswith(c + "-"):
                kind = c
                break
        if kind is None:
            continue
        # operand names inside the call parens
        args = line[line.index(op + "(") + len(op) + 1:]
        operands = re.findall(r"%[\w.\-]+", args)
        nbytes = sum(sizes.get(o, 0) for o in operands)
        if nbytes == 0:                     # fallback: result size
            nbytes = _type_bytes(m.group(2))
        out[kind] += nbytes
        counts[kind] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


# ---------------------------------------------------------------------------
# Step construction
# ---------------------------------------------------------------------------


def build_step(arch: str, shape_name: str, *, remat: bool = True,
               grad_accum: int = 1,
               window_override: Optional[int] = "auto"):
    """Returns (step_fn, args_sds tuple, in_shardings tuple, meta)."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    model = build_model(cfg)
    kind = shape.kind
    window = None
    if window_override == "auto":
        if shape_name == "long_500k":
            window = LONG_WINDOW.get(arch)
    else:
        window = window_override

    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch_sds = model.input_specs(shape)

    if kind == "train":
        opt_cfg = OptimizerConfig(
            moment_dtype="bfloat16"
            if opt_flags.enabled("opt_bf16_moments") else None)
        import jax.numpy as _jnp
        step = make_train_step(
            model, opt_cfg, remat=remat, grad_accum=grad_accum,
            accum_dtype=_jnp.bfloat16
            if opt_flags.enabled("opt_bf16_moments") else None)
        opt_sds = jax.eval_shape(
            lambda ps: optimizer.init(ps, opt_cfg.moment_dtype), params_sds)
        args = (params_sds, opt_sds, batch_sds)
        meta = {"step": "train_step"}
        return step, args, meta, model, cfg, shape

    if kind == "prefill":
        def step(params, batch, state):
            return model.prefill(params, batch, state)
        state_sds = model.state_specs(shape.global_batch, shape.seq_len)
        args = (params_sds, batch_sds, state_sds)
        return step, args, {"step": "prefill_step"}, model, cfg, shape

    # decode: ONE token against a cache of seq_len
    def step(params, token, state):
        if window is not None:
            return model.decode(params, token, state, window=window)
        return model.decode(params, token, state)

    state_sds = model.state_specs(shape.global_batch, shape.seq_len,
                                  window=window)
    args = (params_sds, batch_sds["token"], state_sds)
    meta = {"step": "serve_step", "window": window}
    return step, args, meta, model, cfg, shape


def shardings_for(args, kind: str, cfg: ModelConfig, mesh,
                  shape: InputShape):
    from jax.sharding import NamedSharding, PartitionSpec as P
    params_shd = shd.param_shardings_for(args[0], mesh)
    if kind == "train":
        opt_shd = shd.opt_state_shardings(args[0], mesh)
        batch_shd = shd.batch_shardings(mesh, args[2])
        return (params_shd, opt_shd, batch_shd)
    if kind == "prefill":
        batch_shd = shd.batch_shardings(mesh, args[1])
        state_shd = shd.state_shardings(args[2], cfg, mesh)
        return (params_shd, batch_shd, state_shd)
    token_shd = NamedSharding(
        mesh, shd.batch_spec(mesh, shape.global_batch, 1))
    state_shd = shd.state_shardings(args[2], cfg, mesh)
    return (params_shd, token_shd, state_shd)


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            out_dir: Optional[str] = None, remat: bool = True,
            grad_accum: int = 1,
            window_override="auto", verbose: bool = True) -> Dict[str, Any]:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    if shape_name == "long_500k" and arch in LONG_SKIP:
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                  "status": "skipped",
                  "reason": "enc-dec full attention; see DESIGN.md §5"}
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            fn = os.path.join(out_dir,
                              f"{arch}.{shape_name}.{mesh_name}.json")
            with open(fn, "w") as f:
                json.dump(result, f, indent=1)
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: SKIPPED "
              f"({result['reason']})")
        return result
    t0 = time.perf_counter()
    mesh = make_production_mesh(multi_pod=multi_pod)
    step, args, meta, model, cfg, shape = build_step(
        arch, shape_name, remat=remat, grad_accum=grad_accum,
        window_override=window_override)
    in_shd = shardings_for(args, shape.kind, cfg, mesh, shape)

    # donate the mutable buffers (train: params+opt; serve: the KV cache)
    # so XLA aliases them in place — production memory behavior.
    donate = {"train": (0, 1), "prefill": (), "decode": (2,)}[shape.kind]
    with use_mesh(mesh):
        jitted = jax.jit(step, in_shardings=in_shd, donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    # loop-aware per-device costs: multiply while-body costs by trip counts
    # (cost_analysis counts scan bodies ONCE — see analysis/hlo_costs.py)
    from repro.analysis.hlo_costs import analyze_hlo
    la = analyze_hlo(hlo)

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", **meta,
        "opt_flags": opt_flags.all_flags(),
        "grad_accum": grad_accum,
        "n_devices": int(mesh.devices.size),
        "device_kind": PRODUCTION_DEVICE_KIND,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "cost": {
            "flops": la["flops"],
            "bytes_accessed": la["memory_bytes"],
            "xla_flops_noloop": cost.get("flops"),
            "xla_bytes_noloop": cost.get("bytes accessed"),
        },
        "collectives": la["collectives"],
        "collectives_noloop": coll,
        "params": cfg.param_count(),
        "params_active": cfg.param_count(active_only=True),
        "hlo_lines": hlo.count("\n"),
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: OK "
              f"flops={result['cost']['flops']:.3e} "
              f"coll={la['collectives']['total_bytes']:.3e}B "
              f"(lower {t_lower:.1f}s compile {t_compile:.1f}s)")
        print(f"  memory_analysis: {result['memory']}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fn = os.path.join(out_dir, f"{arch}.{shape_name}.{mesh_name}.json")
        with open(fn, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ASSIGNED_ARCHS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--opts", default="none",
                    help="'none' (paper-faithful baseline), 'all', or a "
                         "comma-list of repro.opt flags")
    args = ap.parse_args(argv)
    opt_flags.set_flags(**opt_flags.parse(args.opts))

    combos = []
    archs = list(ASSIGNED_ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ([False, True] if args.both_meshes
              else [bool(args.multi_pod)])
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    failures = 0
    for a, s, mp in combos:
        try:
            run_one(a, s, multi_pod=mp, out_dir=args.out,
                    remat=not args.no_remat, grad_accum=args.grad_accum)
        except Exception:
            failures += 1
            print(f"[dryrun] {a} x {s} x "
                  f"{'pod2x16x16' if mp else 'pod16x16'}: FAILED")
            traceback.print_exc()
    print(f"[dryrun] done: {len(combos) - failures}/{len(combos)} OK")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
