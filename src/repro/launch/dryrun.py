"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh).

MUST be imported/run before any other jax usage: the first two lines pin the
placeholder device count for the production meshes (dry-run ONLY — smoke
tests and benches see the real single CPU device).
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import (ALL_ARCHS, ASSIGNED_ARCHS, INPUT_SHAPES, DPConfig,
                           InputShape, ModelConfig, get_config)
from repro.launch import steps as ST
from repro.launch.mesh import make_production_mesh, mesh_config
from repro.models import build
from repro.sharding import specs as SP

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

FULL_ATTN_FAMILIES = ("dense", "moe", "vlm", "encdec")
LONG_WINDOW = 4096

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def arch_for_shape(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """long_500k requires sub-quadratic attention: full-attention families
    switch to the sliding-window variant (window 4096). SSM runs natively;
    the hybrid's shared-attention KV stays exact (DESIGN.md §4)."""
    if shape.name == "long_500k" and cfg.family in FULL_ATTN_FAMILIES:
        return cfg.with_(attn_window=LONG_WINDOW)
    return cfg


def _shape_bytes(stype: str) -> int:
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", stype)
    if not m:
        return 0
    dt, dims = m.groups()
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_stats(hlo_text: str):
    """Sum result bytes of every collective op in the optimized HLO."""
    stats = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    pat = re.compile(
        r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^ ]*))\s+"
        r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
        r"(?:-start)?\(")
    for m in pat.finditer(hlo_text):
        stype, op = m.groups()
        total = sum(_shape_bytes(s)
                    for s in re.findall(r"[a-z0-9]+\[[0-9,]*\]", stype))
        stats[op]["count"] += 1
        stats[op]["bytes"] += total
    stats["total_bytes"] = sum(v["bytes"] for k, v in stats.items()
                               if isinstance(v, dict))
    return stats


def count_params(params_sh) -> int:
    return sum(int(l.size if hasattr(l, "size") else 0)
               for l in jax.tree_util.tree_leaves(params_sh))


def dryrun_one(arch: str, shape_name: str, multi_pod: bool,
               save: bool = True, verbose: bool = True):
    shape = INPUT_SHAPES[shape_name]
    cfg = arch_for_shape(get_config(arch), shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mcfg = mesh_config(multi_pod=multi_pod)
    model = build(cfg)
    t0 = time.time()

    params_sh = ST.params_shape(model)
    pspecs = SP.param_specs(params_sh, cfg, mcfg)
    inputs = ST.input_specs(cfg, shape)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_sh = ST.opt_state_shape(params_sh)
            fn = ST.make_fed_train_step(model, DPConfig(
                clients_per_round=shape.global_batch), mesh, mcfg, pspecs,
                shape, donate=True)
            key_sh = jax.ShapeDtypeStruct((2,), jnp.uint32)
            lowered = fn.lower(params_sh, opt_sh, inputs, key_sh)
        elif shape.kind == "prefill":
            fn = ST.make_prefill_step(model, mesh, mcfg, pspecs, shape)
            lowered = fn.lower(params_sh, inputs)
        else:  # decode
            fn = ST.make_decode_step(model, mesh, mcfg, pspecs, shape,
                                     donate=True)
            cache_sh = ST.cache_shape(model, shape)
            lowered = fn.lower(params_sh, inputs["tokens"], cache_sh)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "n_devices": mesh.devices.size,
           "n_params": count_params(params_sh),
           "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1)}

    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(ma, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(ma, k)}
    except Exception as e:  # pragma: no cover
        rec["memory"] = {"error": str(e)}

    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        rec["cost"] = {k: float(v) for k, v in ca.items()
                       if isinstance(v, (int, float)) and
                       k in ("flops", "bytes accessed", "transcendentals",
                             "bytes accessed output", "optimal_seconds")}
    except Exception as e:  # pragma: no cover
        rec["cost"] = {"error": str(e)}

    try:
        rec["collectives"] = collective_stats(compiled.as_text())
    except Exception as e:  # pragma: no cover
        rec["collectives"] = {"error": str(e)}

    if save:
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        out = RESULTS_DIR / f"{arch}__{shape_name}__{rec['mesh']}.json"
        out.write_text(json.dumps(rec, indent=1))
    if verbose:
        flops = rec.get("cost", {}).get("flops", 0)
        cb = rec.get("collectives", {}).get("total_bytes", 0)
        print(f"[dryrun] {arch:22s} {shape_name:12s} {rec['mesh']:8s} "
              f"compile={rec['compile_s']:6.1f}s flops={flops:.3e} "
              f"coll={cb/1e9:.2f}GB", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id (default: all)")
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--include-paper-model", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ASSIGNED_ARCHS)
    if args.include_paper_model and "gboard-cifg-lstm" not in archs:
        archs.append("gboard-cifg-lstm")
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    dryrun_one(arch, shape, mp)
                except Exception as e:
                    failures.append((arch, shape, mp, repr(e)))
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f[:3], f[3][:200])
        raise SystemExit(1)
    print("\nall dry-runs passed")


if __name__ == "__main__":
    main()
