"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b \
        --shape train_4k [--multi-pod] [--all] [--out out.json]

For each cell this proves the distribution config is coherent (sharding
propagates, collectives legal, memory fits) and records
``compiled.memory_analysis()`` + ``compiled.cost_analysis()`` for the
roofline (EXPERIMENTS.md S Dry-run / S Roofline).
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks the device count on first init.

import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs import ALL_ARCHS
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, cell_applicable, input_specs
from repro.models import get_config
from repro.sharding.api import mesh_context
from repro.train import make_decode_step, make_prefill_step, make_train_step


# Gradient-accumulation defaults per arch for train_4k: keeps live
# activations under the 16 GB v5e HBM budget (measured via memory_analysis;
# the heavy archs additionally run with seq_shard=True — see configs).
DEFAULT_MICROBATCHES = {"qwen1.5-110b": 16, "gemma2-27b": 8,
                        "recurrentgemma-2b": 8}
FALLBACK_MICROBATCHES = 4


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               impl: Optional[str] = None, microbatches: Optional[int] = None,
               moe_ep: bool = False, cfg_overrides: Optional[Dict] = None,
               donate: bool = True):
    """Returns (lowered, compiled, meta) for one cell."""
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mode, args, out_sh = input_specs(cfg, shape_name, mesh, moe_ep)
    seq, batch, _ = SHAPES[shape_name]
    if microbatches is None:
        microbatches = DEFAULT_MICROBATCHES.get(arch, FALLBACK_MICROBATCHES) \
            if mode == "train" else 1
    if mode == "train":
        # per-microbatch batch must stay shardable over the DP width
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp = sizes.get("data", 1) * sizes.get("pod", 1)
        while microbatches > 1 and (batch // microbatches) % dp:
            microbatches //= 2

    with mesh_context(mesh):
        if mode == "train":
            from repro.sharding.rules import state_specs
            tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
            pspecs = state_specs(cfg, tp, moe_ep)["params"]
            fn = make_train_step(cfg, impl=impl, microbatches=microbatches,
                                 param_specs=pspecs)
            jfn = jax.jit(fn, out_shardings=out_sh,
                          donate_argnums=(0,) if donate else ())
        elif mode == "prefill":
            fn = make_prefill_step(cfg, impl=impl)
            jfn = jax.jit(fn, out_shardings=out_sh)
        else:
            fn = make_decode_step(cfg, impl=impl)
            jfn = jax.jit(fn, out_shardings=out_sh,
                          donate_argnums=(2,) if donate else ())
        t0 = time.perf_counter()
        lowered = jfn.lower(*args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    meta = {
        "arch": arch,
        "shape": shape_name,
        "mode": mode,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "seq": seq,
        "batch": batch,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "microbatches": microbatches,
    }
    return lowered, compiled, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True) -> Dict:
    cfg = get_config(arch)
    ok, reason = cell_applicable(cfg, shape_name)
    if not ok:
        rec = {"arch": arch, "shape": shape_name,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "status": "skip", "reason": reason}
        if verbose:
            print(f"[skip] {arch} x {shape_name}: {reason}", flush=True)
        return rec
    try:
        lowered, compiled, meta = lower_cell(arch, shape_name,
                                             multi_pod=multi_pod)
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        rec = {
            **meta,
            "status": "ok",
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_per_device_bytes": (mem.argument_size_in_bytes
                                          + mem.temp_size_in_bytes),
            },
            "cost": {
                "flops_per_device": cost.get("flops", 0.0),
                "bytes_per_device": cost.get("bytes accessed", 0.0),
            },
        }
        if verbose:
            gb = rec["memory"]["peak_per_device_bytes"] / 2**30
            print(f"[ok]   {arch} x {shape_name} ({rec['mesh']}): "
                  f"compile={meta['compile_s']}s "
                  f"peak/dev={gb:.2f}GiB "
                  f"flops/dev={rec['cost']['flops_per_device']:.3e}",
                  flush=True)
        return rec
    except Exception as e:  # a failure here is a bug in the system
        if verbose:
            print(f"[FAIL] {arch} x {shape_name}: {e}", flush=True)
            traceback.print_exc()
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "fail", "error": str(e)[:2000]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ALL_ARCHS) + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) cell")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args(argv)

    archs = list(ALL_ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else \
        [args.multi_pod]

    records = []
    failed = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp)
                records.append(rec)
                failed += rec["status"] == "fail"
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")
    print(f"\n{len(records)} cells: "
          f"{sum(r['status'] == 'ok' for r in records)} ok, "
          f"{sum(r['status'] == 'skip' for r in records)} skip, "
          f"{failed} fail")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
