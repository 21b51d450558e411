"""Readings that set a training cell's limits: the control and the
faults, at the cell's own size, on several seeds in one process.

    python bench/tools/controls.py --workload <cell> --seeds 1 2 3

The reference's first three steps in float32, computed again with every
matrix product in float8 (the control: the precision below the bfloat16
the configurations state) and with half of each batch left out, the mean
taken over the rest (a fault); each compared with the float32 steps as a
run is.  A state left unchanged reads 1 by that measure and needs no run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def train(ctx, seed):
    from bench import reference
    from bench.common import program_seed
    from bench.reference import train as rtrain
    conf, traffic = ctx["config"], ctx["traffic"]
    ref = reference.load(conf)
    m, opt = ref.dims(conf), traffic["optimizer"]
    rows = traffic["rows_per_data_replica"] * conf["mesh"]["data"]
    s = program_seed(seed)
    want = rtrain.readings(ref, m, opt, s, rows, traffic["seq_len"])
    out = {}
    for label, kw in (("control_fp8", {"prec": "fp8"}),
                      ("fault_half_batch", {"rows_used": rows // 2})):
        got = rtrain.readings(ref, m, opt, s, rows, traffic["seq_len"], **kw)
        out[label] = rtrain.compare(got, want)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench.common import use_checkout_cache
    from bench.run import cell_context, manifest, require_chips
    ctx = cell_context(args.workload, manifest())
    require_chips(ctx["cell"]["chips"])
    use_checkout_cache()
    for seed in args.seeds:
        got = train(ctx, seed)
        print(json.dumps({"workload": args.workload, "seed": seed, **got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
