"""Run one cell several times, each run a fresh process as the check runs
it, and print each run's numbers and the spread of each metric.

    python bench/tools/runs.py --workload <cell> --seeds 11 12 13 \
        --seconds 45 [--trace 0|1] [--out runs.jsonl]

The parent never imports JAX, so each child has the chip to itself.  A
spread is the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from bench.common import quartile_spread  # noqa: E402  (imports no JAX)


def one(workload: str, seed: int, seconds: float, trace: int):
    cmd = [sys.executable, os.path.join(ROOT, "bench", "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    t = time.perf_counter()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t
    lines = p.stdout.strip().splitlines()
    try:
        res = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        res = None
    tail = [ln for ln in p.stderr.splitlines() if ln.startswith("bench")]
    return {"workload": workload, "seed": seed, "trace": trace,
            "rc": p.returncode, "wall_s": wall, "result": res,
            "stderr_bench": tail,
            "stderr_tail": p.stderr[-3000:] if p.returncode else ""}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    recs = []
    for s in args.seeds:
        r = one(args.workload, s, args.seconds, args.trace)
        recs.append(r)
        res = r["result"] or {}
        print(json.dumps({"seed": s, "rc": r["rc"],
                          "wall_s": round(r["wall_s"], 1),
                          "correct": res.get("correct"),
                          "metrics": {k: v["value"] for k, v in
                                      res.get("metrics", {}).items()},
                          "checks": {k: v["value"] for k, v in
                                     res.get("checks", {}).items()},
                          "device": res.get("device")}), flush=True)
        for ln in r["stderr_bench"]:
            print("   ", ln, flush=True)
        if r["rc"]:
            print(r["stderr_tail"], flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(r) + "\n")
    names = sorted({k for r in recs if r["result"]
                    for k in r["result"].get("metrics", {})})
    for k in names:
        vals = [r["result"]["metrics"][k]["value"] for r in recs
                if r["result"] and k in r["result"]["metrics"]]
        print(f"{args.workload} {k}: n={len(vals)} median="
              f"{statistics.median(vals)!r} spread="
              f"{quartile_spread(vals) if len(vals) > 1 else None!r}")
    return 0 if all(r["rc"] == 0 for r in recs) else 1


if __name__ == "__main__":
    sys.exit(main())
