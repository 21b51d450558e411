"""Look at traces by hand before reading them in code.

    python bench/tools/trace_ops.py --workload <cell> --seed <n> \
        --seconds <s> --keep <dir>
    python bench/tools/trace_ops.py --small <path.xplane.pb>

The first form makes one traced run of a cell, keeps its ``.xplane.pb``
under ``--keep``, and prints every plane and line of the trace with its
number of events and the ops that took most device time.  The second
records a small trace of a few jitted steps inside a ``bench.window``
span, of the kind ``bench/tests/test_trace.py`` reduces.
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def describe(path: str, top: int = 40) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("plane", plane.name)
        for ln in plane.lines:
            evs = list(ln.events)
            tot = defaultdict(float)
            for e in evs:
                tot[e.name] += e.duration_ns * 1e-9
            best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
            print(f"  line {ln.name!r}: {len(evs)} events")
            for name, s in best:
                print(f"    {s:12.6f} s  {name[:160]}")


def small(path: str) -> None:
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    d = tempfile.mkdtemp(prefix="small_trace_")
    jax.profiler.start_trace(d)
    w = jax.profiler.TraceAnnotation("bench.window")
    w.__enter__()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            x = f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host"):
            sum(range(200000))
    w.__exit__(None, None, None)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    shutil.copy(src, path)
    shutil.rmtree(d, ignore_errors=True)
    describe(path)


def cell(workload: str, seed: int, seconds: float, keep: str) -> None:
    from bench import run

    class Keep(run.Tracer):
        def reduce(self):
            src = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                            recursive=True)[0]
            os.makedirs(keep, exist_ok=True)
            dst = os.path.join(keep, f"{workload}.{seed}.xplane.pb")
            shutil.copy(src, dst)
            describe(dst)
            return super().reduce()

    run.Tracer = Keep
    res = run.run_cell(workload, seed, seconds, True)
    print({k: res[k] for k in ("correct", "metrics", "device", "breakdown",
                               "checks")})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--keep", default="traces")
    ap.add_argument("--small")
    args = ap.parse_args(argv)
    if args.small:
        small(args.small)
    else:
        cell(args.workload, args.seed, args.seconds, args.keep)
    return 0


if __name__ == "__main__":
    sys.exit(main())
