"""Elastic 3D reshard latency: dp-only vs tp-repartition vs expert-drop.

Times ``reshard_state`` — restore the latest checkpoint onto a DIFFERENT
mesh — for each of the three degradation paths the 3D refactor added
(docs/elastic.md "3D meshes"), against the pre-refactor 2D baseline:

  - ``baseline_2d``: (2, 2) "data"/"model" survivor mesh, the path every
    PR up to the 3D refactor shipped;
  - ``dp_only``:  (2,2,2) -> (1,2,2) — batch axis shrinks, tp/ep intact
    (the 3D equivalent of the baseline; MUST NOT be slower);
  - ``tp_repartition``: (2,2,2) -> (2,1,2) — every "model"-sharded leaf
    is re-partitioned (concat across the old tp group);
  - ``expert_drop``: (2,2,2) -> (2,2,1) — the expert axis folds away
    (params keep full shapes; the router masks the dead experts).

Two state sizes show the scaling.  Needs 8 host devices, so the
measurement runs in a child process with XLA_FLAGS set (the parent —
``benchmarks/run.py`` — keeps the default single device).  The child is
pinned to the CPU backend: the parent may already hold the chip, and these
are host-device timings, not chip numbers.  Emits
machine-readable ``BENCH_elastic.json`` (override: BENCH_ELASTIC_JSON).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
REPEATS = 5


def write_json(results: Dict[str, float],
               path: str = "BENCH_elastic.json") -> str:
    path = os.environ.get("BENCH_ELASTIC_JSON", path)
    with open(path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    return path


def _worker() -> None:
    import dataclasses
    import tempfile
    import time

    import jax
    import numpy as np

    from repro.core import (CheckpointManager, MeshSpec, reshard_state,
                            survivor_mesh, survivor_mesh3d)
    from repro.models import get_config
    from repro.train import init_state

    key = jax.random.PRNGKey(0)
    tiny = get_config("mixtral-8x7b", tiny=True)
    bigger = dataclasses.replace(tiny, name="mixtral-8x7b-tiny-x4",
                                 d_model=128, d_ff=256, num_layers=4)
    results: Dict[str, float] = {}

    for label, cfg in (("tiny", tiny), ("x4", bigger)):
        state = init_state(cfg, key)
        size_mb = sum(np.prod(l.shape) * l.dtype.itemsize
                      for l in jax.tree.leaves(state)) / 2 ** 20
        like = jax.eval_shape(lambda c=cfg: init_state(c, key))
        with tempfile.TemporaryDirectory() as d:
            manager = CheckpointManager(d)
            manager.save(0, state, blocking=True)

            devices = jax.devices()
            targets = {
                "baseline_2d": (survivor_mesh(devices[:4], model_axis=2),
                                False),
                "dp_only": (survivor_mesh3d(
                    devices[:4], MeshSpec.from_config(
                        cfg, data=1, model=2, expert=2)), None),
                "tp_repartition": (survivor_mesh3d(
                    devices[:4], MeshSpec.from_config(
                        cfg, data=2, model=1, expert=2)), None),
                "expert_drop": (survivor_mesh3d(
                    devices[:4], MeshSpec.from_config(
                        cfg, data=2, model=2, expert=1)), None),
            }
            for path_name, (mesh, moe_ep) in targets.items():
                best = float("inf")
                for _ in range(REPEATS):
                    t0 = time.perf_counter()
                    out, _local, _step = reshard_state(manager, cfg, mesh,
                                                       like, moe_ep=moe_ep)
                    jax.block_until_ready(out)
                    best = min(best, time.perf_counter() - t0)
                results[f"{label}.{path_name}_ms"] = round(best * 1e3, 3)
            manager.close()
        results[f"{label}.state_mb"] = round(size_mb, 3)

        base = results[f"{label}.baseline_2d_ms"]
        dp = results[f"{label}.dp_only_ms"]
        results[f"{label}.dp_vs_baseline"] = round(dp / base, 3)
        print(f"{label:5s} state {size_mb:6.2f} MB, 8 CPU host devices: "
              f"2d={base:.1f}ms dp={dp:.1f}ms "
              f"tp={results[f'{label}.tp_repartition_ms']:.1f}ms "
              f"ep={results[f'{label}.expert_drop_ms']:.1f}ms "
              f"(dp/2d={results[f'{label}.dp_vs_baseline']:.2f}x)")
    path = write_json(results)
    print(f"(machine-readable results: {path})")


def main() -> List[str]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    p = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--worker"], env=env, capture_output=True, text=True)
    sys.stdout.write(p.stdout)
    if p.returncode != 0:
        raise RuntimeError(f"bench_elastic worker failed:\n{p.stderr}")

    path = os.environ.get("BENCH_ELASTIC_JSON", "BENCH_elastic.json")
    with open(path) as f:
        results = json.load(f)
    rows = [f"elastic_reshard_{k.replace('.', '_')},{v * 1e3:.1f},cpu"
            for k, v in sorted(results.items()) if k.endswith("_ms")]
    # acceptance: the dp-only path must not regress vs the 2D baseline
    # (x2 tolerance absorbs timer noise on ~ms restores)
    for label in ("tiny", "x4"):
        ratio = results[f"{label}.dp_vs_baseline"]
        if ratio > 2.0:
            raise AssertionError(
                f"dp-only reshard regressed vs the 2D baseline on {label}: "
                f"{ratio:.2f}x")
    return rows


if __name__ == "__main__":
    if "--worker" in sys.argv:
        _worker()
    else:
        main()
