"""Run one benchmark cell once, on the chip, and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``:

- the cell's configuration file (``configs[].file``) and its traffic mix,
  ``bench/traffic/<traffic>.json``, whose ``driver`` names the module
  under ``bench/drivers/`` that runs it;
- the cell's limits, ``bench/cells/<cell>.json``;
- each per-layer metric's reader, ``bench/metrics/<metric>.py``.

With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the window is traced and the result holds its per-layer
metrics, the device's busy time and a breakdown.  Either way the run
checks what the timed path produced against the plain reference, prints
each number compared beside its limit as the last lines of standard
error, and prints the result as one JSON line last on standard output.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.common import (CompileLog, checks_line,  # noqa: E402
                          device_record, load_json, mark,
                          use_checkout_cache)


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


class Tracer:
    """Starts and stops the profiler around the window (``--trace 1``);
    does nothing otherwise."""

    def __init__(self, on: bool):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if on else None

    def start(self):
        if self.dir:
            import jax
            # no Python tracer: it records every Python call, which slows
            # the host-bound loops under test several times over
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        if self.dir:
            import jax
            jax.profiler.stop_trace()

    def reduce(self):
        from bench import trace
        try:
            return trace.reduce(trace.load(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_context(name: str, bench: dict, given: dict = None) -> dict:
    """The cell's entry, configuration, traffic mix and limits, each read
    from its file unless ``given`` holds it."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    ctx = dict(given or {}, cell=cells[name])
    if "config" not in ctx:
        conf = {c["name"]: c for c in bench["configs"]}[ctx["cell"]["config"]]
        with open(os.path.join(ROOT, conf["file"])) as f:
            ctx["config"] = json.load(f)
    if "traffic" not in ctx:
        ctx["traffic"] = load_json("traffic", ctx["cell"]["traffic"] + ".json")
    if "limits" not in ctx:
        ctx["limits"] = load_json("cells", name + ".json")["limits"]
    return ctx


def metrics_for(cell: str, entries: list) -> list:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def read_metric(name: str, view: dict):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(view)


def require_chips(n: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips; JAX found {len(devs)}")


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             bench: dict = None, ctx_overrides: dict = None,
             require_chip: bool = True) -> dict:
    """One run; returns the result object that ``main`` prints.
    ``bench`` stands in for ``BENCHMARK.json`` (tests)."""
    bench = bench or manifest()
    ctx = cell_context(name, bench, ctx_overrides)
    import jax  # noqa: F401
    mark(ctx, "jax_imported")
    if require_chip:
        require_chips(ctx["cell"]["chips"])
        use_checkout_cache()
    mark(ctx, "backend_ready")
    driver = importlib.import_module(
        "bench.drivers." + ctx["traffic"]["driver"])
    tracer = Tracer(trace)
    ctx.update(seed=seed, seconds=seconds, tracer=tracer)
    mark(ctx, "driver_start")
    with CompileLog() as compiles:
        out = driver.run(ctx)
    out["e2e"]["setup_s"] = out["window"][0] - T_START
    checks = driver.check(ctx, out)
    correct = out["failed"] == 0 and all(v <= lim
                                         for v, lim in checks.values())
    device = dict(device_record(),
                  memory_peak_bytes=ctx.get("memory_peak_bytes"))
    info = dict(out.get("info", {}), setup_marks_s={
        k: (round(t - T_START, 3), round(cpu, 3))
        for k, (t, cpu) in ctx["marks"].items()},
        compiles_setup=compiles.totals(0.0, out["window"][0]),
        compiles_window=compiles.totals(*out["window"]))
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"], "info": info}
    if trace:
        red = tracer.reduce()
        view = dict(out, trace=red, config=ctx["config"],
                    traffic=ctx["traffic"], device=device)
        metrics = {}
        for m in metrics_for(name, bench["per_layer"]):
            v = read_metric(m["name"], view)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        metrics = {m["name"]: {"value": float(out["e2e"][m["name"]]),
                               "unit": m["unit"]}
                   for m in metrics_for(name, bench["end_to_end"])}
    result.update(metrics=metrics, device=device,
                  checks={k: {"value": float(v), "limit": lim}
                          for k, (v, lim) in checks.items()})
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 2
    checks = {k: (c["value"], c["limit"])
              for k, c in result["checks"].items()}
    for k, v in result.pop("info", {}).items():
        print(f"bench info: {k}={v!r}", file=sys.stderr)
    print(f"bench: correct={result['correct']} failed={result['failed']} "
          f"of {result['attempted']}", file=sys.stderr)
    print("bench checks: " + checks_line(checks), file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
