"""Chip smoke: the guarded training path and the serving path with failover,
run once on a TPU at granite-3-8b's published widths, cut in depth to one
layer so three copies of the train state fit one v5e chip's 16 GB.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: (2,2) mesh, resume on (4,1)

One chip, in order, each phase through the launchers a user calls:

  serve      ``repro.launch.serve.main``: 2 replicas, one killed mid-run;
             every request must still be served.
  train      ``repro.launch.train.run`` with every dependability tier on
             (async delta checkpoints, scrubber, sentinel) and an injected
             fail-stop: finishes ``done`` after exactly one restart, with a
             delta save and finite losses; a replay without the failure in a
             fresh directory ends at the bit-identical final loss.
  abft       the same step with ``--abft``: its step-1 loss agrees with the
             train phase's within LOSS_RTOL.
  reference  the step-1 loss of the same params and batch in float32 on the
             host CPU backend agrees with the chip's within LOSS_RTOL.

``--four-chips`` runs only the mesh path: train on (data 2, model 2) with a
fail-stop, resume its last checkpoint on (data 4, model 1), and compare each
step's loss with the same steps on one chip.

Times printed here are host-clock times of a few steps: a smoke, not a
benchmark.  Any failed check raises.  On success the last line of stdout is
one JSON object naming the device; without a TPU the script exits non-zero
and prints no such line.
"""
from __future__ import annotations

import argparse
import gc
import importlib.metadata
import json
import math
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the CPU reference needs the host backend beside the TPU
_plats = os.environ.get("JAX_PLATFORMS", "")
if _plats and "cpu" not in _plats.split(","):
    os.environ["JAX_PLATFORMS"] = _plats + ",cpu"

from repro.launch.common import use_compile_cache  # noqa: E402

import jax  # noqa: E402

ARCH = "granite-3-8b"
LAYERS = 1                 # 3 x 4.57 GiB of fp32 state + AdamW moments
SEQ, BATCH = 512, 4        # 2048 tokens a step; divides a 4-way data axis
LOSS_RTOL = 1e-2           # bf16 compute (8-bit mantissa) vs other paths
FAILSTOP_AT = 5            # after the delta save at step 4
TRAIN_STEPS = 5
RESUME_STEPS = 7           # --four-chips: the (4,1) resume runs to here


def smoke_config():
    """The model the launchers build from ``--arch ARCH --layers LAYERS``."""
    from repro.launch.common import model_config
    return model_config(argparse.Namespace(arch=ARCH, tiny=False,
                                           layers=LAYERS))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")
    print(f"  ok: {what}", flush=True)


def memory(label: str) -> None:
    gc.collect()
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        print(f"  [{label}] device {d.id}: bytes_in_use="
              f"{st.get('bytes_in_use')} peak_bytes_in_use (process so far)="
              f"{st.get('peak_bytes_in_use')} bytes_limit="
              f"{st.get('bytes_limit')}", flush=True)


GUARDED = ("--policy", "every_n", "--every-n", "2", "--async-save",
           "--delta-checkpoint", "--scrub", "--sentinel")


def losses(out: dict) -> dict:
    """step -> loss of the run's last pass over each step."""
    return {r["step"]: r["loss"] for r in out["steps"]}


def train(label: str, ckpt_dir: str, steps: int, *extra: str) -> dict:
    from repro.launch import train as launch_train
    print(f"== {label}: train {' '.join(extra)}", flush=True)
    out = launch_train.run([
        "--arch", ARCH, "--layers", str(LAYERS), "--seq-len", str(SEQ),
        "--global-batch", str(BATCH), "--steps", str(steps),
        "--ckpt-dir", ckpt_dir, *extra])
    ms = [f"{r['step']}:{r['seconds'] * 1e3:.1f}" for r in out["steps"]]
    print(f"  [{label}] smoke timing, not a benchmark: step:ms in the order "
          f"run, the first including its compile: {' '.join(ms)}",
          flush=True)
    return out


def check_guarded_run(out: dict) -> None:
    check(out["status"] == "done", f"status {out['status']!r} is 'done'")
    check(out["restarts"] == 1, f"restarts={out['restarts']} == 1")
    check(out["delta_saves"] >= 1,
          f"{out['delta_saves']} delta save(s) of {out['saves']}")
    check(all(math.isfinite(v) for v in losses(out).values()),
          "all losses finite")


def check_block_hash_lowering() -> None:
    """The delta save hashed each large state leaf through
    ``_batched_block_hashes``; its program for those shapes must be the
    Mosaic kernel, not the jnp twin."""
    from repro.core.checkpoint import _DELTA_MIN_ELEMS
    from repro.kernels.block_hash import ops
    from repro.train import init_state
    tmpl = jax.eval_shape(
        lambda: init_state(smoke_config(), jax.random.PRNGKey(0)))
    leaves = [x for x in jax.tree.leaves(tmpl) if x.size >= _DELTA_MIN_ELEMS]
    use_kernel = ops._default_use_kernel()
    text = ops._batched_block_hashes.lower(
        leaves, block_elems=ops.BLOCK_ELEMS, use_kernel=use_kernel,
        interpret=False).compile().as_text()
    check(use_kernel and "tpu_custom_call" in text,
          f"block_hash over {len(leaves)} leaves lowered to a Mosaic "
          f"tpu_custom_call")


def cpu_reference_loss() -> float:
    """Step-1 loss of the seed's params on the first batch, float32 on the
    host CPU backend."""
    import dataclasses

    import jax.numpy as jnp

    from repro.data import make_pipeline
    from repro.train import init_state, loss_fn
    cfg = dataclasses.replace(smoke_config(), dtype=jnp.float32)
    with jax.default_device(jax.devices("cpu")[0]):
        params = jax.jit(lambda: init_state(cfg, jax.random.PRNGKey(0))
                         ["params"])()
        batch = make_pipeline(cfg, SEQ, BATCH, seed=0).peek_batch(0)
        loss, _ = jax.jit(lambda p, b: loss_fn(cfg, p, b))(params, batch)
        return float(loss)


def close(a: float, b: float) -> bool:
    return abs(a - b) <= LOSS_RTOL * abs(b)


def one_chip() -> None:
    from repro.launch import serve as launch_serve

    print("== serve: 2 replicas, replica 1 killed at engine step 6",
          flush=True)
    rc = launch_serve.main(["--arch", ARCH, "--layers", str(LAYERS),
                            "--requests", "6", "--prompt-len", "320",
                            "--gen", "16", "--replicas", "2",
                            "--fault-tolerant", "--kill-replica-at", "6"])
    check(rc == 0, f"serve returned {rc}: every request served after the "
                   f"replica kill")
    memory("after serve")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as d:
        out = train("train", d, TRAIN_STEPS, *GUARDED,
                    "--inject-failure", str(FAILSTOP_AT))
    check_guarded_run(out)
    check_block_hash_lowering()
    memory("after train")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as d:
        replay = train("replay", d, TRAIN_STEPS, *GUARDED)
    check(replay["status"] == "done" and replay["restarts"] == 0,
          "replay finished done with no restart")
    last, last_replay = losses(out)[TRAIN_STEPS], losses(replay)[TRAIN_STEPS]
    print(f"  final loss: with fail-stop {last!r}, replay {last_replay!r}, "
          f"difference {last - last_replay!r}")
    check(last == last_replay, "replay final loss is bit-identical")
    memory("after replay")

    chip_loss = losses(out)[1]
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as d:
        abft = train("abft", d, 2, "--abft", "--policy", "every_n",
                     "--every-n", "1000")
    abft_loss = losses(abft)[1]
    print(f"  step-1 loss: abft {abft_loss!r}, plain {chip_loss!r}, "
          f"difference {abft_loss - chip_loss!r}")
    check(close(abft_loss, chip_loss),
          f"abft step-1 loss within rtol {LOSS_RTOL} of the plain step")
    memory("after abft")

    print("== reference: float32 step-1 loss on the host CPU", flush=True)
    ref = cpu_reference_loss()
    print(f"  step-1 loss: chip {chip_loss!r}, cpu float32 {ref!r}, "
          f"difference {chip_loss - ref!r}")
    check(close(chip_loss, ref),
          f"chip step-1 loss within rtol {LOSS_RTOL} of the CPU reference")


def four_chips() -> None:
    n = len(jax.devices())
    check(n >= 4, f"{n} devices for the four-chip path")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as d:
        mesh22 = train("mesh (2,2)", d, TRAIN_STEPS, *GUARDED,
                       "--data-par", "2", "--model-par", "2",
                       "--inject-failure", str(FAILSTOP_AT))
        check_guarded_run(mesh22)
        memory("after (2,2)")
        mesh41 = train("resume on (4,1)", d, RESUME_STEPS, *GUARDED,
                       "--data-par", "4", "--model-par", "1")
        check(mesh41["status"] == "done", "resume on (4,1) finished done")
        check(min(losses(mesh41)) == TRAIN_STEPS,
              f"resume picked up after step {TRAIN_STEPS - 1}")
        memory("after (4,1)")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as d:
        single = train("one chip", d, RESUME_STEPS, *GUARDED)
    got = {**losses(mesh22), **losses(mesh41)}
    want = losses(single)
    check(sorted(got) == sorted(want) == list(range(1, RESUME_STEPS + 1)),
          f"steps 1..{RESUME_STEPS} on both layouts")
    for s in sorted(want):
        print(f"  step {s}: meshes {got[s]!r}, one chip {want[s]!r}, "
              f"difference {got[s] - want[s]!r}")
    check(all(close(got[s], want[s]) for s in want),
          f"per-step losses within rtol {LOSS_RTOL} of one chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh and resume path")
    args = ap.parse_args()

    cache = use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); refusing "
              f"to run", file=sys.stderr)
        return 2
    import jaxlib
    print(f"device_kind={dev.device_kind!r} count={len(jax.devices())} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"compile_cache={cache}", flush=True)
    free = shutil.disk_usage(tempfile.gettempdir()).free
    print(f"checkpoints under {tempfile.gettempdir()} ({free} bytes free)",
          flush=True)
    memory("start")
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    memory("end")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
