"""Training driver: a training job as the training launcher builds it
(``Dependability`` + ``make_train_step`` + ``run_with_recovery``), with the
traffic file's guard settings and at most one checkpoint save a window.

Set-up builds the one job the window runs: weights and optimiser state
made on the device from the seed in one jitted call, the jitted step, and
the dependability facade.  The job runs from step 1 through
``run_with_recovery``.  Its first three steps give the readings the
reference checks.  Where the scrubber is on, set-up runs until it has
checksummed every rotation of its leaf subsets once, since each subset is
a program of its own.

The window opens at that step boundary and closes at the first step
boundary once ``--seconds`` have passed; the feed then refuses the next
batch, which ends the job with no final save.  Where the mix sets
``window_save``, the window opens with an asynchronous save, forced
through ``run_with_recovery``'s own hook for saves out of cadence
(``proactive``): its snapshot on the training thread and its write in the
background, beside the steps.  That save is waited for, restored, and
compared bit for bit with the state it saved.
"""
from __future__ import annotations

import math
import shutil
import tempfile
import time
from typing import Dict

import numpy as np

from bench import reference
from bench.common import (Spans, as_run, mark, memory_peak_bytes,
                          program_seed)


class WindowClosed(Exception):
    """Raised by the feed at the first batch after the window closed."""


class Feed:
    """The batches ``run_with_recovery`` reads; the data pipeline itself
    stays registered with the facade as the job's local state."""

    def __init__(self, data):
        self.data = data
        self.closed = False

    def next_batch(self):
        if self.closed:
            raise WindowClosed
        return self.data.next_batch()


def model_config(conf: Dict):
    """The program's ModelConfig for a configuration file: the registered
    architecture with the values the reference module's ``FIELDS`` table
    sets from the file (depth, epsilon, a tied or untied head), and every
    value it checks (each width) equal to the file's."""
    import dataclasses

    from repro.models import get_config
    c = as_run(conf)
    table = reference.load(conf).FIELDS[c["family"]]
    missing = sorted(k for k, f in table.items()
                     if f.derive is None and k not in c)
    if missing:
        raise ValueError(f"{c['name']}: the file lacks {missing}")
    cfg = get_config(c["model"], tiny=c.get("tiny", False))
    cfg = dataclasses.replace(cfg, **{f.attr: f.value(c, k)
                                      for k, f in table.items()
                                      if f.action == reference.SET})
    differ = [f"{k}: the file's {f.value(c, k)!r}, the program's "
              f"{f.attr} {getattr(cfg, f.attr)!r}"
              for k, f in table.items() if f.action == reference.CHECK
              and getattr(cfg, f.attr) != f.value(c, k)]
    if differ:
        raise ValueError(f"{c['name']}: the program does not run the "
                         f"file's sizes: " + "; ".join(differ))
    return cfg


def scrub_period(n_leaves: int, fraction: float) -> int:
    """Steps until the scrubber's rotating subsets repeat: it checksums
    ``ceil(n * fraction)`` leaves a step and advances its cursor by as
    many, modulo ``n``."""
    k = max(1, math.ceil(n_leaves * fraction))
    return n_leaves // math.gcd(n_leaves, k)


def _layer_leaves(layer, prefix: str, out: Dict) -> None:
    """Each array of one layer's subtree under ``prefix`` and its own key,
    as the reference names its layers' weights."""
    for k, v in layer.items():
        if isinstance(v, dict):
            _layer_leaves(v, prefix, out)
        elif prefix + k in out:
            raise ValueError(f"two leaves of one layer are named {k!r}")
        else:
            out[prefix + k] = v


def named_leaves(p, vocab: int) -> Dict:
    """A params-shaped tree of the program's, as {name: array}, named as
    the reference names its leaves (``bench/reference/train.py``): the
    embedding and an untied head cut to the file's ``vocab`` rows, and
    each layer's arrays as ``L<layer>.<key>``.  A scanned stack holds
    layer ``r * len(pattern) + p`` in row ``r`` of ``blocks.l<p>``; an
    unscanned one holds layer ``i`` in ``layers.layer_<i>``."""
    import jax
    out = {"embed": p["embed"]["tok"][:vocab],
           "final_norm": p["final_norm"]}
    if "lm_head" in p:
        out["head"] = p["lm_head"][:, :vocab]
    layers = {}
    if "blocks" in p:
        period = len(p["blocks"])
        for pos in range(period):
            block = p["blocks"][f"l{pos}"]
            for r in range(jax.tree.leaves(block)[0].shape[0]):
                layers[r * period + pos] = jax.tree.map(
                    lambda x, r=r: x[r], block)
    else:
        for name, layer in p["layers"].items():
            layers[int(name.removeprefix("layer_"))] = layer
    for i in sorted(layers):
        _layer_leaves(layers[i], f"L{i}.", out)
    return out


def _named_norms(vocab: int):
    """jitted: (tree, base, scale) -> {leaf name: norm of (tree - base) *
    scale} over params-shaped trees (``named_leaves``).  Inside one
    program, so no difference is held on the device."""
    import jax
    import jax.numpy as jnp

    def norms(tree, base, scale):
        a, b = named_leaves(tree, vocab), named_leaves(base, vocab)
        return {k: jnp.linalg.norm((a[k] - b[k]) * scale) for k in a}
    return jax.jit(norms)


def _bit_sums():
    """jitted: state -> per leaf, two uint32 sums of its storage words
    (plain and weighted by odd positions), so that two states' bits can be
    compared without holding both."""
    import jax
    import jax.numpy as jnp

    def sums(x):
        w = jax.lax.bitcast_convert_type(
            x.reshape(-1).astype(jnp.float32) if x.dtype.itemsize == 2
            else x.reshape(-1), jnp.uint32)
        odd = 2 * jnp.arange(w.size, dtype=jnp.uint32) + 1
        return jnp.stack([jnp.sum(w), jnp.sum(w * odd)])
    return jax.jit(lambda tree: jax.tree.map(sums, tree))


def run(ctx: Dict) -> Dict:
    """One run of a training cell; see ``bench/run.py`` for ``ctx``."""
    import jax

    from repro.core import (Dependability, DependabilityConfig,
                            run_with_recovery)
    from repro.data import make_pipeline
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.api import mesh_context, resolve
    from repro.sharding.rules import state_specs
    from repro.train import init_state, make_train_step

    mark(ctx, "program_imported")
    conf, traffic, spans = ctx["config"], ctx["traffic"], Spans()
    seed = program_seed(ctx["seed"])
    cfg = model_config(conf)
    dp, tp = conf["mesh"]["data"], conf["mesh"]["model"]
    seq, rows = traffic["seq_len"], traffic["rows_per_data_replica"] * dp
    guard, opt = dict(traffic["dependability"]), traffic["optimizer"]
    mesh = make_host_mesh(dp, tp)
    specs = state_specs(cfg, tp)
    shardings = jax.tree.map(lambda s: resolve(s, mesh), specs,
                             is_leaf=lambda x: x.__class__.__name__
                             == "PartitionSpec")
    data = make_pipeline(cfg, seq, rows, seed=seed)
    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    dep = Dependability(DependabilityConfig(
        checkpoint_dir=ckpt_dir, **guard)).start()
    dep.register_local_state(data)
    for attr, name in (("save", "ckpt.save"), ("verify_state", "sdc.verify"),
                       ("scrub", "sdc.scrub"),
                       ("check_metrics", "sdc.sentinel")):
        spans.wrap(dep, attr, name)

    st: Dict = {"n": 0, "open": None, "close": None, "steps": {},
                "readings": {"losses": []}}
    feed = Feed(data)
    tracer = ctx["tracer"]
    norms, bit_sums = _named_norms(conf["vocab_size"]), _bit_sums()
    try:
        with mesh_context(mesh):
            step_fn = jax.jit(
                make_train_step(cfg, peak_lr=opt["peak_lr"],
                                warmup_steps=opt["warmup_steps"],
                                total_steps=opt["total_steps"],
                                weight_decay=opt["weight_decay"],
                                clip_norm=opt["clip_norm"],
                                param_specs=specs["params"]),
                out_shardings=(shardings, None))
            template = jax.eval_shape(
                lambda: init_state(cfg, jax.random.PRNGKey(seed)))
            # the key is an argument, so one program serves every seed
            state = jax.jit(lambda k: init_state(cfg, k),
                            out_shardings=shardings)(jax.random.PRNGKey(seed))
            dep.register_global_state(template, shardings)
            checksummed = _count_checksummed(dep, template)
            mark(ctx, "state_made")
            params0 = state["params"]
            zero_m = state["opt"]["m"]     # the moments start at zero
            b1 = opt["b1"]
            setup_steps = 3
            if guard.get("scrub"):
                setup_steps = max(3, scrub_period(
                    len(jax.tree.leaves(template)), guard["scrub_fraction"]))
            window_save = traffic["window_save"]

            def step(s, batch):
                with spans.span("bench.train_step"):
                    out, metrics = step_fn(s, batch)
                st["n"] += 1
                if st["n"] == 1:           # m after one step = (1-b1) g
                    st["readings"]["grad"] = jax.device_get(norms(
                        out["opt"]["m"], zero_m, 1.0 / (1.0 - b1)))
                elif st["n"] == 3:
                    st["readings"]["change"] = jax.device_get(norms(
                        out["params"], params0, 1.0))
                st["last"] = out
                return out, metrics

            def on_metrics(step_no, rec):
                now = time.perf_counter()
                st["steps"][step_no] = (now, rec["seconds"])
                if step_no <= 2:
                    mark(ctx, f"step_{step_no}")
                if step_no <= 3:
                    st["readings"]["losses"].append(rec["loss"])
                if st["open"] is not None and st["close"] is None \
                        and now - st["open"][1] >= ctx["seconds"]:
                    st["close"] = (step_no, now)
                    st["span"].__exit__(None, None, None)
                    tracer.stop()
                    feed.closed = True

            def proactive(step_no):
                """Opens the window, with its one save where the mix
                saves."""
                if step_no != setup_steps:
                    return None
                if window_save:
                    st["saved"] = (step_no,
                                   jax.device_get(bit_sums(st["last"])),
                                   data.state_dict())
                tracer.start()
                # an annotation records only if made once tracing runs
                st["span"] = jax.profiler.TraceAnnotation("bench.window")
                st["span"].__enter__()
                st["open"] = (step_no, time.perf_counter())
                return "window" if window_save else None

            info = None
            try:
                _, info = run_with_recovery(
                    dep, step, state, feed, 10 ** 9, like=template,
                    shardings=shardings, on_metrics=on_metrics,
                    proactive=proactive)
            except WindowClosed:
                pass
            del state, params0, zero_m
            st.pop("last", None)
            dep.manager.wait()
            ctx["memory_peak_bytes"] = memory_peak_bytes(
                mesh.devices.reshape(-1))
            if info is not None or st["close"] is None:
                raise RuntimeError(f"the job ended before the window "
                                   f"closed: {info}")
            if window_save:
                s_at, want, local = st["saved"]
                restored, got_local = dep.manager.restore(
                    step=s_at, like=template, shardings=shardings)
                got = jax.device_get(bit_sums(restored))
                del restored
                st["restore_mismatches"] = int(got_local != local) + sum(
                    not np.array_equal(a, b) for a, b in
                    zip(jax.tree.leaves(want), jax.tree.leaves(got)))
                saved = dep.save_history[0]
    finally:
        dep.stop()
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    (s0, t0), (s1, t1) = st["open"], st["close"]
    window_steps = list(range(s0 + 1, s1 + 1))
    finite = all(np.isfinite([st["steps"][s][1] for s in window_steps]))
    out = {
        "attempted": len(window_steps), "failed": 0 if finite else 1,
        "e2e": {"train_tokens_per_s": len(window_steps) * rows * seq
                / (t1 - t0)},
        "window": (t0, t1), "spans": spans.log,
        "train": {"steps": {s: st["steps"][s] for s in window_steps},
                  "tokens": len(window_steps) * rows * seq,
                  "seq_len": seq, "chips": dp * tp,
                  "checksummed_bytes": sum(checksummed.get(s, 0)
                                           for s in window_steps)},
        "readings": st["readings"],
        "info": {"median_step_s": float(np.median(
                     [st["steps"][s][1] for s in window_steps])),
                 "setup_steps": setup_steps},
    }
    if window_save:
        out["readings"]["restore_mismatches"] = st["restore_mismatches"]
        out["train"]["write_s"] = saved.write_seconds
        out["info"].update(save_snapshot_s=saved.snapshot_seconds,
                           save_write_s=saved.write_seconds,
                           save_bytes=saved.bytes_written)
    return out


def check(ctx: Dict, out: Dict) -> Dict:
    """The numbers compared, each with its limit: the program's first three
    steps against the reference's, and the checkpoint round trip."""
    from bench.reference import train as rtrain
    conf, traffic, limits = ctx["config"], ctx["traffic"], ctx["limits"]
    ref = reference.load(conf)
    rows = traffic["rows_per_data_replica"] * conf["mesh"]["data"]
    want = rtrain.readings(ref, ref.dims(conf), traffic["optimizer"],
                           program_seed(ctx["seed"]), rows, traffic["seq_len"])
    got = rtrain.compare(out["readings"], want)
    if "restore_mismatches" in out["readings"]:
        got["restore_mismatches"] = out["readings"]["restore_mismatches"]
    return {k: (v, limits[k]) for k, v in got.items()}


def _count_checksummed(dep, template) -> Dict[int, int]:
    """Count, per training step, the bytes of the leaves the scrubber
    checksums: ``verify_state`` before the step re-reads the leaves the
    last ``scrub`` recorded, and ``scrub`` after it records the names of
    the next subset.  Wraps both on the instance; returns {step: bytes}."""
    from repro.sdc.checksum import named_leaves
    size = {n: x.size * x.dtype.itemsize for n, x in named_leaves(template)}
    per_step: Dict[int, int] = {}
    last = [0]
    scrub, verify = dep.scrub, dep.verify_state

    def counted_scrub(state, step):
        names = scrub(state, step)
        last[0] = sum(size[n] for n in names)
        per_step[step] = per_step.get(step, 0) + last[0]
        return names

    def counted_verify(state, step):
        per_step[step] = per_step.get(step, 0) + last[0]
        return verify(state, step)
    dep.scrub, dep.verify_state = counted_scrub, counted_verify
    return per_step
