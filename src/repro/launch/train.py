"""Fault-tolerant training driver (the end-to-end launcher).

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b --tiny \
        --steps 200 --ckpt-dir out/ckpt --policy young_daly --async-save

Wires the full DeLIA stack around the BSP training loop: checkpoint policy
(Young/Daly or fixed), sync/async sharded checkpoints (+ optional int8
codec), termination-signal detection, optional UDP heartbeats, straggler
watchdog, and automatic restore-on-restart.  ``--inject-failure N`` simulates
a fail-stop at step N and recovers (the paper's fault model, end to end).

SDC guard (docs/sdc.md): ``--scrub``/``--sentinel`` turn on the tier-2/3
detectors, ``--abft`` opts the projection matmuls into the checksummed
kernel, and ``--inject-bitflip STEP:LEAF:BIT`` flips one state bit mid-run
to watch detection + rollback happen.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax

from repro.core import (Dependability, DependabilityConfig, FaultInjector,
                        SystemModel, run_with_recovery)
from repro.data import make_pipeline
from repro.launch.common import add_model_args, model_config, use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.sharding.api import mesh_context, resolve
from repro.sharding.rules import state_specs
from repro.train import init_state, make_train_step


def run(argv=None) -> dict:
    """Parse ``argv``, train under the guard, and return the outcome:
    ``status``, ``restarts``, every step record in the order run
    (``steps``: a step rolled back and run again appears twice), and the
    number of ``saves`` and ``delta_saves``."""
    ap = argparse.ArgumentParser()
    add_model_args(ap)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--ckpt-dir", required=True,
                    help="checkpoint directory; a run resumes from the "
                         "newest checkpoint it finds here")
    ap.add_argument("--policy", default="young_daly",
                    choices=["young_daly", "every_n", "risk_adjusted"])
    ap.add_argument("--every-n", type=int, default=10)
    ap.add_argument("--node-mtbf-hours", type=float, default=24 * 365)
    ap.add_argument("--num-nodes", type=int, default=1)
    ap.add_argument("--async-save", action="store_true")
    ap.add_argument("--codec", default=None, choices=[None, "int8"])
    ap.add_argument("--delta-checkpoint", action="store_true",
                    help="incremental saves: write only blocks whose "
                         "on-device hash changed since the last checkpoint")
    ap.add_argument("--delta-block", type=int, default=65536,
                    help="elements per delta block (multiple of 256)")
    ap.add_argument("--full-every", type=int, default=8,
                    help="force a full save every N checkpoints "
                         "(bounds the delta reference-chain depth)")
    ap.add_argument("--heartbeat", action="store_true")
    ap.add_argument("--inject-failure", type=int, default=0,
                    help="simulate a fail-stop at this step")
    ap.add_argument("--scrub", action="store_true",
                    help="tier-2 SDC: rotating state-checksum scrubber")
    ap.add_argument("--scrub-fraction", type=float, default=0.25)
    ap.add_argument("--sentinel", action="store_true",
                    help="tier-3 SDC: non-finite/loss-spike sentinel")
    ap.add_argument("--abft", action="store_true",
                    help="tier-1 SDC: checksummed projection matmuls")
    ap.add_argument("--inject-bitflip", default="",
                    help="STEP:LEAF:BIT, e.g. 50:params.embed.tok:30 — "
                         "flip one state bit mid-run (SDC fault model)")
    ap.add_argument("--telemetry-dir", default="",
                    help="record the run's telemetry bundle here "
                         "(events.jsonl + trace.json + metrics, "
                         "docs/observability.md)")
    ap.add_argument("--metrics-snapshot", default="",
                    help="write a JSON metrics snapshot to this path at "
                         "the end of the run")
    ap.add_argument("--telemetry-plane", action="store_true",
                    help="run the in-process telemetry plane: anomaly "
                         "detectors over the event stream, per-host risk "
                         "scores (docs/observability.md)")
    ap.add_argument("--proactive-checkpoint", action="store_true",
                    help="force a checkpoint when a precursor pushes any "
                         "host's risk past --risk-threshold (implies "
                         "--telemetry-plane)")
    ap.add_argument("--risk-threshold", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = model_config(args)
    mesh = make_host_mesh(args.data_par, args.model_par)
    tp = args.model_par
    specs = state_specs(cfg, tp)
    shardings = jax.tree.map(lambda s: resolve(s, mesh), specs,
                             is_leaf=lambda x: x.__class__.__name__
                             == "PartitionSpec")

    data = make_pipeline(cfg, args.seq_len, args.global_batch,
                         seed=args.seed)

    dep = Dependability(DependabilityConfig(
        checkpoint_dir=args.ckpt_dir,
        policy_mode=args.policy,
        every_n=args.every_n,
        async_save=args.async_save,
        codec=args.codec,
        delta_checkpoint=args.delta_checkpoint,
        delta_block=args.delta_block,
        full_every=args.full_every,
        heartbeat=args.heartbeat,
        scrub=args.scrub,
        scrub_fraction=args.scrub_fraction,
        sentinel=args.sentinel,
        system=SystemModel(node_mtbf_seconds=args.node_mtbf_hours * 3600,
                           num_nodes=args.num_nodes),
    )).start()
    dep.register_local_state(data)

    obs = None
    want_plane = args.telemetry_plane or args.proactive_checkpoint
    if args.telemetry_dir or args.metrics_snapshot or want_plane:
        from repro.obs import Observability
        import os as _os
        obs = Observability(
            jsonl_path=(_os.path.join(args.telemetry_dir, "events.jsonl")
                        if args.telemetry_dir else None))
        dep.attach_obs(obs)

    proactive = None
    if want_plane:
        from repro.obs import AnomalyEngine, make_proactive_hook
        anomaly = AnomalyEngine()
        anomaly.attach(obs.bus)
        if args.proactive_checkpoint:
            proactive = make_proactive_hook(
                anomaly.risk_scores, threshold=args.risk_threshold,
                policy=(dep.policy if args.policy == "risk_adjusted"
                        else None))
        elif args.policy == "risk_adjusted":
            # no forced saves — risk still tightens the Young/Daly
            # interval through the policy
            def proactive(step, _a=anomaly, _p=dep.policy):
                _p.observe_risk(
                    max(_a.risk_scores().values(), default=0.0))
                return None

    with mesh_context(mesh):
        step_fn = jax.jit(
            make_train_step(cfg, microbatches=args.microbatches,
                            total_steps=args.steps,
                            impl=("abft" if args.abft else None),
                            param_specs=specs["params"]),
            out_shardings=(shardings, None))

        latest = dep.manager.latest_step()
        template = jax.eval_shape(
            lambda: init_state(cfg, jax.random.PRNGKey(args.seed)))
        if latest is not None:
            state, got = dep.restore_latest(like=template,
                                            shardings=shardings)
            print(f"[train] restored checkpoint step {got}")
        else:
            state = jax.jit(
                lambda: init_state(cfg, jax.random.PRNGKey(args.seed)),
                out_shardings=shardings)()
        dep.register_global_state(template, shardings)

        injector = None
        if args.inject_failure:
            injector = FaultInjector()
            injector.schedule_failstop(args.inject_failure)
        if args.inject_bitflip:
            step_s, leaf, bit_s = args.inject_bitflip.split(":")
            injector = injector or FaultInjector()
            injector.schedule_bitflip(int(step_s), leaf, int(bit_s))

        steps = []

        def on_metrics(step, rec):
            steps.append(rec)
            if step % 10 == 0 or step == args.steps:
                print(f"[train] step {step:5d} loss={rec['loss']:.4f} "
                      f"gnorm={rec['grad_norm']:.3f} "
                      f"{rec['seconds']*1e3:.1f} ms"
                      + (" STRAGGLER" if rec["straggler"] else ""), flush=True)

        t0 = time.perf_counter()
        state, info = run_with_recovery(
            dep, step_fn, state, data, args.steps,
            fault_injector=injector, like=template, shardings=shardings,
            on_metrics=on_metrics, proactive=proactive)
        wall = time.perf_counter() - t0

    n_saves = len(dep.save_history)
    n_delta = sum(1 for s in dep.save_history
                  if getattr(s, "kind", "full") == "delta")
    delta_info = (f" ({n_saves - n_delta} full + {n_delta} delta)"
                  if args.delta_checkpoint else "")
    print(f"[train] {info['status']} in {wall:.1f}s; restarts="
          f"{info['restarts']}; checkpoints={n_saves}{delta_info}; "
          f"young-daly interval={dep.policy.interval_steps()} steps")
    events = [h["event"] for h in info["history"] if "event" in h]
    if events:
        print(f"[train] failure/corruption events: {events}")
    if obs is not None:
        summary = obs.timeline().summary()
        mttr = summary["mttr_s"]
        mttr_txt = f"MTTR={mttr:.3f}s, " if mttr is not None else ""
        print(f"[train] telemetry: {summary['incidents']} incidents, "
              f"{mttr_txt}availability={summary['availability']:.4f} "
              f"over {summary['span_s']:.1f}s observed")
        if args.telemetry_dir:
            paths = obs.dump(args.telemetry_dir)
            print(f"[train] telemetry bundle: {sorted(paths.values())}")
        if args.metrics_snapshot:
            obs.registry.to_json(args.metrics_snapshot)
            print(f"[train] metrics snapshot: {args.metrics_snapshot}")
        obs.close()
    dep.stop()
    return {"status": info["status"], "restarts": info["restarts"],
            "steps": steps, "saves": n_saves, "delta_saves": n_delta}


def main(argv=None) -> int:
    """The CLI: non-zero unless the run finished ``done``."""
    return 0 if run(argv)["status"] == "done" else 1


if __name__ == "__main__":
    sys.exit(main())
