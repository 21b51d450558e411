"""Serving driver: the dependable serving engine (docs/serving.md).

Thin CLI over ``repro.serve.ServeEngine`` — continuous batching over a
block-paged KV cache with prefix sharing (the default; ``--legacy-pool``
forces the old fixed-slot pool), N replicas with heartbeat failover,
decode-path SDC sentinel.  The old fixed-batch demo is what
examples/serve_lm.py still shows; this driver serves a request stream.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b --tiny \
        --requests 8 --prompt-len 32 --gen 32 \
        --replicas 2 --slots 4 --fault-tolerant --kill-replica-at 5

    # push concurrency past the slot budget at the same memory
    PYTHONPATH=src python -m repro.launch.serve --tiny --requests 32 \
        --slots 4 --max-active 16
"""
from __future__ import annotations

import argparse
import statistics
import sys
import tempfile
import time

import jax

from repro.core import CheckpointManager, FaultInjector
from repro.launch.common import add_model_args, model_config, use_compile_cache
from repro.models import init_params
from repro.serve import ServeEngine, make_standby_source, pctl


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    add_model_args(ap)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--replicas", type=int, default=1,
                    help="model replicas in the serving pool")
    ap.add_argument("--slots", type=int, default=4,
                    help="KV-cache slots per replica (max in-flight "
                    "requests each); under --paged this sizes the "
                    "default equal-memory page pool")
    pool = ap.add_mutually_exclusive_group()
    pool.add_argument("--paged", action="store_true", default=None,
                      dest="paged",
                      help="block-paged KV cache with prefix sharing "
                      "(docs/serving.md); the default wherever the model "
                      "supports it")
    pool.add_argument("--legacy-pool", action="store_false", dest="paged",
                      help="force the legacy fixed-slot pool (the "
                      "equal-memory bench comparator)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page (default 16)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pages in each replica's pool (default: the slot "
                    "pool's memory budget, repaged)")
    ap.add_argument("--max-active", type=int, default=None,
                    help="decode rows per replica under --paged (default: "
                    "--slots); raise it to push concurrency past the "
                    "slot count at the same memory")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable refcounted prefix sharing between "
                    "requests")
    ap.set_defaults(paged=None)         # auto: paged where supported
    ap.add_argument("--fault-tolerant", action="store_true",
                    help="heartbeat monitoring + decode sentinel + "
                    "failover (re-execute drained requests on survivors)")
    ap.add_argument("--standbys", type=int, default=0,
                    help="warm standbys restored from a params checkpoint "
                    "on failure (implies --fault-tolerant)")
    ap.add_argument("--kill-replica-at", type=int, default=-1,
                    help="inject a replica kill at this engine step "
                    "(drives the failover path end to end)")
    ap.add_argument("--telemetry-dir", default="",
                    help="record the run's telemetry bundle here "
                         "(events.jsonl + trace.json + metrics, "
                         "docs/observability.md)")
    ap.add_argument("--metrics-snapshot", default="",
                    help="write a JSON metrics snapshot to this path at "
                         "the end of the run")
    ap.add_argument("--pre-drain", action="store_true",
                    help="telemetry plane: run the anomaly detectors over "
                         "the engine's event stream and pre-drain a "
                         "replica whose host risk crosses "
                         "--risk-threshold (docs/observability.md)")
    ap.add_argument("--risk-threshold", type=float, default=0.8)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = model_config(args)
    if not cfg.has_decode:
        print(f"{args.arch} is encoder-only; no decode loop")
        return 1
    if cfg.embedding_inputs:
        print(f"{args.arch} takes embedding inputs; the engine serves "
              "token prompts")
        return 1

    params = init_params(cfg, jax.random.PRNGKey(0))
    injector = None
    if args.kill_replica_at >= 0:
        injector = FaultInjector()
        injector.schedule_replica_kill(args.kill_replica_at,
                                       replica_id=args.replicas - 1)
    fault_tolerant = args.fault_tolerant or args.standbys > 0

    obs = None
    if args.telemetry_dir or args.metrics_snapshot or args.pre_drain:
        import os as _os
        from repro.obs import Observability
        obs = Observability(
            jsonl_path=(_os.path.join(args.telemetry_dir, "events.jsonl")
                        if args.telemetry_dir else None))

    anomaly = None
    risk_source = None
    if args.pre_drain:
        from repro.obs import AnomalyEngine
        anomaly = AnomalyEngine()
        anomaly.attach(obs.bus)
        risk_source = anomaly.risk_scores

    paged_kw = {}
    if args.page_size is not None:
        paged_kw["page_size"] = args.page_size
    engine = ServeEngine(cfg, params, num_replicas=args.replicas,
                         slots_per_replica=args.slots,
                         max_len=args.prompt_len + args.gen,
                         fault_tolerant=fault_tolerant,
                         fault_injector=injector, obs=obs,
                         risk_source=risk_source,
                         pre_drain_threshold=args.risk_threshold,
                         paged=args.paged, num_pages=args.num_pages,
                         max_active=args.max_active,
                         prefix_cache=not args.no_prefix_cache,
                         **paged_kw)
    ckpt_dir = None
    if args.standbys > 0:
        # warm-standby params come back through restore_latest — the same
        # walk-back-past-corruption path training recovery uses
        ckpt_dir = tempfile.mkdtemp(prefix="serve_standby_")
        manager = CheckpointManager(ckpt_dir, fsync="none")
        manager.save(0, {"params": params})
        like = jax.eval_shape(lambda: params)
        for _ in range(args.standbys):
            engine.add_standby(make_standby_source(manager, like))

    for i in range(args.requests):
        prompt = jax.random.randint(jax.random.PRNGKey(100 + i),
                                    (args.prompt_len,), 0, cfg.vocab_size)
        engine.submit([int(t) for t in prompt], args.gen)

    t0 = time.perf_counter()
    results = engine.run()
    wall = time.perf_counter() - t0

    lat = engine.request_latencies()
    ttft = sorted(t for _, t, _ in lat)
    total = sorted(t for _, _, t in lat)
    done_tokens = sum(len(v) for v in results.values())
    prefill_tokens = args.prompt_len * len(lat)
    pool_txt = (f"{engine.fns.max_active} paged rows "
                f"({engine.fns.num_pages} x {engine.fns.page_size}-token "
                f"pages)" if engine.paged else f"{args.slots} slots")
    print(f"served {len(results)}/{args.requests} requests "
          f"({done_tokens} tokens) in {wall:.2f}s on {args.replicas} "
          f"replica(s) x {pool_txt} "
          f"-> {done_tokens / wall:.0f} tok/s decode, "
          f"{prefill_tokens / wall:.0f} tok/s prefill-amortized")
    if engine.paged:
        cons = engine.page_conservation()
        hits = sum(r.pool.prefix_hits
                   for r in engine.router.replicas.values())
        misses = sum(r.pool.prefix_misses
                     for r in engine.router.replicas.values())
        total_lookups = hits + misses
        hit_txt = (f"{hits}/{total_lookups} "
                   f"({hits / total_lookups:.0%})" if total_lookups
                   else "0/0")
        print(f"paged KV: prefix hits {hit_txt}, "
              f"{cons['pages_free']}/{cons['pages_total']} pages free, "
              f"refcounts {'ok' if cons['refs_ok'] else 'DRIFTED'}")
    if total:
        print(f"latency  p50={statistics.median(total) * 1e3:.0f}ms "
              f"p99={pctl(total, 0.99) * 1e3:.0f}ms "
              f"ttft p50={statistics.median(ttft) * 1e3:.0f}ms")
    for ev in engine.events:
        print(f"event step={ev['step']}: {ev['event']} "
              + " ".join(f"{k}={v}" for k, v in ev.items()
                         if k not in ("t", "step", "event")))
    retried = len(engine.scheduler.retried_rids)
    if retried:
        print(f"failover: {retried} request(s) drained and re-executed, "
              f"{len(engine.scheduler.failed_rids)} dropped")
    if obs is not None:
        summary = obs.timeline().summary()
        mttr = summary["mttr_s"]
        mttr_txt = f"MTTR={mttr:.3f}s, " if mttr is not None else ""
        print(f"telemetry: {summary['incidents']} incidents, "
              f"{mttr_txt}availability={summary['availability']:.4f} "
              f"over {summary['span_s']:.1f}s observed")
        if args.telemetry_dir:
            paths = obs.dump(args.telemetry_dir)
            print(f"telemetry bundle: {sorted(paths.values())}")
        if args.metrics_snapshot:
            obs.registry.to_json(args.metrics_snapshot)
            print(f"metrics snapshot: {args.metrics_snapshot}")
        obs.close()
    engine.shutdown()
    return 0 if len(results) == args.requests else 1


if __name__ == "__main__":
    sys.exit(main())
