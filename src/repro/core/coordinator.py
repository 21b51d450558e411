"""BSP training coordinator: the paper's protected iterative loop.

``run_bsp`` executes supersteps with interruption detection + data
preservation at step boundaries.  ``run_with_recovery`` wraps it with
fail-stop AND silent-data-corruption recovery: a (simulated or real)
failure triggers restore from the last committed checkpoint and
continuation; a CorruptionDetected from any SDC tier (docs/sdc.md)
triggers rollback to the last checksum-verified checkpoint — the
end-to-end behaviour DeLIA provides to its host application.

SDC hooks inside each superstep (all no-ops unless enabled):
  - ``dep.verify_state`` at the top: re-checksums the leaves the previous
    iteration's scrub recorded — the state must be bit-identical, because
    nothing legitimate touches it between supersteps.
  - ``fault_injector.apply_sdc`` right before that verify: scheduled
    bit-flips strike the state exactly where real memory corruption
    would, inside the record->verify window.
  - ``dep.scrub`` at the bottom: checksums the next rotating subset of
    the freshly-produced state.
  - ``dep.check_metrics`` after the superstep: the tier-3 loss sentinel.

With an ``Observability`` attached (``dep.attach_obs``) every host
statement of a superstep runs inside one span (``repro.obs.metrics.span``):
``data.batch`` (the next batch), ``train.dispatch`` (the step call),
``train.sync`` (the wait for its metrics), ``train.bookkeep`` (the
boundary poll, the step record, ``on_metrics`` and the checkpoint
decision), or the SDC guard's and the checkpoint path's own spans.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from repro.core.api import Dependability
from repro.core.failures import (CorruptionDetected, FaultInjector,
                                 SimulatedFailure)
from repro.obs.metrics import span


def run_bsp(dep: Dependability, train_step: Callable, state, data,
            num_steps: int, *, fault_injector: Optional[FaultInjector] = None,
            on_metrics: Optional[Callable[[int, Dict], None]] = None,
            stop_check: Optional[Callable[[], Optional[str]]] = None,
            proactive: Optional[Callable[[int], Optional[str]]] = None,
            final_save: bool = True) -> Tuple[Any, str, List[Dict]]:
    """Runs supersteps until ``num_steps`` or interruption.

    Returns (state, status, history); status in {"done", "interrupted",
    "paused:<reason>"}.  ``stop_check`` is polled at each step boundary:
    a non-None reason pauses the loop exactly like an interruption (final
    save + flush) but reports the reason — the elastic layer uses it to
    stop for non-failure events (e.g. a rejoining host growing the mesh).
    ``proactive`` is the telemetry plane's precursor hook
    (``repro.obs.anomaly.make_proactive_hook``): polled after each
    superstep when the policy cadence does NOT already save; a non-None
    reason forces a checkpoint now, ahead of the failure the precursors
    predict (docs/observability.md).  Forced saves flow through
    ``dep.save`` like any other, so they re-anchor the policy cadence.
    May raise SimulatedFailure (injected fail-stop) or CorruptionDetected
    (SDC tier tripped) — run_with_recovery handles both.
    """
    history: List[Dict] = []
    obs = dep.obs
    step = int(jax.device_get(state["step"]))
    while step < num_steps:
        with span(obs, "train.bookkeep"):
            pause = stop_check() if stop_check is not None else None
            stop = dep.interrupted() or pause is not None
            if not stop and fault_injector is not None:
                # SDC strikes the at-rest state inside the record->verify
                # window
                state = fault_injector.apply_sdc(step + 1, state)
        if stop:
            if final_save:
                dep.save(step, state, final=True)
            # flush: the final save may have queued behind a still-running
            # async write — do not hand back control (or exit) with the
            # checkpoint in flight
            dep.manager.wait()
            status = "interrupted" if pause is None else f"paused:{pause}"
            return state, status, history

        dep.verify_state(state, step + 1)      # may raise CorruptionDetected

        with span(obs, "data.batch"):
            batch = data.next_batch()
        t0 = time.perf_counter()
        with span(obs, "train.dispatch"):
            if fault_injector is not None:
                # fail-stop / straggle strikes DURING the superstep
                fault_injector.check(step + 1)  # may raise SimulatedFailure
            state, metrics = train_step(state, batch)
        with span(obs, "train.sync"):
            metrics = jax.device_get(metrics)  # block: end of superstep
        dt = time.perf_counter() - t0
        step += 1

        dep.scrub(state, step)                 # record the next scrub window
        with span(obs, "train.bookkeep"):
            straggler = dep.observe_step(dt, step)
            rec = {"step": step, "seconds": dt, "straggler": straggler,
                   **{k: float(v) for k, v in metrics.items()}}
            history.append(rec)
            if obs is not None:
                obs.emit("train", "step", **rec)
                obs.registry.histogram("train.step_ms").observe(dt * 1e3)
            if on_metrics:
                on_metrics(step, rec)
        dep.check_metrics(step, metrics)       # may raise CorruptionDetected

        with span(obs, "train.bookkeep"):
            why = None
            save = dep.should_checkpoint(step)
            if not save and proactive is not None:
                why = proactive(step)
        if save or why is not None:
            dep.save(step, state)
        if why is not None and obs is not None:
            obs.emit("checkpoint", "proactive", step=step, reason=why)
            obs.registry.counter("checkpoint.proactive").inc()
    dep.manager.wait()
    return state, "done", history


def run_with_recovery(dep: Dependability, train_step: Callable, state, data,
                      num_steps: int, *,
                      fault_injector: Optional[FaultInjector] = None,
                      max_restarts: int = 3,
                      like=None, shardings=None,
                      on_metrics=None,
                      proactive: Optional[Callable[[int], Optional[str]]]
                      = None) -> Tuple[Any, Dict]:
    """Failure recovery loop: restore-from-checkpoint on fail-stop OR
    detected corruption.

    ``like``/``shardings`` describe the state pytree for restore (defaults
    to the registered global template).  Corruption rollback restores the
    newest checksum-verified checkpoint (walking back past any checkpoint
    whose CRCs no longer verify); every rollback/restart is an event in
    the returned history."""
    restarts = 0
    all_history: List[Dict] = []
    state0 = state                           # scratch-restart fallback
    local0 = (dep._local_provider.state_dict()
              if dep._local_provider is not None else None)
    corrupt_exclude: set = set()
    last_corrupt_restore = None              # (step, saves seen at restore)
    while True:
        try:
            state, status, hist = run_bsp(
                dep, train_step, state, data, num_steps,
                fault_injector=fault_injector, on_metrics=on_metrics,
                proactive=proactive)
            all_history.extend(hist)
            return state, {"status": status, "restarts": restarts,
                           "history": all_history}
        except (SimulatedFailure, CorruptionDetected) as e:
            is_corruption = isinstance(e, CorruptionDetected)
            if is_corruption:
                all_history.append({
                    "step": e.step,
                    "event": f"corruption:{e.kind}:{e.detail}"})
            else:
                all_history.append({"step": e.step,
                                    "event": f"failure:{e.kind}"})
                if dep.obs is not None:
                    # SDC tiers emit their own detection inside
                    # verify_state/check_metrics; fail-stop is raised by
                    # the injector, so record the detection here
                    dep.obs.emit("train", "interrupted", step=e.step,
                                 failure_kind=e.kind)
            restarts += 1
            if restarts > max_restarts:
                raise
            dep.manager.wait()
            if (is_corruption and last_corrupt_restore is not None
                    and len(dep.save_history) == last_corrupt_restore[1]):
                # corruption re-tripped without a single new checkpoint:
                # the checkpoint we rolled back to is itself suspect (CRC
                # can't see corruption that happened before the save) —
                # walk one further back instead of livelocking on it
                corrupt_exclude.add(last_corrupt_restore[0])
            try:
                state, got = dep.restore_latest(
                    like=like, shardings=shardings,
                    exclude=corrupt_exclude if is_corruption else None)
                if dep.last_restore_skipped:
                    all_history.append({
                        "step": got, "event": "restore:skipped:" + ",".join(
                            str(s) for s, _ in dep.last_restore_skipped)})
                if is_corruption:
                    last_corrupt_restore = (got, len(dep.save_history))
                if dep.obs is not None:
                    dep.obs.registry.histogram("train.rollback_depth").\
                        observe(max(0, e.step - got))
                    dep.obs.emit("train", "resume", step=got,
                                 rolled_back_from=e.step,
                                 restarts=restarts)
            except FileNotFoundError as fnf:
                # no (acceptable) checkpoint at all: restart from scratch
                all_history.append({"step": e.step,
                                    "event": f"restore:scratch:{fnf}"})
                state = state0
                if local0 is not None:
                    dep._local_provider.load_state_dict(local0)
                last_corrupt_restore = None
                if dep.obs is not None:
                    dep.obs.emit("train", "resume", step=0, scratch=True,
                                 restarts=restarts)
            dep.reset_sdc()
