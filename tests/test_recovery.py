"""End-to-end fail-stop recovery: crash-and-restore training must be
bit-exact with the uninterrupted run (global + local state preserved)."""
import jax
import numpy as np
import pytest

from repro.core import (Dependability, DependabilityConfig, FaultInjector,
                        SimulatedFailure, run_bsp, run_with_recovery)
from repro.data import make_pipeline
from repro.models import get_config
from repro.train import init_state, make_train_step

KEY = jax.random.PRNGKey(0)


def _dep(tmp_path, **kw):
    base = dict(policy_mode="every_n", every_n=2, heartbeat=False,
                signal_detection=False)
    base.update(kw)
    return Dependability(DependabilityConfig(checkpoint_dir=str(tmp_path),
                                             **base)).start()


def _run_reference(cfg, steps):
    step_fn = jax.jit(make_train_step(cfg, total_steps=steps))
    state = init_state(cfg, KEY)
    data = make_pipeline(cfg, 16, 4)
    for _ in range(steps):
        state, m = step_fn(state, data.next_batch())
    return state, float(m["loss"])


@pytest.mark.parametrize("async_save", [False, True])
def test_crash_recovery_bit_exact(tmp_path, async_save):
    cfg = get_config("granite-3-8b", tiny=True)
    steps = 9
    ref_state, ref_loss = _run_reference(cfg, steps)

    step_fn = jax.jit(make_train_step(cfg, total_steps=steps))
    state = init_state(cfg, KEY)
    data = make_pipeline(cfg, 16, 4)
    dep = _dep(tmp_path, async_save=async_save)
    dep.register_local_state(data)
    injector = FaultInjector()
    injector.schedule_failstop(5)
    injector.schedule_failstop(7)
    state, info = run_with_recovery(dep, step_fn, state, data, steps,
                                    fault_injector=injector, like=state,
                                    max_restarts=3)
    assert info["status"] == "done"
    assert info["restarts"] == 2
    last_loss = [h["loss"] for h in info["history"] if "loss" in h][-1]
    assert last_loss == ref_loss
    for a, b in zip(jax.tree.leaves(ref_state["params"]),
                    jax.tree.leaves(state["params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    dep.stop()


def test_recovery_gives_up_after_max_restarts(tmp_path):
    cfg = get_config("gemma-7b", tiny=True)
    step_fn = jax.jit(make_train_step(cfg))
    state = init_state(cfg, KEY)
    data = make_pipeline(cfg, 16, 2)
    dep = _dep(tmp_path)
    dep.register_local_state(data)
    injector = FaultInjector()
    for s in (2, 3, 4, 5, 6):
        injector.schedule_failstop(s)
    with pytest.raises(SimulatedFailure):
        run_with_recovery(dep, step_fn, state, data, 10,
                          fault_injector=injector, like=state,
                          max_restarts=2)
    dep.stop()


def test_straggler_watchdog_flags_slow_step(tmp_path):
    cfg = get_config("gemma-7b", tiny=True)
    step_fn = jax.jit(make_train_step(cfg))
    state = init_state(cfg, KEY)
    data = make_pipeline(cfg, 16, 2)
    dep = _dep(tmp_path, straggler_factor=2.5)
    dep.register_local_state(data)
    injector = FaultInjector()
    injector.schedule_straggle(8, extra_seconds=1.0)
    state, status, hist = run_bsp(dep, step_fn, state, data, 10,
                                  fault_injector=injector)
    # straggle(8) sleeps inside step 8's superstep window
    flagged = dep.stragglers.flagged_steps
    assert 8 in flagged, hist
    dep.stop()


def test_young_daly_policy_in_loop(tmp_path):
    cfg = get_config("gemma-7b", tiny=True)
    step_fn = jax.jit(make_train_step(cfg))
    state = init_state(cfg, KEY)
    data = make_pipeline(cfg, 16, 2)
    dep = _dep(tmp_path, policy_mode="young_daly")
    dep.register_local_state(data)
    state, status, _ = run_bsp(dep, step_fn, state, data, 6)
    assert status == "done"
    assert dep.manager.latest_step() is not None   # bootstrap save happened
    assert dep.policy.ckpt_cost_s is not None      # C measured online
    dep.stop()


LOOP = ("data.batch", "train.dispatch", "train.sync")


def _union_s(intervals):
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def test_run_bsp_spans_cover_the_superstep(tmp_path):
    """With an Observability attached, each superstep opens data.batch,
    train.dispatch and train.sync once, in that order, and the program's
    spans on the training thread (loop, SDC guard, checkpoint) cover
    nearly all of the loop's wall time."""
    import threading

    from repro.obs import Observability
    cfg = get_config("granite-3-8b", tiny=True)
    steps = 8
    step_fn = jax.jit(make_train_step(cfg, total_steps=steps))
    state = init_state(cfg, KEY)
    data = make_pipeline(cfg, 16, 4)
    step_fn(state, data.next_batch())              # compile outside the loop
    dep = _dep(tmp_path, scrub=True, scrub_fraction=0.5, sentinel=True)
    dep.register_local_state(data)
    obs = Observability()
    dep.attach_obs(obs)
    _, status, hist = run_bsp(dep, step_fn, state, data, steps)
    dep.stop()
    assert status == "done" and len(hist) == steps
    me = threading.get_ident()
    mine = [r for r in obs.registry.spans.records() if r[3] == me]
    assert [n for n, *_ in mine if n in LOOP] == list(LOOP) * steps
    assert {n for n, *_ in mine} >= {
        "train.bookkeep", "sdc.leaves", "sdc.reduce", "sdc.fetch",
        "sdc.loss", "ckpt.local", "ckpt.snapshot", "ckpt.write",
        "ckpt.commit"}
    t0 = min(a for _, a, _, _ in mine)
    t1 = max(b for _, _, b, _ in mine)
    covered = _union_s([(a, b) for _, a, b, _ in mine])
    assert covered >= 0.9 * (t1 - t0), (covered, t1 - t0)
    assert obs.registry.spans.dropped == 0


def test_run_bsp_records_nothing_without_observability(tmp_path,
                                                      monkeypatch):
    from repro.obs import metrics
    opened = []
    monkeypatch.setattr(metrics.Span, "__enter__",
                        lambda self: opened.append(self.hist.name))
    cfg = get_config("granite-3-8b", tiny=True)
    step_fn = jax.jit(make_train_step(cfg, total_steps=4))
    data = make_pipeline(cfg, 16, 4)
    dep = _dep(tmp_path, scrub=True, sentinel=True)
    dep.register_local_state(data)
    _, status, _ = run_bsp(dep, step_fn, init_state(cfg, KEY), data, 4)
    dep.stop()
    assert status == "done" and opened == []
