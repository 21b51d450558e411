"""A run with the timed path broken underneath reads ``correct`` false:
once for each fault a cell can have, at the tiny presets on the CPU, with
the limits set for those sizes (``bench/tests/tiny.py``).  (The exchange
between chips is not among them: every cell runs on one chip.)"""
from __future__ import annotations

import pytest

from bench.tests.test_drivers import tiny_run


def _wrap_step(monkeypatch, broken):
    import repro.train
    make = repro.train.make_train_step

    def make_broken(*a, **kw):
        return broken(make(*a, **kw))
    monkeypatch.setattr(repro.train, "make_train_step", make_broken)


def unchanged(step):
    """A step that returns its state unchanged."""
    def f(state, batch):
        return state, step(state, batch)[1]
    return f


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def f(state, batch):
        n = batch["tokens"].shape[0] // 2
        return step(state, {k: v[:n] for k, v in batch.items()})
    return f


@pytest.mark.parametrize("fault", [unchanged, half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_train_step_fault(monkeypatch, fault):
    _wrap_step(monkeypatch, fault)
    res = tiny_run("granite-train-guarded")
    assert not res["correct"], res["checks"]


def test_restored_checkpoint_altered(monkeypatch):
    """An answer altered where it is produced: one restored value."""
    import jax
    from repro.core.checkpoint import CheckpointManager
    restore = CheckpointManager.restore

    def altered(self, **kw):
        state, local = restore(self, **kw)
        w = state["params"]["final_norm"]
        return dict(state, params=dict(
            state["params"], final_norm=w.at[0].add(1.0))), local
    monkeypatch.setattr(CheckpointManager, "restore", altered)
    res = tiny_run("granite-train-guarded")
    assert not res["correct"]
    assert res["checks"]["restore_mismatches"]["value"] >= 1
    assert jax.devices()[0].platform == "cpu"
