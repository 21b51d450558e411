"""The launchers chip_smoke.py drives, at the tiny presets on CPU."""
from __future__ import annotations

import functools
import math
import os

import pytest

from repro.launch import serve, train

GUARDED = ["--tiny", "--steps", "5", "--seq-len", "32", "--global-batch",
           "4", "--policy", "every_n", "--every-n", "2", "--async-save",
           "--delta-checkpoint", "--scrub", "--sentinel"]


def _losses(out):
    return {r["step"]: r["loss"] for r in out["steps"]}


def test_train_recovers_to_the_replay_loss(tmp_path):
    out = train.run(GUARDED + ["--inject-failure", "5",
                               "--ckpt-dir", str(tmp_path / "failed")])
    assert out["status"] == "done"
    assert out["restarts"] == 1
    assert out["delta_saves"] >= 1
    assert [r["step"] for r in out["steps"]] == [1, 2, 3, 4, 5]
    assert all(math.isfinite(v) for v in _losses(out).values())

    replay = train.run(GUARDED + ["--ckpt-dir", str(tmp_path / "replay")])
    assert replay["restarts"] == 0
    assert _losses(out)[5] == _losses(replay)[5]


@pytest.mark.parametrize("interrupted, rc", [(False, 0), (True, 1)])
def test_train_main_exit_code(tmp_path, monkeypatch, interrupted, rc):
    """main exits non-zero unless the run finished ``done``."""
    from repro.core.api import Dependability
    monkeypatch.setattr(Dependability, "interrupted",
                        lambda self: interrupted)
    assert train.main(["--tiny", "--steps", "2", "--seq-len", "16",
                       "--global-batch", "2",
                       "--ckpt-dir", str(tmp_path)]) == rc


def test_serve_serves_every_request_after_a_replica_kill(monkeypatch):
    # the default 0.25 s heartbeat timeout can starve on a test host loaded
    # by parallel workers; the kill under test is injected, not detected
    monkeypatch.setattr(serve, "ServeEngine", functools.partial(
        serve.ServeEngine, heartbeat_timeout_factor=40.0))
    assert serve.main(["--tiny", "--replicas", "2", "--fault-tolerant",
                       "--kill-replica-at", "3"]) == 0


@pytest.mark.parametrize("env, backend, want", [
    ("set", "tpu", "env"), (None, "cpu", None), (None, "tpu", "repo")])
def test_compile_cache_location(tmp_path, monkeypatch, env, backend, want):
    """JAX_COMPILATION_CACHE_DIR wins; else a fixed <repo>/.jax_cache on
    TPU only; CPU runs cache nothing."""
    import jax

    from repro.launch import common
    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = common.use_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    expect = {"env": str(tmp_path), None: None,
              "repo": os.path.join(common.REPO_ROOT, ".jax_cache")}[want]
    assert got == expect
    assert after == (expect if want == "repo" else before)
