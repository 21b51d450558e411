"""Elastic recovery + mesh sharding tests.

These need multiple devices, so each test runs a subprocess with
--xla_force_host_platform_device_count set (the main test process must keep
the default single CPU device)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(script: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       env=env, capture_output=True, text=True,
                       timeout=timeout)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


@pytest.mark.slow
def test_elastic_reshard_restore(tmp_path):
    """Train on a (2,2) mesh, checkpoint, 'lose' 4 devices, restore onto a
    (1,2) survivor mesh and keep training — trajectory must match a run
    that never failed."""
    _run(f"""
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.core import CheckpointManager, survivor_mesh, reshard_state
    from repro.data import make_pipeline
    from repro.models import get_config
    from repro.sharding.api import mesh_context, resolve
    from repro.sharding.rules import state_specs
    from repro.train import init_state, make_train_step
    import jax.numpy as jnp

    cfg = get_config("granite-3-8b", tiny=True)
    key = jax.random.PRNGKey(0)

    def sharded_state(mesh, tp):
        specs = state_specs(cfg, tp)
        sh = jax.tree.map(lambda s: resolve(s, mesh), specs,
                          is_leaf=lambda x: x.__class__.__name__ == "PartitionSpec")
        return sh

    # reference: single-device run, 6 steps
    step = jax.jit(make_train_step(cfg, total_steps=10))
    ref = init_state(cfg, key)
    data = make_pipeline(cfg, 16, 4)
    for _ in range(6):
        ref, m = step(ref, data.next_batch())
    ref_loss = float(m["loss"])

    # mesh A: (2 data, 2 model); 3 steps then checkpoint
    from repro.launch.mesh import make_host_mesh
    mesh_a = make_host_mesh(2, 2)
    sh_a = sharded_state(mesh_a, 2)
    data2 = make_pipeline(cfg, 16, 4)
    with mesh_context(mesh_a):
        st = jax.jit(lambda: init_state(cfg, key), out_shardings=sh_a)()
        step_a = jax.jit(make_train_step(cfg, total_steps=10),
                         out_shardings=(sh_a, None))
        for _ in range(3):
            st, _ = step_a(st, data2.next_batch())
    mgr = CheckpointManager(r"{tmp_path}")
    mgr.save(3, st, data2.state_dict())

    # 'failure': only 2 devices survive -> (1 data, 2 model) mesh
    surv = survivor_mesh(list(jax.devices())[:2], model_axis=2)
    template = jax.eval_shape(lambda: init_state(cfg, key))
    st2, local, got = reshard_state(mgr, cfg, surv, template)
    assert got == 3
    # the resharded restore itself must be BIT-EXACT vs the saved state
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st2)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), "restore differs"
    data3 = make_pipeline(cfg, 16, 4)
    data3.load_state_dict(local)
    sh_b = sharded_state(surv, 2)
    with mesh_context(surv):
        step_b = jax.jit(make_train_step(cfg, total_steps=10),
                         out_shardings=(sh_b, None))
        for _ in range(3):
            st2, m2 = step_b(st2, data3.next_batch())
    got_loss = float(m2["loss"])
    # bf16 cross-shard reduction order differs between mesh layouts and
    # compounds over steps: individual params drift while the losses stay
    # close; on this XLA/CPU version trajectories agree to ~1.6% after 6
    # steps (a broken restore lands ~order 1 off).  The restore itself is
    # checked bit-exact above.
    assert abs(got_loss - ref_loss) < 0.15, (got_loss, ref_loss)
    print("elastic reshard OK", ref_loss, got_loss)
    """, devices=8)


@pytest.mark.slow
def test_restore_onto_different_shard_layout(tmp_path):
    """Save shards on a (4,2) mesh, restore bit-exact onto a (2,1) mesh
    with different partition axes AND onto plain numpy — spans reassembly,
    multi-shard parallel reads, and the device-codec path."""
    _run(f"""
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import CheckpointManager
    from repro.launch.mesh import make_host_mesh

    mesh_a = make_host_mesh(4, 2)
    mesh_b = make_host_mesh(2, 1)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 6, 128), jnp.float32)
    y = jnp.arange(512, dtype=jnp.int32)
    state = {{
        "x": jax.device_put(x, NamedSharding(mesh_a, P("data", "model"))),
        "y": jax.device_put(y, NamedSharding(mesh_a, P("data"))),
        "s": jnp.asarray(3, jnp.int32),
    }}
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        state)
    sh_b = {{
        "x": NamedSharding(mesh_b, P("model", "data")),  # different axes!
        "y": NamedSharding(mesh_b, P(None)),
        "s": NamedSharding(mesh_b, P()),
    }}

    # raw codec: restore must be bit-exact
    d = r"{tmp_path}" + "/raw"
    mgr = CheckpointManager(d, io_threads=4)
    mgr.save(1, state)
    r, _ = mgr.restore(like=like, shardings=sh_b)
    assert np.array_equal(np.asarray(r["x"]), np.asarray(x))
    assert np.array_equal(np.asarray(r["y"]), np.asarray(y))
    assert int(r["s"]) == 3
    r2, _ = mgr.restore()  # numpy (no template) restore, same bytes
    assert np.array_equal(r2["x"], np.asarray(x))

    # device codec: restore within quantization tolerance, same layout rules
    d2 = r"{tmp_path}" + "/dev"
    mgr2 = CheckpointManager(d2, device_codec=True)
    mgr2.save(1, state)
    r3, _ = mgr2.restore(like=like, shardings=sh_b)
    w0, w1 = np.asarray(x), np.asarray(r3["x"])
    assert w1.shape == w0.shape
    assert np.abs(w0 - w1).max() <= np.abs(w0).max() / 127.0 * 0.51 + 1e-6
    assert np.array_equal(np.asarray(r3["y"]), np.asarray(y))  # ints exact
    print("cross-layout restore OK")
    """, devices=8)


@pytest.mark.slow
def test_sharded_training_matches_single_device(tmp_path):
    """(2 data, 2 model) training == single-device training (same seeds)."""
    _run("""
    import jax, numpy as np
    from repro.data import make_pipeline
    from repro.models import get_config
    from repro.sharding.api import mesh_context, resolve
    from repro.sharding.rules import state_specs
    from repro.train import init_state, make_train_step

    cfg = get_config("mixtral-8x7b", tiny=True)
    key = jax.random.PRNGKey(0)
    step = jax.jit(make_train_step(cfg, total_steps=10))
    ref = init_state(cfg, key)
    data = make_pipeline(cfg, 16, 4)
    for _ in range(4):
        ref, m = step(ref, data.next_batch())
    ref_loss = float(m["loss"])

    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(2, 2)
    specs = state_specs(cfg, 2)
    sh = jax.tree.map(lambda s: resolve(s, mesh), specs,
                      is_leaf=lambda x: x.__class__.__name__ == "PartitionSpec")
    data2 = make_pipeline(cfg, 16, 4)
    with mesh_context(mesh):
        st = jax.jit(lambda: init_state(cfg, key), out_shardings=sh)()
        step_m = jax.jit(make_train_step(cfg, total_steps=10,
                                         param_specs=specs["params"]),
                         out_shardings=(sh, None))
        for _ in range(4):
            st, m2 = step_m(st, data2.next_batch())
    got = float(m2["loss"])
    # bf16 reduction-order noise between mesh layouts; measured ~1.4e-2
    # on this XLA/CPU version after 4 steps
    assert abs(got - ref_loss) < 5e-2, (got, ref_loss)
    print("sharded == single", ref_loss, got)
    """, devices=4)


@pytest.mark.slow
def test_delta_save_of_a_sharded_state(tmp_path):
    """Delta saves hash every device shard; shards on different devices
    must not meet in one jitted dispatch.  Full then delta save of a (2,2)
    sharded state restores bit-exact."""
    _run(f"""
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import CheckpointManager
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(2, 2)
    sh = NamedSharding(mesh, P("data", "model"))
    x = jax.device_put(jnp.arange(512 * 512, dtype=jnp.float32)
                       .reshape(512, 512), sh)
    m = CheckpointManager({str(tmp_path)!r}, delta=True, delta_block=1024,
                          fsync="none")
    m.save(1, {{"x": x}})
    y = x.at[0, 0].add(1.0)
    st = m.save(2, {{"x": y}})
    assert st.kind == "delta", st
    got, _, step, _ = m.restore_latest(like={{"x": y}})
    assert step == 2
    np.testing.assert_array_equal(np.asarray(got["x"]), np.asarray(y))
    """, devices=4)


def test_dryrun_single_cell_compiles():
    """End-to-end proof on the real 512-device production mesh (slow)."""
    _run("""
    from repro.launch.dryrun import run_cell
    rec = run_cell("gemma-7b", "train_4k", multi_pod=True)
    assert rec["status"] == "ok", rec
    print("multi-pod cell ok:", rec["cost"]["flops_per_device"])
    """, devices=512, timeout=900)


def test_largest_grid():
    from repro.core import largest_grid
    assert largest_grid(8, 2) == (4, 2)
    assert largest_grid(6, 4) == (2, 3)   # model shrinks to a divisor
    assert largest_grid(5, 2) == (5, 1)


def test_largest_grid_no_survivors_is_a_clear_error():
    from repro.core import NoSurvivorsError, largest_grid
    with pytest.raises(NoSurvivorsError):
        largest_grid(0, 2)                # used to be ZeroDivisionError
    with pytest.raises(NoSurvivorsError):
        largest_grid(-1, 1)


def test_survivor_mesh_fraction_and_empty():
    """A float failed fraction excludes round(f * n) devices (0.5 really
    halves the fleet) and losing everything raises NoSurvivorsError."""
    _run("""
    import jax, pytest
    from repro.core import NoSurvivorsError, survivor_mesh

    n = len(jax.devices())
    assert n == 8
    m = survivor_mesh(0.5, model_axis=2)          # half the devices fail
    assert m.devices.size == 4, m.devices.shape
    m = survivor_mesh(0.25, model_axis=2)
    assert m.devices.size == 6                    # 8 - round(2)
    m = survivor_mesh(2, model_axis=2)            # int: a device count
    assert m.devices.size == 6
    try:
        survivor_mesh(8, model_axis=2)            # all failed
        raise SystemExit("expected NoSurvivorsError")
    except NoSurvivorsError:
        pass
    try:
        survivor_mesh([], model_axis=2)           # empty explicit list
        raise SystemExit("expected NoSurvivorsError")
    except NoSurvivorsError:
        pass
    print("survivor_mesh fraction OK")
    """, devices=8)


def test_rescale_global_batch_keeps_per_replica_constant():
    from repro.core import rescale_global_batch
    # shrink: 8 DP -> 6 DP, per-replica 4 stays constant
    assert rescale_global_batch(32, 8, 6) == 24
    # grow: 6 DP -> 8 DP
    assert rescale_global_batch(24, 6, 8) == 32
    # round trip is lossless (the old code rounded the global batch down)
    assert rescale_global_batch(rescale_global_batch(32, 8, 6), 6, 8) == 32
    with pytest.raises(ValueError):
        rescale_global_batch(30, 8, 6)    # 30 doesn't divide over 8
    with pytest.raises(ValueError):
        rescale_global_batch(32, 8, 0)


def test_largest_grid_legal_widths_regression():
    """Satellite regression: `model = min(model_axis, n)` used to pick a
    width that divides nothing; the legal-divisor form must degrade to the
    widest LEGAL divisor and raise a clear error when none exists."""
    from repro.core import NoLegalGridError, largest_grid
    # degrade to the largest divisor in the legal set
    assert largest_grid(8, 4, legal=(1, 2, 4)) == (2, 4)
    assert largest_grid(6, 4, legal=(1, 2)) == (3, 2)
    assert largest_grid(5, 4, legal=(1, 2, 4)) == (5, 1)
    # no legal width divides n -> error, never a silently-broken grid
    with pytest.raises(NoLegalGridError, match="no legal width divides 5"):
        largest_grid(5, 4, legal=(2, 4))
    with pytest.raises(NoLegalGridError):
        largest_grid(8, 4, legal=())      # empty legal set


def test_rescale_global_batch_3d_oracle_sweep():
    """Satellite oracle: per-replica batch is a function of dp width ONLY.
    Sweeping (dp, tp, ep) grids, rescaling between any two grids with the
    same dp is the identity, and between different dp widths preserves the
    per-replica batch — tp/ep must never leak into the scaling (the
    total-device-count bug this satellite fixes)."""
    from repro.core import rescale_global_batch
    grids = [(dp, tp, ep) for dp in (1, 2, 4, 8)
             for tp in (1, 2, 4) for ep in (1, 2)]
    per_replica = 4
    for (dp0, tp0, ep0) in grids:
        gb0 = per_replica * dp0
        for (dp1, tp1, ep1) in grids:
            got = rescale_global_batch(gb0, dp0, dp1)
            assert got == per_replica * dp1, ((dp0, tp0, ep0),
                                              (dp1, tp1, ep1), got)
            # identity whenever dp is unchanged, whatever tp/ep did
            if dp0 == dp1:
                assert got == gb0


def test_rescale_global_batch_for_mesh_reads_dp_axis():
    """The mesh-aware wrapper reads the "data" axis width off the mesh
    itself, so a 3D mesh's model/expert axes cannot skew the batch."""
    _run("""
    import jax
    from repro.core import (MeshSpec, rescale_global_batch_for_mesh,
                            survivor_mesh3d)

    spec = MeshSpec(data=4, model=2, expert=1, legal_model=(1, 2))
    m_a = survivor_mesh3d(jax.devices(), spec)            # (4, 2, 1)
    spec_b = MeshSpec(data=2, model=2, expert=2, legal_model=(1, 2),
                      num_experts=8)
    m_b = survivor_mesh3d(jax.devices(), spec_b)          # (2, 2, 2)
    # 8 devices either way; dp differs (4 vs 2): batch follows dp alone
    assert rescale_global_batch_for_mesh(16, m_a, m_b) == 8
    assert rescale_global_batch_for_mesh(8, m_b, m_a) == 16
    # same dp, ep folded away: identity
    spec_c = MeshSpec(data=2, model=2, expert=1, legal_model=(1, 2))
    m_c = survivor_mesh3d(jax.devices()[:4], spec_c)      # (2, 2, 1)
    assert rescale_global_batch_for_mesh(8, m_b, m_c) == 8
    print("rescale_for_mesh OK")
    """, devices=8)
