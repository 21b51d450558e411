"""SDC guard end-to-end + unit coverage (docs/sdc.md).

The acceptance loop: inject a bit-flip mid-run -> a detection tier names
it -> run_with_recovery rolls back to the last checksum-verified
checkpoint -> training reconverges bit-exactly with the uninterrupted
reference run.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CheckpointManager, CorruptionDetected, Dependability,
                        DependabilityConfig, FaultInjector, flip_bit,
                        run_with_recovery)
from repro.data import make_pipeline
from repro.models import get_config
from repro.obs import Observability
from repro.sdc import LossSentinel, StateScrubber, leaf_checksum, named_leaves
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


def _param_leaf(state, contains):
    return [n for n, _ in named_leaves(state)
            if n.startswith("params.") and contains in n][0]


# ---------------------------------------------------------------------------
# bit-flip injection
# ---------------------------------------------------------------------------

def test_flip_bit_is_a_deterministic_involution():
    x = jax.random.normal(KEY, (4, 8))
    y = flip_bit(x, 30)
    assert not np.array_equal(np.asarray(x), np.asarray(y))
    # exactly one element differs, and flipping again restores the original
    assert int(np.sum(np.asarray(x) != np.asarray(y))) == 1
    z = flip_bit(y, 30)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(z))


def test_flip_bit_range_checked():
    x = jax.random.normal(KEY, (2, 2))
    with pytest.raises(IndexError):
        flip_bit(x, 2 * 2 * 4 * 8)


def test_injector_applies_scheduled_flip_once():
    inj = FaultInjector()
    inj.schedule_bitflip(3, "a.b", 5)
    state = {"a": {"b": jax.random.normal(KEY, (16,))}, "c": np.arange(4)}
    same = inj.apply_sdc(2, state)
    assert same is state                       # nothing due at step 2
    hit = inj.apply_sdc(3, state)
    assert not np.array_equal(np.asarray(hit["a"]["b"]),
                              np.asarray(state["a"]["b"]))
    np.testing.assert_array_equal(np.asarray(hit["c"]), state["c"])
    assert inj.sdc_injected == [(3, "a.b", 5)]
    again = inj.apply_sdc(3, hit)              # popped: applies only once
    assert again is hit


def test_injector_unknown_leaf_raises():
    inj = FaultInjector()
    inj.schedule_bitflip(1, "nope", 0)
    with pytest.raises(KeyError):
        inj.apply_sdc(1, {"a": np.zeros(4)})


# ---------------------------------------------------------------------------
# tier 2: state scrubber
# ---------------------------------------------------------------------------

def test_leaf_checksum_detects_single_bit_flip():
    for shape in [(64,), (3, 5)]:
        x = jax.random.normal(KEY, shape)
        for bit in (0, 17, 30, 31):
            assert leaf_checksum(x) != leaf_checksum(flip_bit(x, bit))


def _oracle_case(shape, dtype):
    rng = np.random.default_rng(sum(shape) + 1)
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        a = rng.integers(info.min, info.max, shape, dtype=np.int64)
    else:
        a = rng.standard_normal(shape)
    return jnp.asarray(np.asarray(a).astype(dtype))


# leaves of more than one block whose size is not a multiple of it, a
# minor dim that does not divide it, narrow dtypes, a 0-d leaf
@pytest.mark.parametrize("shape,dtype", [
    ((97, 4096), jnp.float32), ((70001,), jnp.float32),
    ((3, 5, 7000), jnp.float32), ((300, 1000), jnp.bfloat16),
    ((513, 129), jnp.int8), ((), jnp.int32)])
def test_device_checksum_matches_host_oracle(shape, dtype):
    """The one-pass device checksum equals the sum of the host oracle's
    block hashes, and a flipped bit at the first word, the last word and
    inside the last partial block changes it (to the oracle's value)."""
    from repro.kernels.block_hash.ops import BLOCK_ELEMS
    from repro.kernels.block_hash.ref import checksum_np
    from repro.sdc.checksum import _device_sums

    x = _oracle_case(shape, dtype)
    want = checksum_np(np.asarray(x))
    assert int(jax.device_get(_device_sums([x]))[0]) == want
    assert leaf_checksum(x) == want
    bits = x.dtype.itemsize * 8
    tail = (x.size // BLOCK_ELEMS) * BLOCK_ELEMS
    for elem in (0, x.size - 1, (tail + x.size) // 2):
        y = flip_bit(x, elem * bits + bits - 1)
        assert leaf_checksum(y) == checksum_np(np.asarray(y)) != want


@pytest.mark.parametrize("k", [1, 4, 9])
def test_checksum_program_returns_one_array_read_once(k, monkeypatch):
    """k device leaves: ``_device_sums`` returns one (k,) uint32 array,
    and ``launch(leaves)()`` reads it with one ``jax.device_get``, host
    leaves (crc32) kept in their place."""
    from repro.kernels.block_hash.ref import checksum_np
    from repro.sdc.checksum import _device_sums, _host_crc, launch

    dev = [_oracle_case((i + 1, 33), jnp.float32) for i in range(k)]
    out = _device_sums(dev)
    assert out.dtype == jnp.uint32 and out.shape == (k,)
    host = np.arange(5, dtype=np.float32)
    calls = []
    get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda v: calls.append(v) or get(v))
    got = launch([host] + dev)()
    assert len(calls) == 1
    assert got == [_host_crc(host)] + [checksum_np(np.asarray(x))
                                       for x in dev]


def test_checksum_program_reads_the_leaf_in_its_own_shape():
    """The scrub's checksum is one reduction over the leaf as it is: for an
    embedding-like leaf (its minor dim not a divisor of the 65,536-word
    block) the program pads nothing and reshapes nothing into block rows."""
    import re

    from repro.sdc.checksum import _device_sums

    leaf = jax.ShapeDtypeStruct((3, 4096 + 8), jnp.float32)
    text = _device_sums.lower([leaf]).as_text()
    assert "stablehlo.pad" not in text
    assert not re.search(r"tensor<[0-9x]*65536x", text)
    assert "stablehlo.reshape" not in text


def test_scrubber_pinpoints_corrupted_leaf():
    state = {"p": {"w1": jax.random.normal(KEY, (32,)),
                   "w2": jax.random.normal(jax.random.fold_in(KEY, 1), (32,))},
             "step": np.int32(7)}
    scr = StateScrubber(fraction=1.0)
    scr.record(state, step=0)
    assert scr.verify(state) == []             # untouched state is clean
    bad = dict(state, p=dict(state["p"], w2=flip_bit(state["p"]["w2"], 40)))
    assert scr.verify(bad) == ["p.w2"]


def test_scrubber_rotation_covers_all_leaves():
    state = {f"w{i}": jnp.full((4,), float(i), jnp.float32) for i in range(8)}
    scr = StateScrubber(fraction=0.25)         # 2 of 8 leaves per record
    scr.obs = Observability()
    seen = set()
    for s in range(4):
        seen.update(scr.record(state, s))
    assert len(seen) == 8                      # full sweep after 1/f steps
    # every leaf checksummed once: 8 leaves of 4 float32
    assert scr.obs.registry.counter("sdc.checksummed_bytes").value == 8 * 16


def test_scrubber_counts_the_device_bytes_it_checksums():
    """``sdc.checksummed_bytes`` is the bytes of the device leaves of each
    subset handed to the checksum program, in ``record`` and ``verify``
    alike; host leaves take the crc32 path and are not counted.  Each
    call opens ``sdc.leaves``, ``sdc.reduce`` and ``sdc.fetch`` in turn."""
    state = {"a": jnp.ones((3, 5), jnp.float32),
             "b": jnp.ones((64,), jnp.bfloat16),
             "c": jnp.arange(7, dtype=jnp.int32),
             "h": np.ones((9,), np.float64)}
    obs = Observability()
    scr = StateScrubber(fraction=0.5)
    scr.obs = obs
    size = {n: (0 if isinstance(x, np.ndarray) else x.size * x.dtype.itemsize)
            for n, x in named_leaves(state)}
    want = 0
    for s in range(3):
        names = scr.record(state, s)
        assert scr.verify(state) == []
        want += 2 * sum(size[n] for n in names)
    assert obs.registry.counter("sdc.checksummed_bytes").value == want > 0
    order = [r[0] for r in obs.registry.spans.records()]
    assert order == ["sdc.leaves", "sdc.reduce", "sdc.fetch"] * 6


def test_scrubber_reset_clears_window():
    state = {"w": jax.random.normal(KEY, (16,))}
    scr = StateScrubber(fraction=1.0)
    scr.record(state, 0)
    scr.reset()
    # a "different" state verifies clean: no stale window to compare against
    assert scr.verify({"w": flip_bit(state["w"], 3)}) == []


# ---------------------------------------------------------------------------
# tier 3: loss sentinel
# ---------------------------------------------------------------------------

def test_sentinel_trips_on_nonfinite():
    s = LossSentinel(warmup=0)
    assert s.observe(1, 1.0) is None
    assert "non-finite" in s.observe(2, float("nan"))
    assert "non-finite" in s.observe(3, 1.0, grad_norm=float("inf"))
    assert "non-finite" in s.observe(4, 1.0, nonfinite=1.0)


def test_sentinel_trips_on_spike_and_keeps_ema_clean():
    s = LossSentinel(spike_factor=10.0, warmup=2)
    for i in range(4):
        assert s.observe(i, 2.0) is None
    ema_before = s.loss_ema
    assert "spike" in s.observe(5, 2000.0)
    assert s.loss_ema == ema_before            # anomaly never enters the EMA
    assert s.observe(6, 2.1) is None           # replayed healthy step passes
    assert s.trips == 1


def test_sentinel_warmup_suppresses_spike():
    s = LossSentinel(spike_factor=2.0, warmup=10)
    assert s.observe(0, 1.0) is None
    assert s.observe(1, 100.0) is None         # still warming up


# ---------------------------------------------------------------------------
# restore walk-back (satellite: CRC-mismatch fallback)
# ---------------------------------------------------------------------------

def _corrupt_a_shard(ckpt_dir, step):
    [shard] = glob.glob(os.path.join(ckpt_dir, f"step_{step:08d}",
                                     "p.w*.npy"))[:1]
    with open(shard, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        last = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([last[0] ^ 0xFF]))


def test_restore_latest_walks_back_past_corrupt_checkpoint(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    state = {"p": {"w": jax.random.normal(KEY, (128,))}}
    states = {}
    for s in (2, 4, 6):
        state = {"p": {"w": state["p"]["w"] + 1.0}}
        states[s] = np.asarray(state["p"]["w"])
        mgr.save(s, state)
    _corrupt_a_shard(str(tmp_path), 6)
    got, local, step, skipped = mgr.restore_latest(like=state)
    assert step == 4
    assert [s for s, _ in skipped] == [6]
    assert "CRC" in skipped[0][1]
    np.testing.assert_array_equal(np.asarray(got["p"]["w"]), states[4])
    mgr.close()


def test_restore_latest_all_corrupt_raises_with_detail(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"p": {"w": jax.random.normal(KEY, (128,))}}
    for s in (1, 2):
        mgr.save(s, state)
        _corrupt_a_shard(str(tmp_path), s)
    with pytest.raises(FileNotFoundError, match="skipped"):
        mgr.restore_latest(like=state)
    mgr.close()


def test_dependability_restore_surfaces_skipped(tmp_path):
    dep = _dep(tmp_path)
    state = {"p": {"w": jax.random.normal(KEY, (128,))}}
    dep.save(2, state)
    dep.save(4, state)
    _corrupt_a_shard(str(tmp_path), 4)
    got, step = dep.restore_latest(like=state)
    assert step == 2
    assert [s for s, _ in dep.last_restore_skipped] == [4]
    dep.stop()


# ---------------------------------------------------------------------------
# end-to-end: inject -> detect -> rollback -> reconverge
# ---------------------------------------------------------------------------

def test_scrub_detects_bitflip_and_recovery_reconverges(tmp_path):
    cfg = get_config("granite-3-8b", tiny=True)
    steps = 9
    ref_state, ref_loss = _run_reference(cfg, steps)

    step_fn = jax.jit(make_train_step(cfg, total_steps=steps))
    state = init_state(cfg, KEY)
    leaf = _param_leaf(state, "attn.wk")
    data = make_pipeline(cfg, 16, 4)
    dep = _dep(tmp_path, scrub=True, scrub_fraction=1.0)
    dep.register_local_state(data)
    injector = FaultInjector()
    injector.schedule_bitflip(5, leaf, bit=30)
    state, info = run_with_recovery(dep, step_fn, state, data, steps,
                                    fault_injector=injector, like=state,
                                    max_restarts=3)
    assert info["status"] == "done"
    assert info["restarts"] == 1
    events = [h["event"] for h in info["history"] if "event" in h]
    # the scrubber pinpoints the corrupted leaf by name
    assert events == [f"corruption:scrub:{leaf}"]
    # rollback went to a scrub-verified checkpoint
    assert dep.verified_steps
    # reconvergence is bit-exact with the uninterrupted run
    last_loss = [h["loss"] for h in info["history"] if "loss" in h][-1]
    assert last_loss == ref_loss
    for a, b in zip(jax.tree.leaves(ref_state["params"]),
                    jax.tree.leaves(state["params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    dep.stop()


def test_repeat_corruption_walks_back_past_suspect_checkpoint(tmp_path):
    """When corruption re-trips after a rollback with no new checkpoint in
    between, the checkpoint recovery rolled back to is suspect (a flip the
    scrubber missed before the save has CRCs that verify fine) — recovery
    must exclude it and walk one checkpoint further back instead of
    livelocking on it until max_restarts."""
    cfg = get_config("granite-3-8b", tiny=True)
    steps = 9
    step_fn = jax.jit(make_train_step(cfg, total_steps=steps))
    state = init_state(cfg, KEY)
    leaf = _param_leaf(state, "attn.wq")
    data = make_pipeline(cfg, 16, 4)
    dep = _dep(tmp_path, scrub=True, scrub_fraction=1.0)
    dep.register_local_state(data)
    # flip at 5 -> detected, rollback to ckpt@4, replay; flip at 6 ->
    # detected again before any new checkpoint: ckpt@4 is now suspect and
    # excluded, so the second rollback must restore ckpt@2
    injector = FaultInjector()
    injector.schedule_bitflip(5, leaf, bit=30)
    injector.schedule_bitflip(6, leaf, bit=31)
    state, info = run_with_recovery(dep, step_fn, state, data, steps,
                                    fault_injector=injector, like=state,
                                    max_restarts=4)
    assert info["status"] == "done"
    assert info["restarts"] == 2
    events = [h["event"] for h in info["history"] if "event" in h]
    assert len(events) == 2
    assert all(ev.startswith("corruption:scrub:") for ev in events)
    # restored from ckpt@2 the second time (ckpt@4 excluded): the replay
    # after the last corruption event starts at step 3
    replayed = [h["step"] for h in info["history"] if "loss" in h]
    assert replayed[0] == 3
    # the run reconverges to the reference despite the double hit
    _, ref_loss = _run_reference(cfg, steps)
    last_loss = [h["loss"] for h in info["history"] if "loss" in h][-1]
    assert last_loss == ref_loss
    dep.stop()


def test_sentinel_catches_unscrubbed_flip_and_recovers(tmp_path):
    """Corruption in a leaf the scrubber never covers still gets caught by
    the tier-3 sentinel (non-finite loss) and rolled back."""
    cfg = get_config("granite-3-8b", tiny=True)
    steps = 8
    ref_state, ref_loss = _run_reference(cfg, steps)

    step_fn = jax.jit(make_train_step(cfg, total_steps=steps))
    state = init_state(cfg, KEY)
    leaf = _param_leaf(state, "final_norm")    # bit 30 -> NaN loss
    data = make_pipeline(cfg, 16, 4)
    dep = _dep(tmp_path, sentinel=True, sentinel_warmup=2)
    dep.register_local_state(data)
    injector = FaultInjector()
    injector.schedule_bitflip(5, leaf, bit=30)
    state, info = run_with_recovery(dep, step_fn, state, data, steps,
                                    fault_injector=injector, like=state,
                                    max_restarts=3)
    assert info["status"] == "done"
    assert info["restarts"] == 1
    events = [h["event"] for h in info["history"] if "event" in h]
    assert len(events) == 1 and events[0].startswith("corruption:sentinel:")
    last_loss = [h["loss"] for h in info["history"] if "loss" in h][-1]
    assert last_loss == ref_loss
    for a, b in zip(jax.tree.leaves(ref_state["params"]),
                    jax.tree.leaves(state["params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    dep.stop()
