"""int8 codec + error-feedback gradient compression properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests; see requirements-dev.txt
from hypothesis import given, settings, strategies as st
from hypothesis.extra import numpy as hnp

from repro.core.codec import CODECS
from repro.optim import dequantize_int8, ef_state_init, quantize_int8

arrays = hnp.arrays(np.float32, hnp.array_shapes(min_dims=1, max_dims=3,
                                                 max_side=64),
                    elements=st.floats(-1e4, 1e4, width=32))


@given(x=arrays)
@settings(max_examples=60, deadline=None)
def test_numpy_codec_roundtrip_bounded(x):
    codec = CODECS["int8"]
    payload, meta = codec.encode(x)
    y = codec.decode(payload, meta)
    assert y.shape == x.shape
    # per-block bound: |err| <= blockmax/127 * 0.5 (+ tiny eps)
    err = np.abs(y - x)
    bound = max(np.abs(x).max() / 127.0, 1e-9) * 0.51 + 1e-6
    assert err.max() <= bound


@given(x=arrays)
@settings(max_examples=40, deadline=None)
def test_jnp_codec_matches_numpy_codec(x):
    codec = CODECS["int8"]
    payload, meta = codec.encode(x)
    y_np = codec.decode(payload, meta)
    q, s, m = quantize_int8(jnp.asarray(x))
    y_jnp = np.asarray(dequantize_int8(q, s, m))
    np.testing.assert_allclose(y_np, y_jnp, atol=1e-5, rtol=1e-5)


def test_error_feedback_unbiased_over_time():
    """EF: the running sum of compressed gradients converges to the true
    running sum (residual stays bounded)."""
    from repro.optim.compress import dequantize_int8 as dq
    from repro.optim.compress import quantize_int8 as qz

    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32))
    ef = jnp.zeros_like(g_true)
    acc = jnp.zeros_like(g_true)
    for _ in range(50):
        g_eff = g_true + ef
        q, s, m = qz(g_eff)
        deq = dq(q, s, m)
        ef = g_eff - deq
        acc = acc + deq
    # after T steps, acc ~ T * g_true with bounded residual
    np.testing.assert_allclose(np.asarray(acc) / 50, np.asarray(g_true),
                               atol=np.abs(g_true).max() / 100)
    assert np.abs(np.asarray(ef)).max() <= np.abs(np.asarray(g_true)).max() \
        / 127 + 1e-5


def test_compressed_psum_in_shard_map():
    """compressed_psum under shard_map equals the plain mean within
    quantization tolerance (single device: group of 1)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.optim import compressed_psum, ef_state_init

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    grads = {"w": jnp.linspace(-2, 2, 256)}
    ef = ef_state_init(grads)

    def f(g, e):
        return compressed_psum(g, e, "data")

    sm = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    red, new_ef = sm(grads, ef)
    np.testing.assert_allclose(np.asarray(red["w"]),
                               np.asarray(grads["w"]), atol=2e-2)
