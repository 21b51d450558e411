"""Per-kernel allclose sweeps vs the pure-jnp ref.py oracles (interpret
mode — kernel bodies execute on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("B,S,H,K,hd", [
    (1, 128, 4, 4, 32), (2, 256, 4, 2, 64), (1, 256, 8, 1, 64),
    (1, 512, 2, 2, 128),
])
@pytest.mark.parametrize("causal,window,softcap", [
    (True, 0, 0.0), (True, 64, 0.0), (True, 0, 30.0), (False, 0, 0.0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, K, hd, causal, window, softcap,
                               dtype):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref

    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, K, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, K, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, block_q=128, block_k=128,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window,
                        softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("B,S,Di,N", [
    (1, 64, 32, 4), (2, 128, 64, 8), (1, 256, 128, 16),
])
def test_selective_scan_sweep(B, S, Di, N):
    from repro.kernels.selective_scan.ops import selective_scan
    from repro.kernels.selective_scan.ref import selective_scan_ref

    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (B, S, Di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, Di))) * 0.1
    bm = jax.random.normal(ks[2], (B, S, N))
    cm = jax.random.normal(ks[3], (B, S, N))
    a = -jnp.exp(jax.random.normal(ks[4], (Di, N)) * 0.2)
    h0 = jax.random.normal(ks[5], (B, Di, N)) * 0.1
    y, h = selective_scan(x, dt, bm, cm, a, h0, block_c=32, chunk=32,
                          interpret=True)
    yr, hr = selective_scan_ref(x, dt, bm, cm, a, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-5,
                               rtol=1e-5)


@pytest.mark.parametrize("shape", [(255,), (256,), (1000,), (64, 256),
                                   (7, 13, 5),
                                   # block counts that are NOT a multiple of
                                   # the kernel's ROWS=64 tile (pad path)
                                   (100, 256), (65, 256), (300, 100),
                                   (16651,)])
@pytest.mark.parametrize("scale", [0.01, 1.0, 100.0])
def test_ckpt_codec_sweep(shape, scale):
    from repro.kernels.ckpt_codec.ops import dequantize, quantize
    from repro.kernels.ckpt_codec.ref import dequantize_ref, quantize_ref

    x = jax.random.normal(KEY, shape) * scale
    q, s = quantize(x, interpret=True)
    qr, sr = quantize_ref(x)
    assert np.array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    y = dequantize(q, s, shape, interpret=True)
    yr = dequantize_ref(qr, sr, shape)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-6)
    # quantization error bounded by half a quantization step per block
    err = np.abs(np.asarray(y) - np.asarray(x))
    assert err.max() <= np.abs(np.asarray(x)).max() / 127.0 * 0.5 + 1e-6


@pytest.mark.parametrize("nb", [1, 63, 64, 65, 100, 128, 130])
def test_ckpt_codec_blocks_any_row_count(nb):
    """Kernel-level check: quantize_blocks/dequantize_blocks handle any NB
    (ROWS-padding path) and match the block-level oracle exactly."""
    from repro.kernels.ckpt_codec.kernel import (dequantize_blocks,
                                                 quantize_blocks)
    from repro.kernels.ckpt_codec.ref import quantize_blocks_ref

    x = jax.random.normal(KEY, (nb, 256)) * 3.0
    q, s = quantize_blocks(x, interpret=True)
    assert q.shape == (nb, 256) and s.shape == (nb, 128)
    qr, sr = quantize_blocks_ref(x)
    assert np.array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s[:, 0]), np.asarray(sr),
                               rtol=1e-6)
    y = dequantize_blocks(q, s, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(qr, np.float32) * np.asarray(sr)[:, None],
        rtol=1e-6)


@pytest.mark.parametrize("shape", [(255,), (300, 100), (65, 256), (7, 13, 5)])
def test_device_codec_kernel_matches_numpy_codec(shape):
    """Acceptance: the on-device codec (interpret-mode Pallas kernel) round-
    trips within quantization tolerance of the numpy Int8BlockCodec for
    arbitrary leaf shapes, including nb % 64 != 0 — and produces the exact
    same payload bytes."""
    from repro.core.codec import DeviceCodec, Int8BlockCodec

    x = jax.random.normal(KEY, shape) * 5.0
    dc = DeviceCodec(use_kernel=True, interpret=True)
    q, s = dc.encode(x)
    codec = Int8BlockCodec()
    ref_payload, meta = codec.encode(np.asarray(x))
    nb = meta["blocks"]
    q_host = ref_payload[:nb * 256].view(np.int8).reshape(nb, 256)
    s_host = ref_payload[nb * 256:].view(np.float32)
    assert np.array_equal(np.asarray(q), q_host)       # int8 payload exact
    np.testing.assert_allclose(np.asarray(s), s_host,  # scales: XLA may fold
                               rtol=1e-6)              # /127 -> *(1/127)
    # device decode == numpy decode == original (within quant tolerance)
    y_dev = np.asarray(dc.decode(q, s, shape))
    y_np = codec.decode(ref_payload, meta)
    np.testing.assert_allclose(y_dev, y_np, rtol=1e-6, atol=1e-7)
    err = np.abs(y_np - np.asarray(x))
    assert err.max() <= np.abs(np.asarray(x)).max() / 127.0 * 0.5 + 1e-6


@pytest.mark.parametrize("M,K,N", [
    (8, 16, 8), (64, 96, 80), (128, 128, 128), (130, 200, 72),
])
def test_abft_matmul_matches_oracle(M, K, N):
    from repro.kernels.abft_matmul.ops import abft_matmul
    from repro.kernels.abft_matmul.ref import abft_matmul_ref

    a = jax.random.normal(KEY, (M, K))
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (K, N))
    c, rep = abft_matmul(a, b, interpret=True)
    ref = abft_matmul_ref(a, b)[:-1, :-1]
    np.testing.assert_allclose(np.asarray(c), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)
    # clean input: nothing detected, nothing "corrected"
    assert not bool(rep["detected"]) and not bool(rep["corrected"])


@pytest.mark.parametrize("i,j,delta", [(3, 7, 50.0), (0, 0, -200.0),
                                       (63, 79, 17.5)])
def test_abft_matmul_corrects_single_output_error(i, j, delta):
    """Acceptance: a single injected output-element error is located and
    corrected in place — the result matches the reference as if nothing
    happened (no rollback)."""
    from repro.kernels.abft_matmul.ops import abft_matmul
    from repro.kernels.abft_matmul.ref import abft_matmul_ref

    a = jax.random.normal(KEY, (64, 96))
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (96, 80))
    ref = abft_matmul_ref(a, b)[:-1, :-1]
    c, rep = abft_matmul(a, b, inject=(i, j, delta), interpret=True)
    assert bool(rep["detected"]) and bool(rep["corrected"])
    assert (int(rep["row"]), int(rep["col"])) == (i, j)
    np.testing.assert_allclose(float(rep["delta"]), delta, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(c), np.asarray(ref), rtol=1e-5,
                               atol=1e-4)


def test_abft_matmul_checksum_element_hit_leaves_data_intact():
    from repro.kernels.abft_matmul.ops import abft_matmul
    from repro.kernels.abft_matmul.ref import abft_matmul_ref

    a = jax.random.normal(KEY, (64, 96))
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (96, 80))
    ref = abft_matmul_ref(a, b)[:-1, :-1]
    for inject in ((64, 7, 50.0), (5, 80, 50.0)):  # checksum row / column
        c, rep = abft_matmul(a, b, inject=inject, interpret=True)
        assert bool(rep["detected"]) and bool(rep["corrected"])
        np.testing.assert_allclose(np.asarray(c), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_abft_matmul_double_error_detected_not_corrected():
    from repro.kernels.abft_matmul.ops import verify_and_correct
    from repro.kernels.abft_matmul.ref import abft_matmul_ref

    a = jax.random.normal(KEY, (64, 96))
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (96, 80))
    full = abft_matmul_ref(a, b)
    full = full.at[2, 3].add(40.0).at[5, 9].add(-30.0)
    _, rep = verify_and_correct(full)
    assert bool(rep["detected"]) and not bool(rep["corrected"])
    assert int(rep["bad_rows"]) == 2 and int(rep["bad_cols"]) == 2


def test_abft_dot_matches_plain_and_differentiates():
    from repro.kernels.abft_matmul.ops import abft_dot

    x = jax.random.normal(KEY, (2, 16, 96), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (96, 80), jnp.bfloat16)
    y = abft_dot(x, w)
    assert y.shape == (2, 16, 80) and y.dtype == x.dtype
    ref = (x.astype(jnp.float32) @ w.astype(jnp.float32)).astype(x.dtype)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32), rtol=2e-2,
                               atol=2e-2)
    # the custom VJP (checksummed backward contractions) matches plain grads
    f_abft = lambda w_: jnp.sum(abft_dot(x.astype(jnp.float32), w_) ** 2)
    f_ref = lambda w_: jnp.sum((x.astype(jnp.float32) @ w_) ** 2)
    wf = w.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(jax.grad(f_abft)(wf)),
                               np.asarray(jax.grad(f_ref)(wf)), rtol=1e-4,
                               atol=1e-3)


def test_mlp_abft_impl_matches_plain():
    from repro.layers.mlp import mlp_apply, mlp_init

    p = mlp_init(KEY, 64, 128, "silu", jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 8, 64))
    y_plain = mlp_apply(p, x, "silu", jnp.float32)
    y_abft = mlp_apply(p, x, "silu", jnp.float32, impl="abft")
    np.testing.assert_allclose(np.asarray(y_abft), np.asarray(y_plain),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape", [(4, 64), (2, 16, 128), (128, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    from repro.kernels.rmsnorm.ops import rms_norm
    from repro.kernels.rmsnorm.ref import rms_norm_ref

    x = jax.random.normal(KEY, shape, dtype)
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (shape[-1],),
                          jnp.float32)
    y = rms_norm(x, w, interpret=True)
    yr = rms_norm_ref(x, w)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=2e-2,
                               rtol=2e-2)


@pytest.mark.parametrize("shape", [
    (1000,), (40, 100), (33, 17, 29), (2048,), (65536,), (70000,),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32,
                                   jnp.int8])
@pytest.mark.parametrize("block_elems", [256, 1024])
def test_block_hash_sweep(shape, dtype, block_elems):
    """Pallas kernel AND jnp twin vs the numpy oracle — bit-exact uint32
    block hashes across dtypes, odd sizes, and tail blocks."""
    from repro.kernels.block_hash.ops import block_hashes
    from repro.kernels.block_hash.ref import block_hashes_np

    if jnp.issubdtype(dtype, jnp.floating):
        x = jax.random.normal(KEY, shape).astype(dtype)
    else:
        n = int(np.prod(shape))
        x = (jnp.arange(n, dtype=jnp.int32) % 251 - 125).astype(
            dtype).reshape(shape)
    ref = block_hashes_np(np.asarray(x), block_elems)
    ker = np.asarray(block_hashes(x, block_elems, use_kernel=True,
                                  interpret=True))
    twin = np.asarray(block_hashes(x, block_elems, use_kernel=False))
    assert ref.dtype == np.uint32 and ker.dtype == np.uint32
    assert ref.shape == (-(-int(np.prod(shape)) // block_elems),)
    np.testing.assert_array_equal(ker, ref)
    np.testing.assert_array_equal(twin, ref)


def test_block_hash_single_bit_flip_changes_exactly_one_hash():
    from repro.kernels.block_hash.ref import block_hashes_np

    x = np.asarray(jax.random.normal(KEY, (4096,)))
    base = block_hashes_np(x, 256)
    # k=31 at an odd word index is the adversarial case for a plain sum's
    # weighted variant: delta = 2^31 * weight — only an ODD weight keeps
    # it nonzero mod 2^32
    for (i, bit) in ((0, 0), (300, 13), (4095, 31), (1, 31)):
        y = x.copy()
        w = y.view(np.uint32)
        w[i] ^= np.uint32(1 << bit)
        h = block_hashes_np(y, 256)
        assert (h != base).sum() == 1
        assert np.nonzero(h != base)[0][0] == i // 256


def test_block_hash_detects_permutations_and_compensating_changes():
    """A plain word sum is permutation-invariant and blind to +d/-d pairs
    — real state updates a delta save must NOT treat as clean.  The odd
    position weights break both symmetries."""
    from repro.kernels.block_hash.ref import block_hashes_np

    x = np.arange(4096, dtype=np.float32)
    base = block_hashes_np(x, 256)
    # swap two unequal values inside one block
    y = x.copy()
    y[10], y[20] = x[20], x[10]
    assert not np.array_equal(block_hashes_np(y, 256), base)
    # compensating integer +d/-d inside one block (sum-preserving)
    z = np.arange(4096, dtype=np.int32)
    bz = block_hashes_np(z, 256)
    z2 = z.copy()
    z2[100] += 7
    z2[101] -= 7
    assert not np.array_equal(block_hashes_np(z2, 256), bz)


def test_block_hash_checksum_is_sum_of_block_hashes():
    """The scrubber's leaf checksum == uint32 sum of the delta-mode block
    hashes (at the same block size — position weights restart per block)
    — scrub and delta share one hash value, each in its own pass."""
    from repro.kernels.block_hash.ops import (BLOCK_ELEMS, block_hashes,
                                              checksum_words)
    from repro.kernels.block_hash.ref import checksum_np
    from repro.sdc.checksum import leaf_checksum

    x = jax.random.normal(KEY, (333, 77))
    hashes = np.asarray(block_hashes(x, BLOCK_ELEMS))
    total = int(hashes.sum(dtype=np.uint32))
    assert total == int(jax.device_get(checksum_words(x)))
    assert total == checksum_np(np.asarray(x))
    assert total == leaf_checksum(x)
    # the identity holds at every (matching) block size
    h256 = np.asarray(block_hashes(x, 256))
    assert int(h256.sum(dtype=np.uint32)) == checksum_np(np.asarray(x), 256)


# ---------------------------------------------------------------------------
# paged decode attention (serve memory stack, docs/serving.md)
# ---------------------------------------------------------------------------

def _paged_case(key, R, H, K, hd, ps, mpr, dtype, num_pages):
    """Random pool + tables: each row maps ``mpr`` distinct live pages
    (none the null page 0); lengths land in every page, including the
    last page's final slot (the fully-dead-trailing-page path falls out
    of short lengths)."""
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (R, 1, H, hd), dtype)
    k_pages = jax.random.normal(ks[1], (num_pages, ps, K, hd), dtype)
    v_pages = jax.random.normal(ks[2], (num_pages, ps, K, hd), dtype)
    perm = jax.random.permutation(ks[3], jnp.arange(1, num_pages))
    page_tables = perm[: R * mpr].reshape(R, mpr).astype(jnp.int32)
    lengths = (jnp.arange(R, dtype=jnp.int32) * 7) % (mpr * ps)
    lengths = lengths.at[-1].set(mpr * ps - 1)     # full table in play
    lengths = lengths.at[0].set(0)                 # single-position row
    return q, k_pages, v_pages, page_tables, lengths


@pytest.mark.parametrize("R,H,K,hd,ps,mpr", [
    (4, 4, 4, 32, 16, 4),    # MHA
    (3, 8, 2, 64, 16, 2),    # GQA 4:1
    (5, 4, 1, 64, 8, 3),     # MQA, small pages
])
@pytest.mark.parametrize("window,softcap", [
    (0, 0.0), (24, 0.0), (0, 30.0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_kernel_sweep(R, H, K, hd, ps, mpr, window,
                                      softcap, dtype):
    """Pallas page-table-chasing kernel vs the gather oracle, across
    head groupings, page sizes, windows, and softcap."""
    from repro.kernels.paged_attention.ops import paged_decode_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref

    num_pages = R * mpr + 3
    q, kp, vp, pt, ln = _paged_case(KEY, R, H, K, hd, ps, mpr, dtype,
                                    num_pages)
    out = paged_decode_attention(q, kp, vp, pt, ln, window=window,
                                 softcap=softcap, impl="pallas",
                                 interpret=True)
    ref = paged_attention_ref(q[:, 0], kp, vp, pt, ln, window=window,
                              softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("window", [0, 12])
def test_paged_attention_ref_impl_matches_oracle(window):
    """The production ``impl="ref"`` path (gather + the slot pool's exact
    decode_mha graph) agrees with the standalone oracle — the bridge that
    ties kernel sweeps to the engine's bit-identity contract."""
    from repro.kernels.paged_attention.ops import paged_decode_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref

    q, kp, vp, pt, ln = _paged_case(KEY, 4, 4, 2, 32, 8, 3, jnp.float32,
                                    4 * 3 + 2)
    out = paged_decode_attention(q, kp, vp, pt, ln, window=window,
                                 impl="ref")
    ref = paged_attention_ref(q[:, 0], kp, vp, pt, ln, window=window)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
