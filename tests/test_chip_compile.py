"""Main-path Pallas kernels compiled for a described v5e chip.

Each test compiles one kernel at a published width for a TPU that is
described, not attached, and asserts the lowering is a Mosaic
``tpu_custom_call``: what the chip's compiler refuses (unaligned slices,
too much VMEM) fails here, with no chip.  Interpret-mode correctness lives
in test_kernels.py.  The scrubber's checksum program, XLA's own fusion and
no kernel, is compiled here too: on the chip's tiled layouts a copy of a
leaf shows only in the compiled program.

The topology is described only inside the module fixture, never at import
or collection: one process at a time may load the TPU library, and every
pytest worker imports this file.  Keep every described-chip compile in
this one file, so that one worker owns them all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

F32, BF16, I32, I8 = jnp.float32, jnp.bfloat16, jnp.int32, jnp.int8

# granite-3-8b: d_model 4096, 32 q / 8 kv heads of 128, d_ff 12800
D, DFF, H, KV, HD = 4096, 12800, 32, 8, 128
# falcon-mamba-7b: d_inner = 2 x 4096, ssm_state 16
DI, N = 8192, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile cannot be read back without a chip
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _block_hash(x):
    from repro.kernels.block_hash.ops import block_hashes
    return block_hashes(x, use_kernel=True)


def _quantize(x):
    from repro.kernels.ckpt_codec.ops import quantize
    return quantize(x, interpret=False)


def _dequantize(q, s):
    from repro.kernels.ckpt_codec.ops import dequantize
    return dequantize(q, s, (D, DFF), interpret=False)


def _rmsnorm(x, w):
    from repro.kernels.rmsnorm.ops import rms_norm
    return rms_norm(x, w, interpret=False)


def _flash(q, k, v):
    from repro.kernels.flash_attention.ops import flash_attention
    return flash_attention(q, k, v, causal=True, interpret=False)


def _abft(a, b):
    from repro.kernels.abft_matmul.ops import abft_matmul
    return abft_matmul(a, b, interpret=False)


def _paged(q, k, v, tables, lengths):
    from repro.kernels.paged_attention.ops import paged_decode_attention
    return paged_decode_attention(q, k, v, tables, lengths, impl="pallas",
                                  interpret=False)


def _scan(x, dt, bm, cm, a, h0):
    from repro.kernels.selective_scan.ops import selective_scan
    return selective_scan(x, dt, bm, cm, a, h0, interpret=False)


NB = D * DFF // 256            # int8 codec blocks of a 4096 x 12800 leaf
PAGES, PS, ROWS = 512, 16, 16  # paged pool: 512 pages of 16 tokens

# name -> (function, argument (shape, dtype)s)
CASES = {
    "block_hash_f32": (_block_hash, [((D, DFF), F32)]),
    "block_hash_bf16": (_block_hash, [((D, DFF), BF16)]),
    "ckpt_codec_quantize": (_quantize, [((D, DFF), F32)]),
    "ckpt_codec_dequantize": (_dequantize, [((NB, 256), I8), ((NB,), F32)]),
    "rmsnorm": (_rmsnorm, [((8192, D), BF16), ((D,), F32)]),
    "flash_attention": (_flash, [((1, 2048, H, HD), BF16),
                                 ((1, 2048, KV, HD), BF16),
                                 ((1, 2048, KV, HD), BF16)]),
    "abft_matmul": (_abft, [((2048, D), BF16), ((D, DFF), BF16)]),
    "paged_attention": (_paged, [((ROWS, 1, H, HD), BF16),
                                 ((PAGES, PS, KV, HD), BF16),
                                 ((PAGES, PS, KV, HD), BF16),
                                 ((ROWS, PAGES // ROWS), I32),
                                 ((ROWS,), I32)]),
    "selective_scan": (_scan, [((1, 2048, DI), F32), ((1, 2048, DI), F32),
                               ((1, 2048, N), F32), ((1, 2048, N), F32),
                               ((DI, N), F32), ((1, DI, N), F32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = CASES[name]
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, f"{name} did not lower to Mosaic"


# an embedding at granite-3-8b's published vocabulary (49155 rows: its size
# not a multiple of the 65,536-word block) and its MLP weight
@pytest.mark.parametrize("shape", [(49155, D), (D, DFF)])
def test_scrub_checksum_reads_the_leaf_in_place_on_v5e(one_chip, shape):
    """The checksum program reads the leaf where it lies: no pad, no
    relayout into block rows, and no temporary that grows with the leaf
    (a padded or relaid copy of a 805 MB embedding would be one)."""
    from repro.sdc.checksum import _device_sums

    leaf = jax.ShapeDtypeStruct(shape, F32, sharding=one_chip)
    compiled = _device_sums.lower([leaf]).compile()
    text = compiled.as_text()
    assert " pad(" not in text and " reshape(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
