"""Public block-hash wrapper: arbitrary leaves -> per-block uint32 hashes.

``words_view`` is THE shared uint32 mod-2^32 reduction idiom: any leaf is
bitcast to a flat run of 32-bit storage words (2-byte dtypes zero-extend,
8-byte dtypes split into two words).  ``block_hashes`` reduces those words
per fixed-size *element* block with odd position weights (2j+1 — see
kernel.py for why a plain sum is too weak for dirty-block detection while
the weighted sum still catches every single-bit flip).

``checksum_words`` is the scrubber's whole-leaf checksum
(repro/sdc/checksum.py): the same words and the same weights, summed over
the whole leaf in one pass on the leaf's own shape.  Its value is the
uint32 sum of the leaf's block hashes, so scrub and CheckpointManager's
delta mode share the hash value, not the pass: the block view pads and
reshapes the words into rows, which the scrub needs neither of.

Backend selection mirrors core/codec.DeviceCodec: the Pallas kernel on TPU,
a jit'd jnp twin elsewhere (interpret-mode Pallas is only for tests — far
too slow for multi-MB leaves on CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.block_hash.kernel import hash_rows

BLOCK_ELEMS = 65536   # default delta block: 64 Ki elements (256 KiB fp32)


def _storage_words(x):
    """int32 storage words of a leaf of a 1-, 2- or 4-byte dtype, in the
    leaf's own shape: 4-byte words as they are, narrower ones
    zero-extended."""
    size = x.dtype.itemsize
    if size == 4:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    if size == 2:
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.int32)
    return jax.lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.int32)


def words_view(x):
    """Flat int32 view of a leaf's storage words (same bits the host-side
    oracle in ref.py hashes).  int32 rather than uint32 so the kernel's
    adds stay on the natively supported type; wraparound is identical."""
    x = x.reshape(-1)
    if x.dtype.itemsize == 8:
        # 8-byte dtypes bitcast to a trailing (..., 2) int32 axis
        return jax.lax.bitcast_convert_type(x, jnp.int32).reshape(-1)
    return _storage_words(x)


def words_per_element(dtype) -> int:
    """How many 32-bit words one element contributes in ``words_view``."""
    return 2 if jnp.dtype(dtype).itemsize == 8 else 1


def _default_use_kernel() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit,
                   static_argnames=("block_elems", "use_kernel", "interpret"))
def _block_hashes(x, block_elems, use_kernel, interpret):
    w = words_view(x)
    width = block_elems * words_per_element(x.dtype)
    pad = (-w.shape[0]) % width
    if pad:
        w = jnp.pad(w, (0, pad))
    rows = w.reshape(-1, width)
    if use_kernel:
        h = hash_rows(rows, interpret=interpret)
    else:
        weights = 2 * jnp.arange(width, dtype=jnp.int32) + 1
        h = jnp.sum(rows * weights[None, :], axis=1)  # int32: wraps mod 2^32
    return jax.lax.bitcast_convert_type(h.astype(jnp.int32), jnp.uint32)


def block_hashes(x, block_elems: int = BLOCK_ELEMS, *, use_kernel=None,
                 interpret=False):
    """x: device array, any shape/dtype -> (NB,) uint32 block hashes, still
    on device, where NB = ceil(x.size / block_elems) (the zero-padded tail
    block hashes its real words only)."""
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    return _block_hashes(x, int(block_elems), bool(use_kernel),
                         bool(interpret))


@functools.partial(jax.jit,
                   static_argnames=("block_elems", "use_kernel", "interpret"))
def _batched_block_hashes(leaves, block_elems, use_kernel, interpret):
    return [_block_hashes(x, block_elems, use_kernel, interpret)
            for x in leaves]


def batched_block_hashes(leaves, block_elems: int = BLOCK_ELEMS, *,
                         use_kernel=None, interpret=False):
    """Hash many leaves in ONE jitted dispatch (per-leaf dispatch overhead
    would rival the reduction itself on small states) — the save-path
    twin of sdc.checksum.checksums' batching."""
    if not leaves:
        return []
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    # one dispatch per device set: a jit takes its arguments from one set,
    # and the per-device shards of a sharded state each live on their own
    groups = {}
    for i, x in enumerate(leaves):
        groups.setdefault(frozenset(x.devices()), []).append(i)
    out = [None] * len(leaves)
    for idx in groups.values():
        hashes = _batched_block_hashes([leaves[i] for i in idx],
                                       int(block_elems), bool(use_kernel),
                                       bool(interpret))
        for i, h in zip(idx, hashes):
            out[i] = h
    return out


def _block_positions(shape, block_elems):
    """Each element's flat index mod ``block_elems`` (a power of two), in
    ``shape``: per-axis iotas times their strides, in wrapping int32 — the
    low bits of a sum or product depend only on the low bits of its
    terms, so the wraparound leaves them exact."""
    pos = jnp.zeros(shape, jnp.int32)
    stride = 1
    for ax in reversed(range(len(shape))):
        if stride % block_elems:
            pos = pos + (jax.lax.broadcasted_iota(jnp.int32, shape, ax)
                         * (stride % block_elems))
        stride *= shape[ax]
    return pos & (block_elems - 1)


def checksum_words(x):
    """Whole-leaf checksum, traceable inside a larger jit: the uint32 sum
    of every storage word times its block position weight (2j+1), which
    is the uint32 sum of the leaf's block hashes.  One reduction reads
    each word once, in the leaf's own shape: no padding, no reshape into
    block rows (on the TPU's tiled layouts that is a copy).  A single-bit
    flip changes exactly one block hash by a nonzero delta, hence the
    total.  8-byte dtypes take the block path."""
    if x.dtype.itemsize == 8:
        h = _block_hashes(x, BLOCK_ELEMS, False, False)
        s = jnp.sum(jax.lax.bitcast_convert_type(h, jnp.int32))
        return jax.lax.bitcast_convert_type(s.astype(jnp.int32), jnp.uint32)
    weights = 2 * _block_positions(x.shape, BLOCK_ELEMS) + 1
    s = jnp.sum(_storage_words(x) * weights, dtype=jnp.int32)  # wraps
    return jax.lax.bitcast_convert_type(s, jnp.uint32)
