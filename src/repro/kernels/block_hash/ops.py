"""Public block-hash wrapper: arbitrary leaves -> per-block uint32 hashes.

``words_view`` is THE shared uint32 mod-2^32 reduction idiom: any leaf is
bitcast to a flat run of 32-bit storage words (2-byte dtypes zero-extend,
8-byte dtypes split into two words).  ``block_hashes`` reduces those words
per fixed-size *element* block with odd position weights (2j+1 — see
kernel.py for why a plain sum is too weak for dirty-block detection while
the weighted sum still catches every single-bit flip);
``checksum_words`` is the uint32 sum of those block hashes — so a leaf's
scrubber checksum IS the sum of its delta-block hashes, and one hashing
pass can serve both consumers (repro/sdc/checksum.py and
CheckpointManager's delta mode).

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


def words_view(x):
    """Flat int32 view of a leaf's storage words (same bits the host-side
    oracle in ref.py hashes).  int32 rather than uint32 so the kernel's
    adds stay on the natively supported type; wraparound is identical."""
    x = x.reshape(-1)
    size = x.dtype.itemsize
    if size == 4:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    if size == 2:
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.int32)
    if size == 1:
        return jax.lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.int32)
    # 8-byte dtypes bitcast to a trailing (..., 2) int32 axis
    return jax.lax.bitcast_convert_type(x, jnp.int32).reshape(-1)


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


def checksum_words(x, block_elems: int = BLOCK_ELEMS):
    """Whole-leaf checksum = uint32 sum of the leaf's block hashes — the
    scrubber's per-leaf checksum, traceable inside a larger jit.  Built
    from the SAME weighted block reduction delta mode uses, so one pass
    genuinely serves both (and a single-bit flip still changes exactly one
    block hash by a nonzero delta, hence the total)."""
    h = _block_hashes(x, block_elems, False, False)
    s = jnp.sum(jax.lax.bitcast_convert_type(h, jnp.int32))
    return jax.lax.bitcast_convert_type(s.astype(jnp.int32), jnp.uint32)
