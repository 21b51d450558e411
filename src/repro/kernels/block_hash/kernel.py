"""Per-block position-weighted mod-2^32 hash kernel (pl.pallas_call).

One pass over a leaf's storage words produces a hash per fixed-size block —
the primitive behind incremental ("delta") checkpointing (a block whose
hash matches the last committed checkpoint never crosses the device->host
link).  The SDC scrubber's leaf checksum is the mod-2^32 sum of these
block hashes, taken in a pass of its own over the leaf's own shape (see
ops.checksum_words and repro/sdc/checksum.py).

The hash is the wraparound int32 sum of each word MULTIPLIED by an odd
per-position weight (2j+1 for word j within its block):

- single-bit upset: flips word j by ±2^k, changing the hash by
  ±2^k * (2j+1) — an odd multiple of 2^k, never 0 mod 2^32 — so the
  scrubber's single-flip guarantee holds exactly as with a plain sum;
- real state updates: a plain sum is permutation-invariant and blind to
  compensating changes (swap two words, or +d/-d pairs — easy to hit when
  e.g. two embedding rows trade places inside one block), which would make
  delta mode silently reference STALE parent blocks; position weights
  break that symmetry (a swap of unequal words w_a, w_b at j_a != j_b
  shifts the hash by 2(w_a-w_b)(j_a-j_b), zero only on a 2^31 alignment).

Zero padding (rows to a ROWS multiple, words to a WTILE multiple) is free
— zero words contribute nothing regardless of weight.

Layout: words are viewed as (NB, W); each grid step reduces a
(ROWS x WTILE) VMEM tile into a (ROWS, 128) accumulator tile (all lanes
carry the row sum; column 0 is canonical).  The word axis is "arbitrary"
so partial sums accumulate across its tiles; each tile derives its
weights from the global word index (j * WTILE + iota).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


ROWS = 8        # block-hash rows per grid step (sublane tile)
WTILE = 2048    # words reduced per grid step along the word axis
LANES = 128


def _hash_kernel(w_ref, h_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    w = w_ref[...]
    # odd weight 2*(global word index)+1; int32 multiply/add wrap mod 2^32
    idx = j * WTILE + jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    part = jnp.sum(w * (2 * idx + 1), axis=1, keepdims=True)
    h_ref[...] += jnp.broadcast_to(part, h_ref.shape)


def hash_rows(w, *, interpret=False):
    """w: (NB, W) int32 word rows -> (NB,) int32 weighted row sums mod
    2^32.

    Any NB/W is accepted: rows are zero-padded to a ROWS multiple and the
    word axis to a WTILE multiple, then sliced back (zero words are
    sum-neutral)."""
    nb, width = w.shape
    padr = (-nb) % ROWS
    padw = (-width) % WTILE
    if padr or padw:
        w = jnp.pad(w, ((0, padr), (0, padw)))
    nbp, wp = nb + padr, width + padw
    grid = (nbp // ROWS, wp // WTILE)
    h = pl.pallas_call(
        _hash_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((ROWS, WTILE), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((ROWS, LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(w)
    return h[:nb, 0]
