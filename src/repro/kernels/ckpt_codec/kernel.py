"""int8 block quantize/dequantize kernels (pl.pallas_call + BlockSpec).

The paper-aligned kernel: DeLIA's dominant runtime cost is serializing the
application state (the Young/Daly C term).  Quantizing fp32 state to int8 +
per-block fp32 scales on-device shrinks the device->host snapshot and the
bytes the writer thread pushes to the parallel FS by ~3.9x.  The same codec
compresses DP gradients (repro/optim/compress.py is the jnp twin).

Layout: values are viewed as (n_blocks, BLOCK=256); each grid step processes
a (ROWS x BLOCK) VMEM tile, emitting int8 payloads and fp32 scales.  Block
counts that are not a ROWS multiple are zero-padded up to one (a zero block
quantizes to q=0 / scale=0) and sliced back after the call, so every grid
step runs the same full-size tile instead of degrading to 1-row tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BLOCK = 256
ROWS = 64


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...]                                    # (ROWS, BLOCK) f32
    amax = jnp.abs(x).max(axis=1, keepdims=True)      # (ROWS, 1)
    scale = amax / 127.0
    safe = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x / safe), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.broadcast_to(scale, s_ref.shape)


def _dequant_kernel(q_ref, s_ref, y_ref):
    q = q_ref[...].astype(jnp.float32)
    y_ref[...] = q * s_ref[:, :1]


def _pad_rows(arrs, nb):
    """Zero-pad leading dim of each array from nb up to a ROWS multiple."""
    pad = (-nb) % ROWS
    if pad:
        arrs = [jnp.pad(a, ((0, pad), (0, 0))) for a in arrs]
    return arrs, nb + pad


def quantize_blocks(x, *, interpret=False):
    """x: (NB, BLOCK) f32 -> (q (NB, BLOCK) int8, scales (NB, 128) f32 —
    scale value broadcast across the lane dim; column 0 is canonical).

    Any NB is accepted: the grid always runs (ROWS x BLOCK) tiles over a
    zero-padded view, then slices back to NB rows."""
    nb = x.shape[0]
    (x,), nbp = _pad_rows([x], nb)
    grid = (nbp // ROWS,)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 128), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((nbp, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
    if nbp != nb:
        q, s = q[:nb], s[:nb]
    return q, s


def dequantize_blocks(q, scales, *, interpret=False):
    """q: (NB, BLOCK) int8, scales: (NB, 128) f32 -> (NB, BLOCK) f32.

    Like quantize_blocks, NB is padded to a ROWS multiple for the grid."""
    nb = q.shape[0]
    (q, scales), nbp = _pad_rows([q, scales], nb)
    grid = (nbp // ROWS,)
    y = pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 128), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, BLOCK), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(q, scales)
    return y[:nb] if nbp != nb else y
