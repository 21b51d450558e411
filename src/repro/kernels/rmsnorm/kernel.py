"""Fused RMSNorm kernel (pl.pallas_call + BlockSpec VMEM tiling).

One HBM read + one write per element (vs separate square/mean/rsqrt/mul HLO
ops); rows tiled (ROWS x D) into VMEM, fp32 accumulation."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


ROWS = 128


def _kernel(x_ref, w_ref, y_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                # (rows, D)
    var = jnp.mean(x * x, axis=1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    y_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(y_ref.dtype)


def rms_norm_2d(x, w, *, eps=1e-6, interpret=False):
    """x: (R, D); w: (D,) -> (R, D)."""
    R, D = x.shape
    rows = ROWS if R % ROWS == 0 else 1
    grid = (R // rows,)
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w)
