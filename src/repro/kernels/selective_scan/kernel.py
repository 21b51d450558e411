"""Mamba-1 selective-scan kernel (pl.pallas_call + BlockSpec VMEM tiling).

TPU adaptation of the CUDA fused scan (DESIGN.md S2): grid =
(B, channel_blocks, time_chunks); the SSM state h (bc x N) stays resident in
VMEM scratch across the sequential time-chunk dim, so HBM traffic is
O(inputs + outputs + one state snapshot per chunk) instead of
O(S * Di * N).  Inside a chunk the recurrence steps over time with a
fori_loop on VMEM-resident tiles (VPU work; the surrounding projections are
the MXU work and live outside the kernel).

    h_t = exp(dt_t * A) h_{t-1} + (dt_t B_t) x_t ;  y_t = C_t . h_t
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, hout_ref,
            h_scr, *, q: int, nchunks: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    def body(t, h):
        dt_t = dt_ref[0, t, :]                     # (bc,)
        x_t = x_ref[0, t, :]                       # (bc,)
        b_t = b_ref[0, t, :]                       # (N,)
        c_t = c_ref[0, t, :]                       # (N,)
        a = jnp.exp(dt_t[:, None] * a_ref[...])    # (bc, N)
        h = a * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_ref[0, t, :] = (h * c_t[None, :]).sum(axis=1)
        return h

    h = lax.fori_loop(0, q, body, h_scr[...])
    h_scr[...] = h

    @pl.when(j == nchunks - 1)
    def _flush():
        hout_ref[0] = h


def selective_scan_kernel(x, dt, bm, cm, a, h0, *, block_c=512, chunk=128,
                          interpret=False):
    """x, dt: (B,S,Di) f32; bm, cm: (B,S,N) f32; a: (Di,N) f32;
    h0: (B,Di,N) f32.  Returns (y (B,S,Di) f32, h_last (B,Di,N) f32)."""
    B, S, Di = x.shape
    N = bm.shape[-1]
    bc = min(block_c, Di)
    q = min(chunk, S)
    assert Di % bc == 0 and S % q == 0, (Di, bc, S, q)
    ncb, nch = Di // bc, S // q

    grid = (B, ncb, nch)
    kern = functools.partial(_kernel, q=q, nchunks=nch)
    y, h_last = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, bc), lambda b, c, j: (b, j, c)),   # x
            pl.BlockSpec((1, q, bc), lambda b, c, j: (b, j, c)),   # dt
            pl.BlockSpec((1, q, N), lambda b, c, j: (b, j, 0)),    # B
            pl.BlockSpec((1, q, N), lambda b, c, j: (b, j, 0)),    # C
            pl.BlockSpec((bc, N), lambda b, c, j: (c, 0)),         # A
            pl.BlockSpec((1, bc, N), lambda b, c, j: (b, c, 0)),   # h0
        ],
        out_specs=[
            pl.BlockSpec((1, q, bc), lambda b, c, j: (b, j, c)),   # y
            pl.BlockSpec((1, bc, N), lambda b, c, j: (b, c, 0)),   # h_last
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, Di), jnp.float32),
            jax.ShapeDtypeStruct((B, Di, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bc, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, bm, cm, a, h0)
    return y, h_last
