"""Checksum-extended matmul kernel (pl.pallas_call + BlockSpec MXU tiling).

Computes C_full = A_ext @ B_ext where the operands carry their ABFT
checksum row/column (see ref.py).  The checksums flow through the SAME
pallas_call / MXU path as the data, which is the point: a transient
compute error in any output tile perturbs the data and its checks
inconsistently and becomes detectable by the verifier in ops.py.

Standard 3-phase tiled matmul: grid (M/bm, N/bn, K/bk), fp32 accumulation
in the revisited output tile ("arbitrary" K dimension), zero-init on the
first K step.  ops.py pads the extended operands to tile multiples with
zeros (which contribute nothing to sums or products) and slices back.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BM = 128
BN = 128
BK = 128


def _kernel(a_ref, b_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                          preferred_element_type=jnp.float32)


def matmul_f32(a, b, *, interpret=False):
    """a: (M, K) f32, b: (K, N) f32 -> (M, N) f32; M, N, K tile multiples."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = min(BM, M), min(BN, N), min(BK, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (a.shape, b.shape)
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
