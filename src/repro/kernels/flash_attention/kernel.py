"""Flash attention forward kernel (pl.pallas_call + BlockSpec VMEM tiling).

Grid: (B, H, num_q_blocks, num_kv_blocks); the kv-block dim is the innermost
sequential ("arbitrary") dim so the online-softmax state (m, l, acc) lives in
VMEM scratch across kv iterations.  GQA is handled in the k/v index_map
(kv head = q head // group).  MXU work: (bq x hd) @ (hd x bk) and
(bq x bk) @ (bk x hd) per grid cell — block sizes default to 256/512 so both
matmul dims are 128-aligned.

Supports: causal, sliding window, attention-logit softcap, custom scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
LANES = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, softcap: float,
            bq: int, bk: int, nk: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    iq = pl.program_id(2)
    if causal or window:
        qpos = iq * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ik * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = mask & (kpos <= qpos)
        if window:
            mask = mask & (qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, :1]                                # (bq, 1)
    l_prev = l_scr[:, :1]
    m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)                               # (bq, bk)
    l_cur = l_prev * corr + p.sum(axis=1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_cur, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _flush():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal=True, window=0, softcap=0.0,
                         scale=None, block_q=256, block_k=512,
                         interpret=False):
    """q: (B,H,S,hd); k,v: (B,K,Sk,hd).  Returns o: (B,H,S,hd)."""
    B, H, S, hd = q.shape
    K, Sk = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale else hd ** -0.5
    bq = min(block_q, S)
    bk = min(block_k, Sk)
    assert S % bq == 0 and Sk % bk == 0, (S, bq, Sk, bk)
    nq, nk = S // bq, Sk // bk

    grid = (B, H, nq, nk)
    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             window=window, softcap=softcap,
                             bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
