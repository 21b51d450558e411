"""Paged decode-attention kernel (PrefetchScalarGridSpec + online softmax).

One query token per request gathers its KV history through a per-request
page table instead of a contiguous cache row.  The page table and the
per-request lengths ride in as *scalar prefetch* operands, so the k/v
``index_map`` can chase ``page_tables[r, j]`` to pick which physical page
the next grid step streams into VMEM — the gather never materializes.

Grid: (R, K, num_pages_per_request); the page dim is the innermost
sequential ("arbitrary") dim so the online-softmax state (m, l, acc)
lives in VMEM scratch across page iterations, exactly like the kv-block
dim of ``flash_attention``.  Pages past a request's length resolve to
the null page 0 in its table; their logits are masked by the length
bound, so they only cost the (tiny) page stream.

Layout note: pages arrive as (P, K, ps, hd) — KV-head major — so each
grid cell streams one (ps, hd) tile per head, mirroring the (bk, hd)
kv tile of the flash kernel.
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


def _kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *,
            scale: float, window: int, softcap: float, ps: int, npages: int):
    r = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (ps, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)  # (G, ps)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    cur = len_ref[r]
    kpos = j * ps + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos <= cur                       # query sits at position cur
    if window:
        mask = mask & (cur - kpos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, :1]                                # (G, 1)
    l_prev = l_scr[:, :1]
    m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_cur)
    # mask p explicitly: a fully-dead page would otherwise contribute
    # exp(NEG_INF - NEG_INF) = 1 while m is still at its init value
    p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)         # (G, ps)
    l_cur = l_prev * corr + p.sum(axis=1, keepdims=True)
    pv = lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_cur, l_scr.shape)

    @pl.when(j == npages - 1)
    def _flush():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


def paged_attention_rkgd(q, k_pages, v_pages, page_tables, lengths, *,
                         window=0, softcap=0.0, scale=None, interpret=False):
    """q: (R, K, G, hd); k_pages/v_pages: (P, K, ps, hd);
    page_tables: (R, MPR) int32; lengths: (R,) int32 (query position).
    Returns o: (R, K, G, hd)."""
    R, K, G, hd = q.shape
    P, _, ps, _ = k_pages.shape
    MPR = page_tables.shape[1]
    scale = scale if scale else hd ** -0.5

    grid = (R, K, MPR)
    kern = functools.partial(_kernel, scale=scale, window=window,
                             softcap=softcap, ps=ps, npages=MPR)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, hd),
                         lambda r, h, j, pt, ln: (r, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda r, h, j, pt, ln: (pt[r, j], h, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda r, h, j, pt, ln: (pt[r, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda r, h, j, pt, ln: (r, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, K, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_tables, lengths, q, k_pages, v_pages)
