"""Generic model stack: runs every assigned architecture.

One parameter/forward implementation covering dense & MoE transformers
(GQA/MQA, qkv-bias, GeGLU/SwiGLU, sliding-window, local/global alternation,
attention & final logit soft-capping, RoPE / M-RoPE), Mamba-1 SSM stacks,
RG-LRU hybrids and bidirectional encoders.  Layer kinds come from
``cfg.pattern``; homogeneous stacks are scanned (stacked params, O(1-layer)
HLO), heterogeneous/small stacks can unroll (``cfg.scan_layers=False``).

Modes: ``train`` (logits only), ``prefill`` (logits + filled KV cache),
``decode`` (one token against the cache).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.layers.attention import decode_mha, mha
from repro.layers.mlp import mlp_apply, mlp_init, _act
from repro.layers.moe import moe_apply, moe_init
from repro.layers.norms import rms_norm
from repro.layers.rope import apply_mrope, apply_rope, make_positions
from repro.models.base import BIDIR, FULL, LOCAL, REC, SSM, ModelConfig
from repro.models.mamba import ssm_apply, ssm_cache_init, ssm_init
from repro.models.rglru import rec_apply, rec_cache_init, rec_init
from repro.sharding.api import U, constrain
from repro.sharding.rules import DP_AXES, TP, gathered, res_spec


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _attn_layer_init(key, cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    h, kv = cfg.effective_num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 8)
    pd = cfg.param_dtype
    s = d ** -0.5
    attn = {
        "wq": (jax.random.normal(ks[0], (d, h, hd)) * s).astype(pd),
        "wk": (jax.random.normal(ks[1], (d, kv, hd)) * s).astype(pd),
        "wv": (jax.random.normal(ks[2], (d, kv, hd)) * s).astype(pd),
        "wo": (jax.random.normal(ks[3], (h, hd, d)) * (h * hd) ** -0.5).astype(pd),
    }
    if cfg.qkv_bias:
        attn["bq"] = jnp.zeros((h, hd), pd)
        attn["bk"] = jnp.zeros((kv, hd), pd)
        attn["bv"] = jnp.zeros((kv, hd), pd)
    p: Dict[str, Any] = {"ln1": jnp.ones((d,), pd), "attn": attn,
                         "ln2": jnp.ones((d,), pd)}
    if cfg.sandwich_norm:
        p["ln1_post"] = jnp.ones((d,), pd)
        p["ln2_post"] = jnp.ones((d,), pd)
    if cfg.num_experts:
        p["moe"] = moe_init(ks[4], d, cfg.d_ff, cfg.num_experts, pd)
    else:
        p["mlp"] = mlp_init(ks[4], d, cfg.d_ff, cfg.mlp_act, pd)
    return p


def _layer_init(key, cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    if kind in (FULL, LOCAL, BIDIR):
        return _attn_layer_init(key, cfg, kind)
    if kind == SSM:
        return {"ln": jnp.ones((cfg.d_model,), cfg.param_dtype),
                "ssm": ssm_init(key, cfg)}
    if kind == REC:
        return rec_init(key, cfg)
    raise ValueError(kind)


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    kinds = cfg.layer_kinds()
    keys = jax.random.split(key, 4)
    params: Dict[str, Any] = {}
    if not cfg.embedding_inputs:
        params["embed"] = {
            "tok": (jax.random.normal(keys[0], (cfg.padded_vocab, cfg.d_model))
                    * cfg.d_model ** -0.5).astype(cfg.param_dtype)}
    if cfg.scan_layers:
        P_ = len(cfg.pattern)
        assert cfg.num_layers % P_ == 0, (cfg.name, cfg.num_layers, P_)
        G = cfg.num_layers // P_
        gkeys = jax.random.split(keys[1], G)

        def one_block(k):
            sub = jax.random.split(k, P_)
            return {f"l{p}": _layer_init(sub[p], cfg, cfg.pattern[p])
                    for p in range(P_)}

        params["blocks"] = jax.vmap(one_block)(gkeys)
    else:
        lkeys = jax.random.split(keys[1], cfg.num_layers)
        params["layers"] = {f"layer_{i}": _layer_init(lkeys[i], cfg, kinds[i])
                            for i in range(cfg.num_layers)}
    params["final_norm"] = jnp.ones((cfg.d_model,), cfg.param_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(keys[2],
                             (cfg.d_model, cfg.padded_vocab))
                             * cfg.d_model ** -0.5).astype(cfg.param_dtype)
    return params


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------

def _attn_cache_init(cfg: ModelConfig, kind: str, batch: int, cache_len: int):
    sc = cache_len if (kind != LOCAL or not cfg.window) \
        else min(cache_len, cfg.window)
    kv = cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, sc, kv, hd), cfg.dtype),
        "v": jnp.zeros((batch, sc, kv, hd), cfg.dtype),
        "pos": jnp.full((sc,), -1, jnp.int32),
    }


def _cache_entry_init(cfg, kind, batch, cache_len):
    if kind in (FULL, LOCAL, BIDIR):
        return _attn_cache_init(cfg, kind, batch, cache_len)
    if kind == SSM:
        return ssm_cache_init(cfg, batch)
    if kind == REC:
        return rec_cache_init(cfg, batch)
    raise ValueError(kind)


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int):
    """Block-paged KV pool (serve/page_table.py): one shared pool of
    ``num_pages`` pages of ``page_size`` tokens per attention layer, in
    place of per-request contiguous rows.  Page 0 is the reserved null
    page.  Only attention stacks page — SSM/REC state has no sequence
    axis to page over (the legacy slot pool still serves those)."""
    kinds = cfg.layer_kinds()
    bad = sorted({k for k in kinds if k not in (FULL, LOCAL)})
    if bad:
        raise ValueError(f"paged KV cache needs an attention-only decode "
                         f"stack; {cfg.name} has {bad} layers")
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim

    def entry():
        return {"k": jnp.zeros((num_pages, page_size, kv, hd), cfg.dtype),
                "v": jnp.zeros((num_pages, page_size, kv, hd), cfg.dtype)}

    if cfg.scan_layers:
        P_ = len(cfg.pattern)
        G = cfg.num_layers // P_

        def stack(e):
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x, (G,) + x.shape), e)

        return {"blocks": {f"l{p}": stack(entry()) for p in range(P_)}}
    return {"layers": {f"layer_{i}": entry()
                       for i in range(cfg.num_layers)}}


def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    kinds = cfg.layer_kinds()
    if cfg.scan_layers:
        P_ = len(cfg.pattern)
        G = cfg.num_layers // P_

        def stack(entry):
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x, (G,) + x.shape), entry)

        blocks = {f"l{p}": stack(_cache_entry_init(cfg, cfg.pattern[p],
                                                   batch, cache_len))
                  for p in range(P_)}
        return {"blocks": blocks, "index": jnp.zeros((), jnp.int32)}
    layers = {f"layer_{i}": _cache_entry_init(cfg, kinds[i], batch, cache_len)
              for i in range(cfg.num_layers)}
    return {"layers": layers, "index": jnp.zeros((), jnp.int32)}


# --------------------------------------------------------------------------
# layer application
# --------------------------------------------------------------------------

def _project(h, w, bias, cd, impl=None):
    if impl == "abft":
        from repro.kernels.abft_matmul.ops import abft_dot

        d, nh, hd = w.shape
        y = abft_dot(h, w.astype(cd).reshape(d, nh * hd))
        y = y.reshape(h.shape[:-1] + (nh, hd))
    else:
        y = jnp.einsum("bsd,dhk->bshk", h, w.astype(cd))
    if bias is not None:
        y = y + bias.astype(cd)
    return y


def _rope_q_k(cfg, q, k, positions):
    if cfg.mrope_sections:
        q = apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k


def _head_mask(cfg: ModelConfig):
    """(He,) mask zeroing TP-padding q-heads (see base.effective_num_heads)."""
    he, k = cfg.effective_num_heads, max(cfg.num_kv_heads, 1)
    if he == cfg.num_heads:
        return None
    gp, g = he // k, cfg.num_heads // k
    return (jnp.arange(he) % gp < g).astype(cfg.dtype)


def _attn_apply(p, x, kind, cfg: ModelConfig, positions, cache=None,
                impl="auto", page_tables=None):
    cd = cfg.dtype
    a = p["attn"]
    B, S = x.shape[0], x.shape[1]
    scale = cfg.query_scale or None
    window = cfg.window if kind == LOCAL else 0
    hmask = _head_mask(cfg)
    # impl="abft" opts the projection matmuls into the checksummed kernel
    # (docs/sdc.md tier 1); the attention core itself falls back to "auto"
    proj_impl = "abft" if impl == "abft" else None
    if impl == "abft":
        impl = "auto"

    # SP: gather the bf16 residual BEFORE the norm — a gather placed after
    # would let GSPMD reshard the norm's fp32 internals (2x wire bytes).
    h = rms_norm(gathered(cfg, x), p["ln1"], cfg.norm_eps,
                 use_pallas=cfg.use_pallas)
    q = _project(h, a["wq"], a.get("bq"), cd, impl=proj_impl)
    k = _project(h, a["wk"], a.get("bk"), cd, impl=proj_impl)
    v = _project(h, a["wv"], a.get("bv"), cd, impl=proj_impl)
    q = constrain(q, P(DP_AXES, U, TP, U))
    if kind != BIDIR or cfg.rope_theta > 0:
        q, k = _rope_q_k(cfg, q, k, positions)

    new_cache = None
    if page_tables is not None:                          # paged decode
        from repro.kernels.paged_attention.ops import paged_decode_attention

        ps = cache["k"].shape[1]
        lengths = positions[:, 0].astype(jnp.int32)      # (R,)
        ridx = jnp.arange(B)
        # write this step's k/v at logical position lengths[r]; inactive
        # rows (zeroed table, length 0) land on the null page 0, which the
        # length mask keeps out of every real request's softmax
        pidx = page_tables[ridx, lengths // ps]
        off = lengths % ps
        kc = cache["k"].at[pidx, off].set(k[:, 0].astype(cd))
        vc = cache["v"].at[pidx, off].set(v[:, 0].astype(cd))
        o = paged_decode_attention(
            q, kc, vc, page_tables, lengths, window=window,
            softcap=cfg.attn_softcap, scale=scale,
            impl=("pallas" if impl == "pallas" else "ref"))
        new_cache = {"k": kc, "v": vc}
    elif cache is not None and S == 1:                   # decode
        sc = cache["k"].shape[1]
        cur = positions[0, 0, 0] if cfg.mrope_sections else positions[0, 0]
        slot = cur % sc
        kc = lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cd), slot, 1)
        vc = lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cd), slot, 1)
        pos = cache["pos"].at[slot].set(cur)
        o = decode_mha(q, kc, vc, pos, cur, window=window,
                       softcap=cfg.attn_softcap, scale=scale)
        new_cache = {"k": kc, "v": vc, "pos": pos}
    else:
        o = mha(q, k, v, causal=(kind != BIDIR), window=window,
                softcap=cfg.attn_softcap, scale=scale, impl=impl)
        if cache is not None:                            # prefill fills cache
            sc = cache["k"].shape[1]
            if sc >= S:
                kc = lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cd), 0, 1)
                vc = lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cd), 0, 1)
                pos = cache["pos"].at[:S].set(jnp.arange(S, dtype=jnp.int32))
            else:                                        # rolling window cache
                tail_pos = jnp.arange(S - sc, S, dtype=jnp.int32)
                slots = tail_pos % sc
                kc = cache["k"].at[:, slots].set(k[:, S - sc:].astype(cd))
                vc = cache["v"].at[:, slots].set(v[:, S - sc:].astype(cd))
                pos = cache["pos"].at[slots].set(tail_pos)
            new_cache = {"k": kc, "v": vc, "pos": pos}

    if hmask is not None:
        o = o * hmask[None, None, :, None]
    # pin o (and via transpose its cotangent) to head-TP sharding: keeps the
    # backward dot aligned with wo's "model" sharding (see mlp_apply)
    o = constrain(o, P(DP_AXES, U, TP, U))
    if proj_impl == "abft":
        from repro.kernels.abft_matmul.ops import abft_dot

        nh, hd, d = a["wo"].shape
        o = abft_dot(o.reshape(B, S, nh * hd),
                     a["wo"].astype(cd).reshape(nh * hd, d))
    else:
        o = jnp.einsum("bshk,hkd->bsd", o, a["wo"].astype(cd))
    if cfg.sandwich_norm:
        o = rms_norm(o, p["ln1_post"], cfg.norm_eps)
    x = x + o
    x = constrain(x, res_spec(cfg))

    h2 = rms_norm(gathered(cfg, x), p["ln2"], cfg.norm_eps,
                  use_pallas=cfg.use_pallas)
    aux = jnp.zeros((), jnp.float32)
    if cfg.num_experts:
        m, aux = moe_apply(p["moe"], h2, num_experts=cfg.num_experts,
                           k=cfg.experts_per_token,
                           capacity_factor=cfg.capacity_factor,
                           act=_act(cfg.mlp_act), compute_dtype=cd,
                           dead_experts=cfg.dead_experts)
    else:
        m = mlp_apply(p["mlp"], h2, cfg.mlp_act, cd, impl=proj_impl)
    if cfg.sandwich_norm:
        m = rms_norm(m, p["ln2_post"], cfg.norm_eps)
    x = x + m
    x = constrain(x, res_spec(cfg))
    return x, new_cache, aux


def _apply_layer(p, x, kind, cfg, positions, cache=None, impl="auto",
                 page_tables=None):
    if kind in (FULL, LOCAL, BIDIR):
        return _attn_apply(p, x, kind, cfg, positions, cache, impl,
                           page_tables)
    if kind == SSM:
        y, nc = ssm_apply(p, x, cfg, cache, use_pallas=cfg.use_pallas)
        return y, nc, jnp.zeros((), jnp.float32)
    if kind == REC:
        y, nc = rec_apply(p, x, cfg, cache)
        return y, nc, jnp.zeros((), jnp.float32)
    raise ValueError(kind)


# --------------------------------------------------------------------------
# full model
# --------------------------------------------------------------------------

def _embed_lookup(cfg, table, tokens):
    """Vocab-sharded embedding lookup.

    A plain gather over the model-sharded vocab dim makes GSPMD all-gather
    the WHOLE table (hundreds of MB per step).  Instead: shard_map over
    "model" — each shard looks up its local rows masked, then one psum of
    the (B,S,D) activations (EXPERIMENTS.md S Perf)."""
    from repro.sharding.api import current_mesh

    mesh = current_mesh()
    tp = (dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
          if mesh is not None else 1)
    if tp <= 1 or table.shape[0] % tp != 0:
        return table[tokens]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = 1
    for a in dp_axes:
        dp *= sizes[a]
    b_ax = dp_axes if (dp > 1 and tokens.shape[0] % dp == 0) else None
    local_v = table.shape[0] // tp

    def f(tab, tok):
        lo = jax.lax.axis_index("model") * local_v
        ids = tok - lo
        ok = (ids >= 0) & (ids < local_v)
        vals = tab[jnp.clip(ids, 0, local_v - 1)]
        vals = jnp.where(ok[..., None], vals, jnp.zeros((), tab.dtype))
        return jax.lax.psum(vals, "model")

    sm = jax.shard_map(f, mesh=mesh, in_specs=(P(TP, None), P(b_ax, None)),
                       out_specs=P(b_ax, None, None), check_vma=False)
    return sm(table, tokens)


def _embed_in(cfg, params, batch):
    cd = cfg.dtype
    if cfg.embedding_inputs:
        x = batch["embeddings"].astype(cd)
    else:
        x = _embed_lookup(cfg, params["embed"]["tok"].astype(cd),
                          batch["tokens"])
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cd)
    return constrain(x, res_spec(cfg))


def _logits_out(cfg, params, x):
    cd = cfg.dtype
    x = rms_norm(gathered(cfg, x), params["final_norm"], cfg.norm_eps,
                 use_pallas=cfg.use_pallas)
    if cfg.tie_embeddings and not cfg.embedding_inputs:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]["tok"].astype(cd))
    else:
        logits = x @ params["lm_head"].astype(cd)
    if cfg.final_softcap:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return constrain(logits, P(DP_AXES, None, TP))


def _positions_for(cfg, batch, S, offset=0):
    if cfg.mrope_sections:
        if "positions" in batch:
            return batch["positions"]
        B = (batch.get("tokens") if "tokens" in batch
             else batch["embeddings"]).shape[0]
        pos = make_positions(B, S, offset)
        return jnp.broadcast_to(pos[None], (3,) + pos.shape)
    B = (batch.get("tokens") if "tokens" in batch
         else batch["embeddings"]).shape[0]
    return make_positions(B, S, offset)


# Recurrence-dynamics leaves stay fp32 (exp() of these is sensitive).
_KEEP_FP32 = ("A_log", "D", "lam")


def _cast_params(cfg: ModelConfig, params):
    """Cast float32 weights to the compute dtype ONCE, outside the
    remat/scan region, and PIN the cast outputs to the parameter sharding.
    Without the pin, GSPMD propagates the consumers' replicated sharding
    backward through the elementwise cast and all-gathers fp32 weights
    (2x the wire bytes) — measured in EXPERIMENTS.md S Perf."""
    if cfg.dtype == jnp.float32:
        return params

    from repro.sharding.api import current_mesh
    from repro.sharding.rules import param_specs

    mesh = current_mesh()
    specs = None
    if mesh is not None and "model" in mesh.axis_names:
        tp = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
        specs = param_specs(cfg, tp)

    def cast(path, w, spec=None):
        name = getattr(path[-1], "key", "") if path else ""
        if w.dtype == jnp.float32 and name not in _KEEP_FP32:
            w = w.astype(cfg.dtype)
            if spec is not None:
                w = constrain(w, spec)
        return w

    if specs is None:
        return jax.tree_util.tree_map_with_path(cast, params)
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s), (len(flat_p), len(flat_s))
    rebuilt = [cast(path, w, s) for (path, w), s in zip(flat_p, flat_s)]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params), rebuilt)


def forward(cfg: ModelConfig, params, batch, *, mode: str = "train",
            cache=None, impl: Optional[str] = None):
    """Returns (logits, new_cache, aux_loss).  new_cache is None in train."""
    impl = impl or ("pallas" if cfg.use_pallas else "auto")
    params = _cast_params(cfg, params)
    x = _embed_in(cfg, params, batch)
    B, S = x.shape[0], x.shape[1]
    page_tables = None
    if mode == "paged_decode":
        if cfg.mrope_sections:
            raise ValueError("paged decode does not support M-RoPE")
        # one token per request at its own position; the page table maps
        # logical positions onto the shared pool (init_paged_cache)
        page_tables = batch["page_tables"]
        positions = batch["lengths"].astype(jnp.int32)[:, None]   # (R, 1)
    elif mode == "decode":
        offset = cache["index"]
        positions = _positions_for(cfg, batch, 1, offset)
    else:
        positions = _positions_for(cfg, batch, S)

    kinds = cfg.layer_kinds()
    aux_total = jnp.zeros((), jnp.float32)

    remat_on = cfg.remat and mode == "train"

    # Per-layer remat: each layer recomputes from its own input in the
    # backward pass (saved residual = one (B,S,D) tensor per layer).
    def apply_one(p, xc, kind, entry, layer_remat=True):
        fn = functools.partial(_apply_layer, impl=impl,
                               page_tables=page_tables)
        if remat_on and layer_remat:
            fn = jax.checkpoint(fn, static_argnums=(2, 3), prevent_cse=False)
        return fn(p, xc, kind, cfg, positions, entry)

    if cfg.scan_layers:
        P_ = len(cfg.pattern)
        # Short patterns: checkpoint the whole scan body (one residual per
        # block, measurably lower peak).  Long patterns (recurrentgemma's 13):
        # per-layer checkpoints to bound the recompute live-set.
        block_level = P_ <= 2

        def block_fn(carry, xs):
            xc, aux = carry
            blk_params, blk_cache = xs
            new_entries = {}
            for pi in range(P_):
                entry = None if blk_cache is None else blk_cache[f"l{pi}"]
                xc, nc, a = apply_one(blk_params[f"l{pi}"], xc,
                                      cfg.pattern[pi], entry,
                                      layer_remat=not block_level)
                aux = aux + a
                if nc is not None:
                    new_entries[f"l{pi}"] = nc
            return (xc, aux), (new_entries if new_entries else None)

        fn = block_fn
        if remat_on and block_level:
            fn = jax.checkpoint(block_fn, prevent_cse=False)
        blk_cache_xs = cache["blocks"] if cache is not None else None
        (x, aux_total), ys = lax.scan(
            fn, (x, aux_total), (params["blocks"], blk_cache_xs))
        new_cache = None
        if cache is not None:
            if mode == "paged_decode":                   # pool has no index
                new_cache = {"blocks": ys}
            else:
                new_cache = {"blocks": ys,
                             "index": cache["index"]
                             + (S if mode != "decode" else 1)}
    else:
        new_layers = {}
        for i in range(cfg.num_layers):
            name = f"layer_{i}"
            entry = None if cache is None else cache["layers"][name]
            x, nc, a = apply_one(params["layers"][name], x, kinds[i], entry)
            aux_total = aux_total + a
            if nc is not None:
                new_layers[name] = nc
        new_cache = None
        if cache is not None:
            if mode == "paged_decode":                   # pool has no index
                new_cache = {"layers": new_layers}
            else:
                new_cache = {"layers": new_layers,
                             "index": cache["index"]
                             + (S if mode != "decode" else 1)}

    logits = _logits_out(cfg, params, x)
    return logits, new_cache, aux_total
