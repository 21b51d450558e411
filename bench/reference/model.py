"""Plain float32 reference of the two model families the benchmark runs:
a dense Granite-style decoder and a Mamba-1 (Falcon-Mamba) stack, with the
output head tied to the embedding or not, as the file says.

Written from the published descriptions and the configuration files under
``bench/configs``, in straightforward ``jax.numpy``: no kernels, no cache,
no batching tricks, no remat, and a sequential scan for the state-space
layer.  It computes each configuration as the program runs it: the
published values, with each of the file's ``departures`` in place
(``bench.common.as_run``).  It imports nothing of the program.  Each
matrix product runs at ``Precision.HIGHEST``; ``prec="fp8"`` is the
control, which rounds both operands of every product to float8 e4m3 with
a per-tensor scale first (the step below bfloat16, the precision the
configurations state).

Weights are drawn from the seed by the recipe the program's initialiser
follows (the same key splits and shapes), so one seed gives both the same
weights without either taking anything from the other.

It is the reference module of every configuration file that names none
(``bench/reference/__init__.py``): besides the model it holds the two
families' file keys (``FIELDS``), leaf names and FLOP counts.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from bench.common import as_run
from bench.reference import CHECK, SET, Field

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x):
    """Round to float8 e4m3 with a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(spec: str, a, b, prec: str):
    if prec == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HI)


# --------------------------------------------------------------------------
# configuration
# --------------------------------------------------------------------------

# What the training driver does with each key of a file, per ``family``.
FIELDS = {
    "dense": {
        "num_hidden_layers": Field("num_layers", SET),
        "rms_norm_eps": Field("norm_eps", SET),
        "tie_word_embeddings": Field("tie_embeddings", SET),
        "hidden_size": Field("d_model", CHECK),
        "vocab_size": Field("vocab_size", CHECK),
        "num_attention_heads": Field("num_heads", CHECK),
        "num_key_value_heads": Field("num_kv_heads", CHECK),
        "intermediate_size": Field("d_ff", CHECK),
        "head_dim": Field("resolved_head_dim", CHECK, lambda c: (
            c["hidden_size"] // c["num_attention_heads"])),
        "rope_theta": Field("rope_theta", CHECK),
    },
    "ssm": {
        "num_hidden_layers": Field("num_layers", SET),
        "layer_norm_epsilon": Field("norm_eps", SET),
        "tie_word_embeddings": Field("tie_embeddings", SET),
        "hidden_size": Field("d_model", CHECK),
        "vocab_size": Field("vocab_size", CHECK),
        "intermediate_size": Field("d_inner", CHECK),
        "state_size": Field("ssm_state", CHECK),
        "conv_kernel": Field("conv_width", CHECK),
        "time_step_rank": Field("resolved_dt_rank", CHECK),
    },
}


def padded_vocab(v: int) -> int:
    """Rows of the embedding table the program draws (a multiple of 2048);
    only the first ``v`` are ever used."""
    mult = 2048 if v > 2048 else 128
    return -(-v // mult) * mult


def dims(conf: Dict) -> Dict:
    """The sizes and scalars the reference needs, read from a
    configuration file as the program runs it."""
    c = as_run(conf)
    d = dict(family=c["family"], d=c["hidden_size"], L=c["num_hidden_layers"],
             V=c["vocab_size"], eps=c.get("rms_norm_eps",
                                          c.get("layer_norm_epsilon")),
             tied=c["tie_word_embeddings"])
    if c["family"] == "dense":
        d.update(H=c["num_attention_heads"], K=c["num_key_value_heads"],
                 hd=c["hidden_size"] // c["num_attention_heads"],
                 F=c["intermediate_size"], theta=c["rope_theta"],
                 att_mult=c["attention_multiplier"],
                 emb_mult=c["embedding_multiplier"],
                 logit_scale=c["logits_scaling"],
                 res_mult=c["residual_multiplier"])
    else:
        d.update(Di=c["intermediate_size"], N=c["state_size"],
                 W=c["conv_kernel"], R=c["time_step_rank"])
    return d


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------

def _normal(k, shape, scale):
    return jax.random.normal(k, shape) * scale


def _dense_layer(k, m):
    d, H, K, hd, F = m["d"], m["H"], m["K"], m["hd"], m["F"]
    ks = jax.random.split(k, 8)
    k1, k2, k3 = jax.random.split(ks[4], 3)
    return {"ln1": jnp.ones((d,)), "ln2": jnp.ones((d,)),
            "wq": _normal(ks[0], (d, H, hd), d ** -0.5),
            "wk": _normal(ks[1], (d, K, hd), d ** -0.5),
            "wv": _normal(ks[2], (d, K, hd), d ** -0.5),
            "wo": _normal(ks[3], (H, hd, d), (H * hd) ** -0.5),
            "w_in": _normal(k1, (d, F), d ** -0.5),
            "w_out": _normal(k2, (F, d), F ** -0.5),
            "w_gate": _normal(k3, (d, F), d ** -0.5)}


def _ssm_layer(k, m):
    d, Di, N, R, W = m["d"], m["Di"], m["N"], m["R"], m["W"]
    ks = jax.random.split(k, 6)
    return {"ln": jnp.ones((d,)),
            "in_proj": _normal(ks[0], (d, 2 * Di), d ** -0.5),
            "conv_w": _normal(ks[1], (W, Di), 0.1),
            "conv_b": jnp.zeros((Di,)),
            "x_proj": _normal(ks[2], (Di, R + 2 * N), Di ** -0.5),
            "dt_w": _normal(ks[3], (R, Di), R ** -0.5),
            "dt_b": jnp.full((Di,), -4.6),
            "A_log": jnp.log(jnp.broadcast_to(
                jnp.arange(1, N + 1, dtype=jnp.float32), (Di, N))),
            "D": jnp.ones((Di,)),
            "out_proj": _normal(ks[4], (Di, d), Di ** -0.5)}


def init_params(m: Dict, key):
    """All weights, float32, from the seed's ``key``: the embedding (first
    V rows of the padded draw), one entry per layer, the final norm, and
    an untied head (first V columns of the padded draw)."""
    keys = jax.random.split(key, 4)
    emb = _normal(keys[0], (padded_vocab(m["V"]), m["d"]), m["d"] ** -0.5)
    layer = _dense_layer if m["family"] == "dense" else _ssm_layer
    lkeys = jax.random.split(keys[1], m["L"])
    # the program draws the layers' weights in one stacked call over the
    # layer keys; drawing each key on its own gives the same numbers
    layers = [layer(jax.random.split(k, 1)[0], m) for k in lkeys]
    params = {"embed": emb[: m["V"]], "layers": layers,
              "final_norm": jnp.ones((m["d"],))}
    if not m["tied"]:
        head = _normal(keys[2], (m["d"], padded_vocab(m["V"])), m["d"] ** -0.5)
        params["head"] = head[:, : m["V"]]
    return params


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotate-half RoPE; x (B, S, H, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def dense_block(p, x, m, prec):
    B, S, _ = x.shape
    G = m["H"] // m["K"]
    h = rms_norm(x, p["ln1"], m["eps"])
    q = rope(mm("bsd,dhk->bshk", h, p["wq"], prec), m["theta"])
    k = rope(mm("bsd,dhk->bshk", h, p["wk"], prec), m["theta"])
    v = mm("bsd,dhk->bshk", h, p["wv"], prec)
    k = jnp.repeat(k, G, axis=2)          # query head i reads kv head i // G
    v = jnp.repeat(v, G, axis=2)
    s = mm("bshk,bthk->bhst", q, k, prec) * m["att_mult"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = mm("bhst,bthk->bshk", jax.nn.softmax(s, -1), v, prec)
    x = x + mm("bshk,hkd->bsd", o, p["wo"], prec) * m["res_mult"]
    h = rms_norm(x, p["ln2"], m["eps"])
    g = jax.nn.silu(mm("bsd,df->bsf", h, p["w_gate"], prec))
    u = mm("bsd,df->bsf", h, p["w_in"], prec)
    return x + mm("bsf,fd->bsd", g * u, p["w_out"], prec) * m["res_mult"]


def ssm_block(p, x, m, prec):
    B, S, _ = x.shape
    Di, N, R, W = m["Di"], m["N"], m["R"], m["W"]
    h = rms_norm(x, p["ln"], m["eps"])
    xz = mm("bsd,de->bse", h, p["in_proj"], prec)
    xi, z = xz[..., :Di], xz[..., Di:]
    xp = jnp.pad(xi, ((0, 0), (W - 1, 0), (0, 0)))
    xi = sum(xp[:, w:w + S] * p["conv_w"][w] for w in range(W)) + p["conv_b"]
    xi = jax.nn.silu(xi)
    bcd = mm("bse,ef->bsf", xi, p["x_proj"], prec)
    dt = jax.nn.softplus(mm("bsr,re->bse", bcd[..., :R], p["dt_w"], prec)
                         + p["dt_b"])
    Bm, Cm = bcd[..., R:R + N], bcd[..., R + N:]
    A = -jnp.exp(p["A_log"])                                   # (Di, N)

    def step(hs, t):                       # h_t = e^{dt A} h + dt x B
        dt_t, x_t, b_t, c_t = t
        hs = jnp.exp(dt_t[..., None] * A) * hs \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return hs, jnp.einsum("ben,bn->be", hs, c_t, precision=HI)

    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (dt, xi, Bm, Cm))
    _, y = jax.lax.scan(step, jnp.zeros((B, Di, N)), seq)
    y = jnp.moveaxis(y, 0, 1) + p["D"] * xi
    return x + mm("bse,ed->bsd", y * jax.nn.silu(z), p["out_proj"], prec)


def logits(m: Dict, params, tokens, prec: str = "f32"):
    """(B, S) tokens -> (B, S, V) float32 logits."""
    x = params["embed"][tokens]
    block = dense_block if m["family"] == "dense" else ssm_block
    if m["family"] == "dense":
        x = x * m["emb_mult"]
    for p in params["layers"]:
        x = block(p, x, m, prec)
    x = rms_norm(x, params["final_norm"], m["eps"])
    out = (mm("bsd,vd->bsv", x, params["embed"], prec) if m["tied"]
           else mm("bsd,dv->bsv", x, params["head"], prec))
    return out / m["logit_scale"] if m["family"] == "dense" else out


def loss(m: Dict, params, tokens, targets, prec: str = "f32"):
    z = logits(m, params, tokens, prec)
    gold = jnp.take_along_axis(z, targets[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(z, -1) - gold)


def leaf_norms(params) -> Dict[str, jax.Array]:
    """The norm of each leaf, named as the training driver names the
    program's leaves: ``embed``, ``final_norm``, ``head`` where untied, and
    ``L<i>.<weight>``."""
    out = {"embed": jnp.linalg.norm(params["embed"]),
           "final_norm": jnp.linalg.norm(params["final_norm"])}
    if "head" in params:
        out["head"] = jnp.linalg.norm(params["head"])
    for i, layer in enumerate(params["layers"]):
        for k, v in layer.items():
            out[f"L{i}.{k}"] = jnp.linalg.norm(v)
    return out


# --------------------------------------------------------------------------
# operations the algorithms need (bench/flops.py)
# --------------------------------------------------------------------------

def _matmul_params(c: Dict) -> int:
    """Weights that each token meets in a matrix product, per layer."""
    d = c["hidden_size"]
    if c["family"] == "dense":
        hd = d // c["num_attention_heads"]
        h, k, f = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["intermediate_size"])
        return d * h * hd + 2 * d * k * hd + h * hd * d + 3 * d * f
    di, n, r = c["intermediate_size"], c["state_size"], c["time_step_rank"]
    return d * 2 * di + di * (r + 2 * n) + r * di + di * d


def _mixing(c: Dict, context: float) -> float:
    """Forward operations per token per layer outside the weight products:
    causal attention over ``context`` earlier positions (scores and
    values), or the selective scan and the depthwise convolution."""
    if c["family"] == "dense":
        hd = c["hidden_size"] // c["num_attention_heads"]
        return 4.0 * c["num_attention_heads"] * hd * context
    di, n = c["intermediate_size"], c["state_size"]
    # per (channel, state): exp(dt*A), decay*h + input, dt*x*B, C.h
    return 7.0 * di * n + 2.0 * c["conv_kernel"] * di


def layers_forward_per_token(c: Dict, context: float) -> float:
    """Forward FLOPs of all the layers for one token that attends to
    ``context`` positions (itself included)."""
    return c["num_hidden_layers"] * (2.0 * _matmul_params(c)
                                     + _mixing(c, context))
