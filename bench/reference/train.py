"""The reference's first three training steps, and the comparison that
decides a training cell's ``correct``.

Readings of one run, program or reference, are three things:

- ``losses``: the loss of each of the first three steps;
- ``grad``: per leaf, the norm of the first step's gradient as the
  optimiser receives it (after global-norm clipping);
- ``change``: per leaf, the norm of the parameters' change over the three
  steps.

Leaves are named by the reference module's ``leaf_norms``: ``embed``,
``final_norm``, ``head`` where the head is untied, and ``L<i>.<weight>``;
the embedding and the head count only the configuration's ``vocab_size``
rows.  The optimiser is the one every training configuration states, so
it lives here and not in a reference module.
"""
from __future__ import annotations

import math
import statistics
from types import ModuleType
from typing import Dict, List

import jax
import jax.numpy as jnp


def batch_tokens(seed: int, step: int, batch: int, seq: int, vocab: int):
    """The rows the synthetic data feed draws for ``step`` (0-based):
    ``seq + 1`` uniform token ids per row, split into inputs and targets."""
    k = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    k = jax.random.fold_in(k, 0)                 # the single feed shard
    toks = jax.random.randint(k, (batch, seq + 1), 0, vocab, jnp.int32)
    return toks[:, :-1], toks[:, 1:]


def readings(ref: ModuleType, m: Dict, opt: Dict, seed: int, batch: int,
             seq: int, prec: str = "f32", rows_used: int = 0) -> Dict:
    """Three AdamW steps of the reference module ``ref`` from the seed's
    weights, at ``m = ref.dims(conf)``.  ``rows_used`` keeps only the first
    rows of each batch (a fault that the comparison has to catch); 0 keeps
    all."""
    with jax.default_matmul_precision("highest"):
        return _readings(ref, m, opt, seed, batch, seq, prec,
                         rows_used or batch)


def _readings(ref, m, opt, seed, batch, seq, prec, rows_used):
    params0 = jax.jit(lambda k: ref.init_params(m, k))(
        jax.random.PRNGKey(seed))
    vg = jax.jit(jax.value_and_grad(
        lambda p, t, y: ref.loss(m, p, t, y, prec)))
    upd = jax.jit(lambda g, m1, m2, p, t, lr: adamw(
        clip(g, opt["clip_norm"]), m1, m2, p, t, lr, opt),
        static_argnums=(4,))
    zeros = jax.tree.map(jnp.zeros_like, params0)
    p, m1, m2 = params0, zeros, zeros
    losses: List[float] = []
    grad = raw = None
    for t in range(1, 4):
        toks, tgts = batch_tokens(seed, t - 1, batch, seq, m["V"])
        toks, tgts = toks[:rows_used], tgts[:rows_used]
        lval, g = vg(p, toks, tgts)
        losses.append(float(lval))
        if t == 1:
            raw = jax.device_get(ref.leaf_norms(g))
            grad = jax.device_get(ref.leaf_norms(clip(g, opt["clip_norm"])))
        p, m1, m2 = upd(g, m1, m2, p, t, lr_at(t - 1, opt))
    change = jax.device_get(ref.leaf_norms(
        jax.tree.map(jnp.subtract, p, params0)))
    return {"losses": losses, "grad": {k: float(v) for k, v in grad.items()},
            "change": {k: float(v) for k, v in change.items()},
            "raw_grad": {k: float(v) for k, v in raw.items()}}


def _worst_leaf(got: Dict[str, float], want: Dict[str, float]) -> float:
    """Largest gap between the two norms of a leaf, against the larger of
    that leaf's reference norm and the median leaf's."""
    med = statistics.median(want.values())
    return max(abs(got[k] - w) / max(w, med) for k, w in want.items())


def compare(got: Dict, want: Dict) -> Dict[str, float]:
    """The three numbers a training cell compares.  Leaves whose reference
    gradient is under a thousandth of the median leaf's move under Adam by
    round-off alone; they are left out of the change."""
    med = statistics.median(want["raw_grad"].values())
    moving = {k: v for k, v in want["change"].items()
              if want["raw_grad"][k] >= 1e-3 * med}
    return {
        "loss_rel": max(abs(a - b) / abs(b)
                        for a, b in zip(got["losses"], want["losses"])),
        "grad_leaf_rel": _worst_leaf(got["grad"], want["grad"]),
        "change_leaf_rel": _worst_leaf(got["change"], moving),
    }


# --------------------------------------------------------------------------
# the optimiser the training configuration states
# --------------------------------------------------------------------------

def lr_at(step: int, opt: Dict) -> float:
    """Linear warm-up, then cosine decay to ``final_frac`` of the peak."""
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    ff = opt["final_frac"]
    return peak * (ff + (1 - ff) * 0.5 * (1 + math.cos(math.pi * t)))


def clip(grads, max_norm: float):
    n = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(n, 1e-12))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw(grads, m1, m2, params, t: int, lr: float, opt: Dict):
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    m1 = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m1, grads)
    m2 = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, m2, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + wd * p), params, m1, m2)
    return params, m1, m2
