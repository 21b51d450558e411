"""The reference's first three training steps, and the comparison that
decides a training cell's ``correct``.

Readings of one run, program or reference, are three things:

- ``losses``: the loss of each of the first three steps;
- ``grad``: per leaf, the norm of the first step's gradient as the
  optimiser receives it (after global-norm clipping);
- ``change``: per leaf, the norm of the parameters' change over the three
  steps.

Leaves are named ``embed``, ``final_norm`` and ``L<i>.<weight>``; the
embedding counts only the configuration's ``vocab_size`` rows.
"""
from __future__ import annotations

import statistics
from typing import Dict, List

import jax
import jax.numpy as jnp

from bench.reference import model as ref


def batch_tokens(seed: int, step: int, batch: int, seq: int, vocab: int):
    """The rows the synthetic data feed draws for ``step`` (0-based):
    ``seq + 1`` uniform token ids per row, split into inputs and targets."""
    k = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    k = jax.random.fold_in(k, 0)                 # the single feed shard
    toks = jax.random.randint(k, (batch, seq + 1), 0, vocab, jnp.int32)
    return toks[:, :-1], toks[:, 1:]


def leaf_norms(params) -> Dict[str, jax.Array]:
    out = {"embed": jnp.linalg.norm(params["embed"]),
           "final_norm": jnp.linalg.norm(params["final_norm"])}
    for i, layer in enumerate(params["layers"]):
        for k, v in layer.items():
            out[f"L{i}.{k}"] = jnp.linalg.norm(v)
    return out


def readings(m: Dict, opt: Dict, seed: int, batch: int, seq: int,
             prec: str = "f32", rows_used: int = 0) -> Dict:
    """Three AdamW steps of the reference from the seed's weights.
    ``rows_used`` keeps only the first rows of each batch (a fault that
    the comparison has to catch); 0 keeps all."""
    with jax.default_matmul_precision("highest"):
        return _readings(m, opt, seed, batch, seq, prec, rows_used or batch)


def _readings(m, opt, seed, batch, seq, prec, rows_used):
    params0 = jax.jit(lambda k: ref.init_params(m, k))(
        jax.random.PRNGKey(seed))
    vg = jax.jit(jax.value_and_grad(
        lambda p, t, y: ref.loss(m, p, t, y, prec)))
    upd = jax.jit(lambda g, m1, m2, p, t, lr: ref.adamw(
        ref.clip(g, opt["clip_norm"]), m1, m2, p, t, lr, opt),
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
            raw = jax.device_get(leaf_norms(g))
            grad = jax.device_get(leaf_norms(ref.clip(g, opt["clip_norm"])))
        p, m1, m2 = upd(g, m1, m2, p, t, ref.lr_at(t - 1, opt))
    change = jax.device_get(leaf_norms(
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
