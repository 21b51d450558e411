"""Operations and bytes the algorithms need, counted from the shapes in a
configuration file.  What the program happens to compute beyond this
(masked attention blocks, recomputation in the backward pass, padded
vocabulary rows) is not counted: a utilisation is work required over
time taken."""
from __future__ import annotations

from typing import Dict


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


def forward_per_token(c: Dict, context: float) -> float:
    """Forward FLOPs for one token that attends to ``context`` positions
    (itself included); the tied output head counts, the lookup does not."""
    layers = c["num_hidden_layers"]
    head = 2.0 * c["vocab_size"] * c["hidden_size"]
    return layers * (2.0 * _matmul_params(c) + _mixing(c, context)) + head


def train_per_token(c: Dict, seq_len: int) -> float:
    """Forward and backward FLOPs per token of a causal sequence of
    ``seq_len``: three times the forward, averaged over the positions
    (position i attends to i + 1 of them)."""
    return 3.0 * forward_per_token(c, (seq_len + 1) / 2.0)
