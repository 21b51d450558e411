"""Operation counts of bench/flops.py against counts made by hand at tiny
sizes."""
from __future__ import annotations

import pytest

from bench import flops

DENSE = {"family": "dense", "hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16,
         "num_hidden_layers": 3, "vocab_size": 10}
SSM = {"family": "ssm", "hidden_size": 8, "intermediate_size": 16,
       "state_size": 2, "conv_kernel": 4, "time_step_rank": 1,
       "num_hidden_layers": 2, "vocab_size": 10}


def test_dense_forward_per_token():
    # head_dim 4: q 8x8, k and v 8x4 each, o 8x8, gate/up/down 3 x 8x16
    weights = 64 + 32 + 32 + 64 + 3 * 128
    attention = 4 * 2 * 4 * 5          # scores and values over 5 positions
    head = 2 * 10 * 8
    assert flops.forward_per_token(DENSE, 5) == 3 * (2 * weights
                                                     + attention) + head


def test_ssm_forward_per_token():
    # in_proj 8x32, x_proj 16x(1+4), dt 1x16, out_proj 16x8
    weights = 8 * 32 + 16 * 5 + 1 * 16 + 16 * 8
    scan = 7 * 16 * 2 + 2 * 4 * 16
    head = 2 * 10 * 8
    assert flops.forward_per_token(SSM, 99) == 2 * (2 * weights + scan) + head


@pytest.mark.parametrize("conf", [DENSE, SSM], ids=["dense", "ssm"])
def test_training_is_three_forwards_at_the_mean_context(conf):
    seq = 6
    assert flops.train_per_token(conf, seq) == pytest.approx(
        3 * sum(flops.forward_per_token(conf, i + 1) for i in range(seq))
        / seq)
