"""Operations and bytes the algorithms need, counted from the shapes in a
configuration file.  What the program happens to compute beyond this
(masked attention blocks, recomputation in the backward pass, padded
vocabulary rows) is not counted: a utilisation is work required over
time taken.  The layers' count comes from the reference module the file
names (``layers_forward_per_token``), which knows the family."""
from __future__ import annotations

from typing import Dict

from bench import reference


def forward_per_token(c: Dict, context: float) -> float:
    """Forward FLOPs for one token that attends to ``context`` positions
    (itself included); the output head counts, the lookup does not."""
    head = 2.0 * c["vocab_size"] * c["hidden_size"]
    return reference.load(c).layers_forward_per_token(c, context) + head


def train_per_token(c: Dict, seq_len: int) -> float:
    """Forward and backward FLOPs per token of a causal sequence of
    ``seq_len``: three times the forward, averaged over the positions
    (position i attends to i + 1 of them)."""
    return 3.0 * forward_per_token(c, (seq_len + 1) / 2.0)
