"""Model FLOP/s utilisation of training, %: the forward and backward
operations per token that the configuration requires (bench/flops.py; no
recomputation, no optimiser) times the window's tokens per second, over
the chips' bf16 peak."""
from bench import flops
from bench.common import peak_flops_bytes


def read(view):
    tr = view.get("train")
    if not tr or not tr["tokens"]:
        return None
    peak = peak_flops_bytes(view["device"]["kind"])[0]
    t0, t1 = view["window"]
    per_tok = flops.train_per_token(view["config"], tr["seq_len"])
    return 100.0 * per_tok * tr["tokens"] / (t1 - t0) / (tr["chips"] * peak)
