"""Tiny stand-ins for the cells' configurations and traffic, so a test can
drive a whole run on the CPU: the program's own tiny presets, with the
same keys as the configuration files."""
from __future__ import annotations

import copy

from bench.common import load_json
from bench.run import manifest as benchmark

# Cells the harness can run that no entry of BENCHMARK.json holds yet:
# the state-space family, with a tied and with an untied head, through the
# same training driver and reference.  Their configurations and limits
# exist only here, at the tiny size.  Each reports the metrics of the
# admitted cell named beside it.
NOT_ADMITTED = [({"name": "falcon-mamba-train-guarded",
                  "config": "falcon-mamba-7b.L1", "traffic": "train-guarded",
                  "chips": 1}, "granite-train-guarded"),
                ({"name": "falcon-mamba-untied-train-guarded",
                  "config": "falcon-mamba-7b.untied",
                  "traffic": "train-guarded", "chips": 1},
                 "granite-train-guarded")]


def manifest() -> dict:
    """The manifest the tests drive: ``BENCHMARK.json`` itself, with the
    entries of the cells it does not hold yet."""
    bench = benchmark()
    for entry, like in NOT_ADMITTED:
        bench["workloads"].append(dict(entry))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(entry["name"])
    return bench


CONFIGS = {
    "granite-3-8b.L1": {
        "name": "granite-tiny", "model": "granite-3-8b", "tiny": True,
        "family": "dense", "mesh": {"data": 1, "model": 1},
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
        "attention_multiplier": 0.25, "embedding_multiplier": 1.0,
        "logits_scaling": 1.0, "residual_multiplier": 1.0},
    "falcon-mamba-7b.L1": {
        "name": "falcon-mamba-tiny", "model": "falcon-mamba-7b",
        "tiny": True, "family": "ssm", "mesh": {"data": 1, "model": 1},
        "hidden_size": 64, "intermediate_size": 128, "state_size": 4,
        "conv_kernel": 4, "time_step_rank": 4, "num_hidden_layers": 2,
        "vocab_size": 256, "layer_norm_epsilon": 1e-6,
        "tie_word_embeddings": True},
}
# As published, Falcon-Mamba's head is untied: the driver unties the
# program's from the file, and the reference draws and compares it.
CONFIGS["falcon-mamba-7b.untied"] = dict(
    CONFIGS["falcon-mamba-7b.L1"], name="falcon-mamba-untied-tiny",
    tie_word_embeddings=False)

# The cells' limits are set from readings at their own sizes on the chip;
# these are set the same way from readings at the tiny sizes on the CPU
# (six seeds each): above the largest reading of the program, below the
# smallest of the float8 control or of the half-batch fault.
LIMITS = {
    # program up to 5.7e-4 / 2.0e-3 / 1.1e-3; half batch from 6.9e-3 /
    # 0.074 / 0.014; control 1.3e-3 / 2.0 / 0.98
    "granite-train-guarded": {"loss_rel": 2.5e-3, "grad_leaf_rel": 1.5e-2,
                              "change_leaf_rel": 5e-3,
                              "restore_mismatches": 0},
    "granite-train-bare": {"loss_rel": 2.5e-3, "grad_leaf_rel": 1.5e-2,
                           "change_leaf_rel": 5e-3},
    # program up to 2.4e-4 / 1.8e-3 / 1.1e-3; control from 1.1e-3 / 0.72
    # / 0.97; half batch from 7.2e-3 / 0.19 / 0.048
    "falcon-mamba-train-guarded": {"loss_rel": 6e-4, "grad_leaf_rel": 1.5e-2,
                                   "change_leaf_rel": 6e-3,
                                   "restore_mismatches": 0},
}
LIMITS["falcon-mamba-untied-train-guarded"] = LIMITS[
    "falcon-mamba-train-guarded"]


def traffic(name: str) -> dict:
    t = copy.deepcopy(load_json("traffic", name + ".json"))
    t.update(seq_len=32, rows_per_data_replica=4)
    return t


def overrides(cell: str, bench: dict) -> dict:
    """``run_cell`` overrides that shrink ``cell`` to the tiny presets."""
    w = {c["name"]: c for c in bench["workloads"]}[cell]
    return {"config": copy.deepcopy(CONFIGS[w["config"]]),
            "traffic": traffic(w["traffic"]), "limits": dict(LIMITS[cell])}
