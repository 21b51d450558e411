"""The training driver and the FLOP count know no family: each configuration
file's reference module holds its keys, leaf names and FLOP terms.  These
tests hold the harness to the numbers it gave before that move
(``data/golden_harness.json``), name the leaves of stacks with more than one
layer kind, scanned or not, and refuse a file whose widths the program does
not run."""
from __future__ import annotations

import copy
import dataclasses
import sys
import types

import jax
import pytest

from bench import flops, reference
from bench.common import load_json
from bench.drivers.train import _named_norms, model_config, named_leaves
from bench.reference import train as rtrain
from bench.tests import tiny

GOLDEN = load_json("tests", "data", "golden_harness.json")["configs"]
SEED = 12345


def _conf(name):
    if name.startswith("tiny:"):
        return copy.deepcopy(tiny.CONFIGS[name.removeprefix("tiny:")])
    return load_json("configs", name + ".json")


def _template(cfg):
    from repro.train import init_state
    return jax.eval_shape(
        lambda: init_state(cfg, jax.random.PRNGKey(0)))["params"]


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_config_leaves_and_flops_as_before(name):
    want, conf = GOLDEN[name], _conf(name)
    cfg = model_config(conf)
    assert repr(cfg) == want["model_config"]
    tmpl = _template(cfg)
    names = jax.eval_shape(_named_norms(conf["vocab_size"]), tmpl, tmpl, 1.0)
    assert sorted(names) == want["leaves"]
    assert flops.train_per_token(conf, want["seq_len"]) == \
        want["train_per_token"]


@pytest.mark.parametrize("name", sorted(k for k in GOLDEN
                                        if "readings" in GOLDEN[k]))
def test_reference_readings_as_before(name):
    want, conf = GOLDEN[name]["readings"], _conf(name)
    traffic = tiny.traffic("train-guarded")
    ref = reference.load(conf)
    got = rtrain.readings(ref, ref.dims(conf), traffic["optimizer"], SEED,
                          traffic["rows_per_data_replica"],
                          traffic["seq_len"])
    assert got["losses"] == pytest.approx(want["losses"], rel=1e-6)
    for part in ("grad", "change", "raw_grad"):
        assert set(got[part]) == set(want[part]), part
        assert got[part] == pytest.approx(want[part], rel=1e-6), part


@pytest.mark.parametrize("name,key,value", [
    ("falcon-mamba-7b.L1", "state_size", 8),
    ("granite-3-8b.L1", "num_key_value_heads", 4),
])
def test_a_width_the_program_does_not_run_is_refused(name, key, value):
    conf = dict(tiny.CONFIGS[name], **{key: value})
    with pytest.raises(ValueError, match=key):
        model_config(conf)


def test_a_file_lacking_a_key_is_refused():
    conf = dict(tiny.CONFIGS["falcon-mamba-7b.L1"])
    del conf["tie_word_embeddings"]
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        model_config(conf)


def _flat(tree):
    """{key: array} over a layer's subtree, by each array's own key."""
    out = {}
    for k, v in tree.items():
        out.update(_flat(v) if isinstance(v, dict) else {k: v})
    return out


def test_scanned_pattern_names_every_layer():
    """gemma2's tiny preset scans a (local, full) pattern over 4 layers:
    row r of blocks.l<p> is layer 2r + p, with the shapes an unscanned
    stack gives that layer."""
    from repro.models import get_config, init_params
    cfg = get_config("gemma2-27b", tiny=True)
    assert cfg.scan_layers and len(cfg.pattern) == 2
    params = init_params(cfg, jax.random.PRNGKey(1))
    named = named_leaves(params, cfg.vocab_size)
    flat = jax.eval_shape(lambda: init_params(
        dataclasses.replace(cfg, scan_layers=False),
        jax.random.PRNGKey(1)))["layers"]
    for i in range(cfg.num_layers):
        block = params["blocks"][f"l{i % 2}"]
        want = _flat(flat[f"layer_{i}"])
        got = {k.removeprefix(f"L{i}."): v for k, v in named.items()
               if k.startswith(f"L{i}.")}
        assert set(got) == set(want)
        assert all(got[k].shape == want[k].shape for k in want)
        rows = _flat(jax.tree.map(lambda x: x[i // 2], block))
        assert all((got[k] == rows[k]).all() for k in want)
    assert len(named) == 2 + sum(
        len(_flat(v)) for v in flat.values())


def test_unscanned_stack_names_every_layer():
    """recurrentgemma's tiny preset holds 5 layers of two kinds, unscanned,
    in layers.layer_<i>."""
    from repro.models import get_config, init_params
    cfg = get_config("recurrentgemma-2b", tiny=True)
    assert not cfg.scan_layers
    params = init_params(cfg, jax.random.PRNGKey(2))
    named = named_leaves(params, cfg.vocab_size)
    kinds = cfg.layer_kinds()
    assert len(set(kinds)) == 2
    for i, kind in enumerate(kinds):
        want = _flat(params["layers"][f"layer_{i}"])
        got = {k.removeprefix(f"L{i}."): v for k, v in named.items()
               if k.startswith(f"L{i}.")}
        assert set(got) == set(want)
        assert all(got[k] is want[k] for k in want)
        assert ("wq" in got) == (kind == "local"), (i, kind)
    assert named["embed"].shape == (cfg.vocab_size, cfg.d_model)


def test_flops_take_the_layers_from_the_named_module(monkeypatch):
    """A file that names its own reference module gets its layers' count
    from there; the head term stays the harness's."""
    fake = types.ModuleType("bench.reference.fake_family")
    fake.layers_forward_per_token = lambda c, context: 1000.0 * context
    monkeypatch.setitem(sys.modules, "bench.reference.fake_family", fake)
    conf = {"reference": "fake_family", "vocab_size": 10, "hidden_size": 8}
    assert flops.forward_per_token(conf, 3) == 3000.0 + 2 * 10 * 8


@pytest.mark.parametrize("name", ["../model", "bench.reference.model", ""])
def test_a_reference_outside_the_package_is_refused(name):
    with pytest.raises(ValueError):
        reference.load({"name": "x", "reference": name})

