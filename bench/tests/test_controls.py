"""The controls come out as not correct: the reference computed with
float8 products in the program's place, at the tiny presets on the CPU
and under the limits set for those sizes (``bench/tests/tiny.py``;
``bench/tools/controls.py`` reads the same at the cells' own sizes on the
chip, where the cells' own limits hold)."""
from __future__ import annotations

import pytest

from bench import reference
from bench.reference import train as rtrain
from bench.tests import tiny


@pytest.mark.parametrize("cell", ["granite-train-guarded",
                                  "falcon-mamba-train-guarded",
                                  "falcon-mamba-untied-train-guarded"])
def test_train_control_fails(cell):
    ov = tiny.overrides(cell, tiny.manifest())
    conf, traffic = ov["config"], ov["traffic"]
    ref = reference.load(conf)
    m, opt = ref.dims(conf), traffic["optimizer"]
    rows, seq = traffic["rows_per_data_replica"], traffic["seq_len"]
    want = rtrain.readings(ref, m, opt, 5, rows, seq)
    got = rtrain.compare(rtrain.readings(ref, m, opt, 5, rows, seq, "fp8"),
                         want)
    limits = ov["limits"]
    assert any(v > limits[k] for k, v in got.items()), got
