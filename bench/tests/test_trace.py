"""The reduction from a profiler trace to busy time, per-op time and idle
gaps: on a trace written out by hand, and on a small trace recorded on a
TPU v5e chip (``bench/tools/trace_ops.py --small``)."""
from __future__ import annotations

import os

import pytest

from bench import trace

HOST = [("bench.window", 0.5, 4.0), ("bench.step", 0.9, 2.1),
        ("ckpt.save", 2.1, 3.0)]


def test_busy_ops_and_gaps_by_hand():
    tr = {"devices": {"/device:TPU:0": [("step/fusion.1", 1.0, 1.5),
                                        ("jit__device_sums/cc", 1.4, 2.0),
                                        ("jit__device_sums/cc", 1.5, 1.6),
                                        ("step/fusion.2", 3.0, 3.5),
                                        ("step/fusion.3", 3.9, 4.5)]},
          "host": HOST}
    red = trace.reduce(tr)
    assert red["window_s"] == pytest.approx(3.5)
    # union [1.0, 2.0] + [3.0, 3.5] + [3.9, 4.0], clipped to the window
    assert red["busy_s"] == pytest.approx(1.6)
    assert red["op_s"]["jit__device_sums/cc"] == pytest.approx(0.7)
    assert red["op_s"]["step/fusion.3"] == pytest.approx(0.1)
    # nested ops count once in their program's time
    assert red["program_s"][trace.CHECKSUM_PROGRAM] == pytest.approx(0.6)
    assert red["program_s"]["step"] == pytest.approx(1.1)
    gaps = dict(red["idle_gaps"])
    # [0.5, 1.0] inside the window span only; [2.0, 3.0] inside the save;
    # [3.5, 3.9] inside the window span only
    assert gaps["bench.window"] == pytest.approx(0.9)
    assert gaps["ckpt.save"] == pytest.approx(1.0)
    assert trace.idle_share(red) == pytest.approx(100 * (1 - 1.6 / 3.5))


def test_devices_are_averaged():
    one = [("a", 1.0, 2.0)]
    two = [("a", 1.0, 3.0)]
    red = trace.reduce({"devices": {"/device:TPU:0": one,
                                    "/device:TPU:1": two}, "host": HOST})
    assert red["busy_s"] == pytest.approx(1.5)
    assert red["op_s"]["a"] == pytest.approx(1.5)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": [("other", 0.0, 1.0)]})


SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_small_trace_recorded_on_the_chip():
    """Three steps of a jitted bf16 matmul, each followed by host work in
    a ``bench.host`` span, inside ``bench.window``."""
    from jax.profiler import ProfileData
    tr = trace.from_profile(ProfileData.from_file(SMALL))
    assert [p for p in tr["devices"] if "TPU" in p]
    red = trace.reduce(tr)
    assert 0 < red["busy_s"] < red["window_s"]
    names = [n for n, _ in red["device_ops"]]
    assert any("dot" in n or "fusion" in n or "convolution" in n
               for n in names), names
    gaps = dict(red["idle_gaps"])
    assert gaps.get("bench.host", 0) > 0.5 * sum(gaps.values())
