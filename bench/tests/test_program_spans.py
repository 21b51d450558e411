"""The program's own spans and counters inside the training driver's job,
turned on by attaching an ``Observability`` to it from the test: their
names fit the trace reduction's pattern and reuse none of the benchmark's
own, and the scrubber's count of checksummed bytes over the window is the
driver's."""
from __future__ import annotations

import pytest

from bench import run, trace
from bench.tests.test_drivers import PEAKS, tiny_run
from repro.core.api import Dependability
from repro.obs import Counter, Observability

LOOP = {"data.batch", "train.dispatch", "train.sync", "train.bookkeep"}
GUARDED = LOOP | {"sdc.leaves", "sdc.reduce", "sdc.fetch", "sdc.loss",
                  "ckpt.local", "ckpt.snapshot", "ckpt.write", "ckpt.commit",
                  "ckpt.drain"}


def _counters(obs):
    """The program's counters by name, summed over their labels."""
    out = {}
    for c in obs.registry.instruments():
        if isinstance(c, Counter):
            out[c.name] = out.get(c.name, 0.0) + c.value
    return out


@pytest.fixture
def observed(monkeypatch):
    """The next run's job gets an ``Observability``; the fixture keeps it,
    its counters when the window opens and closes, and the driver's
    output as its ``check`` is given it."""
    import bench.common
    from bench.drivers import train
    monkeypatch.setattr(bench.common, "peak_flops_bytes", lambda kind: PEAKS)
    got = {"obs": Observability(), "counters": []}
    start, check = Dependability.start, train.check

    def attached(self):
        return start(self).attach_obs(got["obs"])

    class Counting(run.Tracer):
        def start(self):
            got["counters"].append(_counters(got["obs"]))
            super().start()

        def stop(self):
            super().stop()
            got["counters"].append(_counters(got["obs"]))

    def keeping(ctx, out):
        got["out"] = out
        return check(ctx, out)
    monkeypatch.setattr(Dependability, "start", attached)
    monkeypatch.setattr(run, "Tracer", Counting)
    monkeypatch.setattr(train, "check", keeping)
    return got


@pytest.mark.parametrize("cell,names", [("granite-train-guarded", GUARDED),
                                        ("granite-train-bare", LOOP)])
def test_program_spans_in_a_traced_run(observed, cell, names):
    res = tiny_run(cell, trace=True)
    assert res["correct"], res["checks"]
    log = observed["obs"].registry.spans
    got = {n for n, *_ in log.records()}
    assert got == names and log.dropped == 0
    assert all(trace.SPAN.match(n) for n in got)
    out = observed["out"]
    assert not got & ({n for n, *_ in out["spans"]} | {trace.WINDOW})
    before, after = observed["counters"]
    window = (after.get("sdc.checksummed_bytes", 0.0)
              - before.get("sdc.checksummed_bytes", 0.0))
    assert window == out["train"]["checksummed_bytes"]
    assert (window > 0) == (cell == "granite-train-guarded")
