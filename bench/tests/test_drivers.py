"""Each driver's plumbing, end to end on the CPU at the program's tiny
presets: a whole run through ``bench/run.py`` past its look for a chip,
with the cell's own limits."""
from __future__ import annotations

import pytest

from bench import run
from bench.tests import tiny


def tiny_run(cell, seconds=1.0, trace=False, seed=2 ** 31 + 7):
    bench = tiny.manifest()
    return run.run_cell(cell, seed, seconds, trace, bench=bench,
                        ctx_overrides=tiny.overrides(cell, bench),
                        require_chip=False)


@pytest.mark.parametrize("cell", ["granite-train-guarded",
                                  "falcon-mamba-train-guarded",
                                  "falcon-mamba-untied-train-guarded"])
def test_train_cell(cell):
    res = tiny_run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert res["checks"]["restore_mismatches"]["value"] == 0


def test_untied_head_is_compared(monkeypatch):
    """A file that unties the head gets an untied program and reference,
    and the head is among the leaves compared, on both sides."""
    from bench.reference import train as rtrain
    seen = []
    compare = rtrain.compare

    def recording(got, want):
        seen.append((set(got["grad"]), set(want["grad"]),
                     set(got["change"])))
        return compare(got, want)
    monkeypatch.setattr(rtrain, "compare", recording)
    res = tiny_run("falcon-mamba-untied-train-guarded")
    assert res["correct"], res["checks"]
    (got, want, change), = seen
    assert "head" in got and got == want == change


def test_bare_train_cell():
    """Every guard off: no save in the window, so no round trip to check."""
    res = tiny_run("granite-train-bare")
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["checks"]) == {"loss_rel", "grad_leaf_rel",
                                  "change_leaf_rel"}


PEAKS = (1e12, 1e11, 1e10)


def test_traced_train_run_reads_its_layers(monkeypatch):
    """A traced run reports the per-layer metrics its readers find; the
    CPU has no device plane, so those read from device ops stay out."""
    import bench.common
    monkeypatch.setattr(bench.common, "peak_flops_bytes", lambda kind: PEAKS)
    res = tiny_run("granite-train-guarded", trace=True)
    assert res["correct"], res["checks"]
    got = set(res["metrics"])
    assert {"ckpt.stall_ms", "sdc.guard_ms_per_step", "train.mfu"} <= got
    assert "scrub_checksum_roofline" not in got
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_chip_no_result(capsys):
    """Without a TPU the command exits non-zero and prints no result."""
    rc = run.main(["--workload", "granite-train-guarded", "--seed", "1",
                   "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""
