"""BENCHMARK.json against the benchmark's contract: names, units, keys and
sizes, and every file the harness finds by name."""
from __future__ import annotations

import json
import os
import re

import pytest

from bench.common import BENCH_DIR, ROOT, load_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
WIDTHS = re.compile(r"(_dim|_rank)$|^(hidden|intermediate|state|head|"
                    r"moe_intermediate|shared_intermediate)_size$|^expan|"
                    r"latent|proj|experts_per_tok")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_keys_and_sizes(bench):
    assert set(bench) == KEYS["top"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    for part in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[part]:
            extra = set(e) - KEYS[part]
            assert extra <= ({"workloads"} if part in ("end_to_end",
                                                       "per_layer") else set())
            assert KEYS[part] <= set(e), (part, e)
    assert 1 <= len(bench["configs"]) <= 24
    assert 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51


def test_command_and_paths(bench):
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = bench["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for w in cmd[1:]:
        assert not w.startswith("/") and ".." not in w
        if os.path.exists(os.path.join(ROOT, w)):
            assert any(w.startswith(p + "/") for p in bench["paths"])


def test_names_units_and_lines(bench):
    seen = set()
    for part in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[part]]
        assert len(names) == len(set(names)), part
        for e in bench[part]:
            assert NAME.match(e["name"]), e["name"]
            if part in ("end_to_end", "per_layer"):
                assert e["name"] not in seen
                seen.add(e["name"])
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e and part != "end_to_end" and part != "per_layer":
                    assert _line(e[k]), (e["name"], k)
            if part == "per_layer":
                assert _line(e["layer"])
    for c in bench["configs"]:
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTHS.search(k), k


def test_every_file_the_harness_finds_by_name(bench):
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        conf = json.load(open(os.path.join(ROOT, c["file"])))
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        assert set(conf["published"]) == set(c["reduced"])
        # a departure states the published value and what the program runs
        for k, d in conf.get("departures", {}).items():
            assert k in conf and k not in c["reduced"], k
            assert set(d) == {"as_run", "why"} and _line(d["why"]), k
        assert any(w["config"] == c["name"] for w in cells.values())
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(pairs) == len(set(pairs))
    for name, w in cells.items():
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"]) and NAME.match(w["traffic"])
        traffic = load_json("traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(BENCH_DIR, "drivers",
                                           traffic["driver"] + ".py"))
        assert load_json("cells", name + ".json")["limits"]
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 2)
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "metrics",
                                           m["name"] + ".py")), m["name"]


REFERENCE_FUNCTIONS = ("dims", "init_params", "loss", "leaf_norms",
                       "layers_forward_per_token")


def test_every_reference_module_exports_what_the_harness_calls(bench):
    """Each configuration's reference module (its ``reference`` key, else
    ``model``) exists and holds the family's keys, weights, loss, leaf
    names and FLOP terms; its table covers the file's family, with
    attributes the program's ModelConfig has."""
    import dataclasses

    from bench import reference
    from bench.tests import tiny
    from repro.models import ModelConfig
    attrs = {f.name for f in dataclasses.fields(ModelConfig)} | {
        k for k, v in vars(ModelConfig).items() if isinstance(v, property)}
    confs = [json.load(open(os.path.join(ROOT, c["file"])))
             for c in bench["configs"]] + list(tiny.CONFIGS.values())
    for conf in confs:
        name = conf.get("reference", "model")
        assert os.path.exists(os.path.join(BENCH_DIR, "reference",
                                           name + ".py")), name
        mod = reference.load(conf)
        assert all(callable(getattr(mod, f)) for f in REFERENCE_FUNCTIONS)
        table = mod.FIELDS[conf["family"]]
        assert {f.action for f in table.values()} == {reference.SET,
                                                      reference.CHECK}
        assert {f.attr for f in table.values()} <= attrs, name


def test_metrics_reach_every_cell(bench):
    """Every cell reports setup_s, another end-to-end metric and a
    per-layer metric; each per-layer metric moves an end-to-end metric
    that every cell it lists reports."""
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"] == cells
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.0 < m["bound"] <= 0.25
        assert e2e[m["name"]] <= cells
    for m in bench["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert set(m["workloads"]) <= e2e[m["moves"]]
    for c in cells:
        assert sum(c in w for n, w in e2e.items() if n != "setup_s") >= 1
        assert any(c in m["workloads"] for m in bench["per_layer"])
    layers = {}
    for m in bench["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_check_fits_the_time_limit(bench):
    """A full check of 24 cells at this run length fits 43200 seconds."""
    s = bench["run_seconds"]
    assert 2 * (s + 60) + 24 * (14 * (s + 60) + 2 * 90) + 1200 <= 43200


def test_peaks_table_names_its_source():
    peaks = load_json("peaks.json")
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9
