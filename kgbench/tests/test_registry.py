"""The harness finds every cell's configuration, traffic mix, loop and
metric readers by name, and BENCHMARK.json keeps to its contract."""
import json
import os
import re

import pytest

from kgbench import registry

SPEC = registry.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][0] == "python3"
    for word in SPEC["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert any(word.startswith(p + "/") for p in SPEC["paths"])
    assert 1 <= SPEC["run_seconds"] <= 51


def test_texts_fit_the_contract():
    assert os.path.getsize(os.path.join(registry.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    texts = [e[k] for k in ("why", "source", "layer")
             for part in ("configs", "workloads", "per_layer")
             for e in SPEC[part] if k in e] + SPEC["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
    for e in SPEC["configs"]:
        assert len(e["reduced"]) <= 16
        assert set(e) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert len({(w["config"], w["traffic"]) for w in SPEC["workloads"]}) \
        == len(SPEC["workloads"])


def test_names_units_and_entries():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_resolves_with_metrics_and_readers(workload):
    cell = registry.resolve(workload)
    e2e = {m["name"] for m in cell.end_to_end()}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cell.per_layer()
    assert layer, "every cell reports a per-layer metric"
    for m in layer:
        assert callable(registry.metric_reader(m["name"]).read)
        assert m["moves"] in e2e
    loop = cell.loop()
    for fn in ("setup", "window", "check", "program_output",
               "control_output", "recompiles"):
        assert callable(getattr(loop, fn))
    assert callable(cell.shape().deployment)
    assert cell.workload["chips"] in (1, 4)


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_config_file_states_what_it_cut(entry):
    path = os.path.join(registry.ROOT, entry["file"])
    with open(path) as f:
        cfg = json.load(f)
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    assert all(k in cfg for k in entry["reduced"])
    assert "assumed" in cfg and "guarantees" in cfg
    assert os.path.isfile(os.path.join(registry.HERE, "shapes",
                                       cfg["shape"] + ".py"))


def test_every_reader_file_is_a_metric():
    readers = {f[:-3] for f in os.listdir(os.path.join(registry.HERE,
                                                       "metrics"))
               if f.endswith(".py")}
    assert readers == {m["name"] for m in SPEC["per_layer"]}


def test_unknown_workload_is_named():
    with pytest.raises(KeyError, match="no workload named"):
        registry.resolve("no-such-cell")
