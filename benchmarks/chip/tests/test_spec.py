"""Every entry of BENCHMARK.json resolves to files of its own: a cell's
configuration, traffic mix and limits, and each metric's reader. A later
cell or metric is new files plus new entries."""
import json
import re

import pytest

from harness import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = spec.load_cell(cell, BENCH)
    assert c.traffic["kind"] == "train"
    assert c.limits, "a cell without limits can never be correct"
    for lim in c.limits.values():
        assert lim["lower"] < lim["limit"] < lim["upper"]
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
    spec.model_config(c.config)


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_has_reader(metric):
    read = spec.metric_reader(metric)
    assert callable(read)


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_states_its_cuts(conf):
    f = json.loads((spec.ROOT / conf["file"]).read_text())
    assert f["name"] == conf["name"]
    assert conf["file"].startswith(BENCH["paths"][0] + "/")
    published = f.get("published", {})
    assert sorted(published) == sorted(conf["reduced"])
    for key in conf["reduced"]:
        assert f[key] != published[key]
        assert not key.endswith(("_dim", "_rank", "_size")), \
            "a width is never cut"


def test_names_and_units():
    for entry in BENCH["configs"] + BENCH["workloads"] + METRICS:
        assert NAME.match(entry["name"]), entry["name"]
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in CELLS
    names = [e["name"] for e in METRICS]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
