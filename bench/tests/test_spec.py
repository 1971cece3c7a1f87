"""BENCHMARK.json and the files it names: every cell, configuration, mix
and metric is found by name, and the file keeps the benchmark contract's
shape."""

import json
import os
import re

import pytest

from bench import harness, reference

ROOT = harness.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert all(not w.startswith("/") and ".." not in w
               for w in SPEC["command"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    loaded = harness.load_cell(ROOT, cell)
    geo = reference.Geometry.from_config(loaded["config"])
    assert geo.dim % 32 == 0 and loaded["sizes"]["sessions"] > 0
    mix = loaded["mix"]
    assert mix["tick_s"] > 0
    assert mix["pool_cycles"] % mix["cycles"] == 0
    assert loaded["cell"]["chips"] in (1, 4)
    reported = {m["name"] for m in harness.metrics_for(
        SPEC, cell, "end_to_end")}
    assert "setup_s" in reported and len(reported) >= 2
    assert harness.metrics_for(SPEC, cell, "per_layer")


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_has_a_reader(metric):
    path = os.path.join(ROOT, "bench", "metrics", metric + ".py")
    assert os.path.exists(path)
    with open(path) as f:
        assert "def read(run)" in f.read()


def test_names_units_and_bounds():
    names = [c["name"] for c in SPEC["configs"]] + CELLS + [
        m["name"] for m in METRICS]
    assert len(set(CELLS)) == len(CELLS)
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    assert all(NAME.match(n) for n in names)
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "mfu" not in m["name"]
        for cell in m["workloads"]:
            assert cell in next(e for e in SPEC["end_to_end"]
                                if e["name"] == m["moves"]).get(
                                    "workloads", CELLS)


def test_configs_and_cells():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    assert len({(w["config"], w["traffic"]) for w in SPEC["workloads"]}) \
        == len(CELLS)
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(CELLS) // 2)
    for w in SPEC["workloads"] + SPEC["configs"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert len(json.dumps(SPEC)) < 64 * 1024
