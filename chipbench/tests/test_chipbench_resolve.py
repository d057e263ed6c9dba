"""BENCHMARK.json: every cell, configuration, traffic mix and metric
resolves by name to its file, and the file keeps the benchmark's
contract."""
from __future__ import annotations

import json
import re

import pytest

from chipbench import bench

BENCH = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"][1:] == ["chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its 43200 s
    cells = 24
    runs = 2 + 14 * cells
    assert runs * (BENCH["run_seconds"] + 60) + cells * 180 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = bench.resolve(cell)
    assert c.chips in (1, 4)
    assert callable(c.driver.Driver)
    assert hasattr(c.generator, "generate")
    assert hasattr(c.reference, "answer") and hasattr(c.reference, "rounds")
    for m in c.end_to_end + c.per_layer:
        assert callable(c.readers[m["name"]].read)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    # a per-layer metric's cells report the end-to-end metric it moves
    for m in c.per_layer:
        assert m["moves"] in names


def test_names_units_and_entries():
    seen = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(0 < len(c[k]) <= 200 for k in ("source", "why"))
        assert c["file"].startswith("chipbench/")
        cfg = json.loads((bench.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(cfg["reduced"]) == set(c["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
        seen.add(c["name"])
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in seen and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(BENCH["workloads"])
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    assert len((bench.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        bench.resolve("no-such-cell")
