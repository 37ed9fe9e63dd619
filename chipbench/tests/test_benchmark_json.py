"""A dry parse of BENCHMARK.json against the rules a run cannot show."""

import os
import re

from chipbench import run as R

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    return R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))


def test_keys_and_names():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    names = []
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        names.append(w["name"])
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert m["source"] in SOURCES
    for m in b["end_to_end"] + b["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(b["workloads"]) // 4)


def test_every_cell_has_its_files_and_metrics():
    b = bench()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    cells = {w["name"] for w in b["workloads"]}
    used = set()
    for w in b["workloads"]:
        cell, cfg, mix = R.load_cell(b, w["name"])
        used.add(w["config"])
        assert os.path.exists(os.path.join(R.HERE, "runners", cfg["runner"] + ".py"))
        mine = [m["name"] for m in R.metrics_for(b["end_to_end"], w["name"])]
        assert "setup_s" in mine and len(mine) >= 2
        assert R.metrics_for(b["per_layer"], w["name"])
    assert used == {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert c["file"].startswith(b["paths"][0] + "/")
        cfg = R.load_json(os.path.join(R.ROOT, c["file"]))
        assert set(c["reduced"]) == set(cfg["reduced"])
    for m in b["per_layer"]:
        assert os.path.exists(os.path.join(R.HERE, "layer_metrics", m["name"] + ".py"))
        assert hasattr(R.load_reader(m["name"]), "read")
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert "workloads" not in moved or cell in moved["workloads"]
    for m in b["end_to_end"]:
        assert set(m.get("workloads", [])) <= cells


def test_layers_are_perf_mds():
    b = bench()
    with open(os.path.join(R.ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in b["per_layer"]:
        assert "\n" not in m["layer"] and m["layer"] in perf
