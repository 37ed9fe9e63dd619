"""A dry parse of BENCHMARK.json against the rules a run cannot show."""

import os
import re
import textwrap

import pytest

from chipbench import families
from chipbench import program_trace as pt
from chipbench import run as R
from helpers import clear_trace_caches

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


# PR 40's seven readings keep one entry a cell: the repository's tier-1
# ``tests/test_timelines.py`` asserts their count and their one-cell lists,
# and a benchmark PR may not touch it (PERF.md section 7).
PER_CELL = {"decode_dispatch_latency_ms", "decode_completion_latency_ms",
            "host_between_calls_ms", "device_programs_per_decode_call",
            "ttft_prefill_dev_share", "ttft_decode_dev_share",
            "ttft_idle_share"}


def test_an_entry_is_a_reading_not_a_cell():
    """One entry a reading, with the cells it is read in as its
    ``workloads``: no name carries a cell's suffix (but ``PER_CELL``), and a
    reader names no family, cell, configuration or traffic mix."""
    b = bench()
    assert len(b["per_layer"]) <= 128
    traffics = {w["traffic"] for w in b["workloads"]}
    named = ({w["name"] for w in b["workloads"]} | traffics
             | {c["name"] for c in b["configs"]}
             | {os.path.splitext(f)[0] for f in os.listdir(
                 os.path.join(R.HERE, "families")) if not f.startswith("_")}
             | {f[:-3] for f in os.listdir(R.HERE) if f.startswith("flops_")})
    for m in b["per_layer"]:
        assert m.get("workloads"), m["name"]
        base, _, last = m["name"].rpartition(".")
        if base in PER_CELL:
            assert last in traffics and len(m["workloads"]) == 1
            continue
        assert last not in traffics, m["name"]
        with open(os.path.join(R.HERE, "layer_metrics",
                               m["name"] + ".py")) as f:
            text = f.read()
        assert not [n for n in named if n in text], m["name"]
    readers = {f[:-3] for f in os.listdir(os.path.join(R.HERE, "layer_metrics"))
               if f.endswith(".py") and f != "__init__.py"}
    assert readers == {m["name"] for m in b["per_layer"]}


def _listed():
    return [(m["name"], cell) for m in bench()["per_layer"]
            for cell in m["workloads"]]


@pytest.mark.parametrize("metric,cell", _listed())
def test_a_reader_gives_none_on_a_run_without_a_trace(metric, cell):
    """For every cell an entry lists, with the family resolved as a run
    resolves it: a traced run of a program without spans raises nothing."""
    _, cfg, mix = R.load_cell(bench(), cell)
    view = R.TraceView(None, {"trace_path": None, "e2e": {},
                              "compiles_in_window": 0}, cfg, mix,
                       {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}, 1)
    assert view.family is families.of(cfg) is not None
    got = R.load_reader(metric).read(view)
    assert got is None or metric == "compiles_in_window"


def test_a_sixth_family_joins_three_readings_with_no_file_edited(
        tmp_path, monkeypatch):
    """The room is usable: a made-up family (a scope tuple, two groups, a
    counting function) and a configuration that names it, all in a
    temporary directory, read ``decode_step_dev_ms``, ``unscoped_dev_share``
    and ``decode_hbm_roofline_share`` through ``metrics_for`` +
    ``load_reader`` + ``TraceView`` once the cell's name is appended to
    their lists — what a later PR does by adding files."""
    (tmp_path / "made_up.py").write_text(textwrap.dedent("""
        SCOPES = ("state.update", "state.read", "moe.experts")
        GROUPS = {"state": ("state.update", "state.read"),
                  "moe_experts": ("moe.experts",)}

        def decode_step_bytes(cfg, facts):
            return 4.0 * cfg["state_size"] * facts["n"] + facts["kv_rows"]
    """))
    monkeypatch.setattr(families, "__path__",
                        list(families.__path__) + [str(tmp_path)])
    cfg = {"model_type": "made_up", "state_size": 1000}
    cell = "made-up-serve.ramble"
    b = bench()
    joined = ("decode_step_dev_ms", "unscoped_dev_share",
              "decode_hbm_roofline_share")
    for m in b["per_layer"]:
        if m["name"] in joined:
            m["workloads"] = m["workloads"] + [cell]
    mine = [m["name"] for m in R.metrics_for(b["per_layer"], cell)]
    assert sorted(mine) == sorted(joined)

    ms = 1e6
    j = "jit(p)/"
    spans = [(pt.DECODE, 0.0, 10 * ms, {"n": 2, "kv_rows": 500})]
    ops = [("a", 1 * ms, 2 * ms, j + "state.update/dot_general:"),
           ("w", 3 * ms, 4 * ms, ""),  # a loop around its scoped body
           ("b", 3 * ms, 3 * ms, j + "moe.experts/dot_general:"),
           ("c", 7 * ms, 1 * ms, j + "attn.core/dot_general:")]  # not its
    loaded = pt.ProgramTrace(spans, [ops])
    monkeypatch.setattr(pt, "load", lambda path: loaded)
    clear_trace_caches()
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [list(o[:3]) for o in ops]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["chipbench.window", 0.0, 20 * ms, {}],
            ["chipbench.backend_decode", 0.5 * ms, 9 * ms, {}]]}]}]}
    view = R.TraceView(trace, {"trace_path": "made-up"}, cfg, {},
                       {"hbm_bytes_per_s": 1e9}, 1)
    assert view.family.__name__ == "chipbench.families.made_up"
    got = {name: R.load_reader(name).read(view) for name in mine}
    assert got["decode_step_dev_ms"] == 7.0
    # of 7 ms busy, 5 under the family's scopes: the loop's last turn and
    # another family's scope are not
    assert got["unscoped_dev_share"] == pytest.approx(100.0 * 2 / 7)
    assert got["decode_hbm_roofline_share"] == pytest.approx(
        100.0 * (4.0 * 1000 * 2 + 500) / 1e9 / 7e-3)
    # a reading whose group the family has not: None, nothing raised
    assert R.load_reader("decode_conv_dev_ms").read(view) is None
    assert R.load_reader("decode_full_attention_roofline_share").read(
        view) is None
    clear_trace_caches()
