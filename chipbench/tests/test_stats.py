"""The spread on hand-made sets, and the tool that prints it: the range
with the farthest run left out, by which the driver's check decides whether
a bound holds."""

import json

import pytest

from chipbench import stats
from chipbench.tools import spread as tool


@pytest.mark.parametrize("runs, by_range", [
    # six runs two apart: one end goes (95 or 105), a range of 8 stays
    ([95, 97, 99, 101, 103, 105], 0.08),
    # one run far off: it is the one left out, and the rest agree to 2 %
    ([100, 101, 99, 100.5, 99.5, 140], 2 / 100.25),
    # two far-off runs: one stays in, and the spread says so
    ([100, 101, 99, 100, 140, 141], 41 / 100.5),
    # the far run lies below
    ([46.3, 46.6, 46.5, 46.5, 46.4, 30.0], 0.3 / 46.45),
    # two runs: nothing to leave out
    ([10.0, 11.0], 1 / 10.5),
])
def test_range_spread_leaves_out_the_farthest_run(runs, by_range):
    assert stats.range_spread(runs) == pytest.approx(by_range)


def test_too_few_runs_have_no_spread():
    assert stats.range_spread([3.0]) is None and stats.range_spread([]) is None
    assert stats.range_spread([0.0, 0.0, 0.0]) is None  # no median to share


def test_the_tool_derives_the_bound_from_the_drivers_rule(tmp_path, capsys):
    def line(ttft, itl, setup):
        return json.dumps({"correct": True, "attempted": 20, "failed": 0,
                           "metrics": {"ttft_mean_ms": {"value": ttft},
                                       "itl_p95_ms": {"value": itl},
                                       "setup_s": {"value": setup}}})

    sets = [[(800, 46.5, 110), (810, 46.6, 21), (790, 46.4, 22),
             (805, 46.5, 21.5), (795, 46.5, 21.2), (990, 46.5, 21.1)],
            [(800, 46.5, 105), (840, 46.6, 21), (760, 46.4, 22),
             (805, 46.5, 21.5), (795, 46.5, 21.2), (802, 46.5, 21.1)]]
    paths = []
    for k, runs in enumerate(sets):
        p = tmp_path / f"set{k}.jsonl"
        p.write_text("chipbench: a log line\n"
                     + "\n".join(line(*r) for r in runs) + "\n")
        paths.append(str(p))
    tool.main(*paths)
    out = {l.split(":")[0]: l for l in capsys.readouterr().out.splitlines()
           if not l.startswith(" ")}
    # set 1's 990 is left out (20 of 802.5); set 2 keeps 760 (45 of 801)
    assert "spread 2.492%" in out["ttft_mean_ms"]
    assert "spread 5.618%" in out["ttft_mean_ms"]
    assert out["ttft_mean_ms"].endswith(
        "widest 5.618% -> bound >= 11.24%, <= 44.94%")
    # the compiling first run of a set is not in setup_s' spread
    assert "median 21.2 " in out["setup_s"]
