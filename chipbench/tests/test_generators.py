import numpy as np

from chipbench.generators import requests as gen

MIX = {"generator": "open_loop", "rate_rps": 2.0,
       "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                      "min": 32, "max": 2048},
       "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                      "min": 16, "max": 512}}


def test_same_seed_same_traffic():
    a = gen.generate(MIX, 2**31 + 5, 30.0, 32000)
    b = gen.generate(MIX, 2**31 + 5, 30.0, 32000)
    assert np.array_equal(a.due_s, b.due_s)
    assert np.array_equal(a.output_lens, b.output_lens)
    assert all(np.array_equal(p, q) for p, q in zip(a.prompts, b.prompts))


def test_seeds_offer_the_same_work_in_another_order():
    a = gen.generate(MIX, 1, 30.0, 32000)
    b = gen.generate(MIX, 2, 30.0, 32000)
    assert sorted(map(len, a.prompts)) == sorted(map(len, b.prompts))
    assert sorted(a.output_lens) == sorted(b.output_lens)
    assert [len(p) for p in a.prompts] != [len(p) for p in b.prompts]
    assert np.allclose(np.sort(np.diff(a.due_s)), np.sort(np.diff(b.due_s)))


def test_open_loop_count_window_and_clipping():
    t = gen.generate(MIX, 3, 30.0, 32000)
    assert len(t.prompts) == 60
    assert t.due_s[0] == 0.0 and np.all(np.diff(t.due_s) > 0)
    assert 29.0 < t.due_s[-1] < 30.0
    lens = np.array([len(p) for p in t.prompts])
    assert lens.min() >= 32 and lens.max() <= 2048
    assert 200 < np.median(lens) < 320
    assert t.output_lens.min() >= 16 and t.output_lens.max() <= 512
    assert all(p.dtype == np.int32 and p.max() < 32000 for p in t.prompts)


def test_backlog_is_all_due_at_zero():
    mix = {"generator": "backlog", "requests": 2000,
           "prompt_len": {"dist": "lognormal", "median": 1536, "sigma": 0.5,
                          "min": 512, "max": 3584},
           "output_len": {"dist": "uniform", "min": 8, "max": 32}}
    t = gen.generate(mix, 4, 51.0, 32000)
    assert len(t.prompts) == 2000 and not t.due_s.any()
    lens = np.array([len(p) for p in t.prompts])
    assert lens.min() >= 512 and lens.max() <= 3584
    assert t.output_lens.min() >= 8 and t.output_lens.max() <= 32
    assert (lens + t.output_lens).max() <= 4096


def test_a_pinned_order_is_one_schedule_for_every_seed():
    """``order_seed``: every seed offers the same lengths at the same times —
    the arrangement ``--seed order_seed`` drew before the key existed — and
    draws only the tokens."""
    pinned = dict(MIX, order_seed=576721147)
    was = gen.generate(MIX, 576721147, 30.0, 32000)
    a = gen.generate(pinned, 1, 30.0, 32000)
    b = gen.generate(pinned, 2**31 + 7, 30.0, 32000)
    for t in (a, b):
        assert np.array_equal(t.due_s, was.due_s)
        assert np.array_equal(t.output_lens, was.output_lens)
        assert [len(p) for p in t.prompts] == [len(p) for p in was.prompts]
        assert all(p.dtype == np.int32 and 0 <= p.min() and p.max() < 32000
                   for p in t.prompts)
    assert not any(np.array_equal(p, q) for p, q in zip(a.prompts, b.prompts))
    again = gen.generate(pinned, 1, 30.0, 32000)
    assert all(np.array_equal(p, q) for p, q in zip(a.prompts, again.prompts))


def test_the_cells_pin_their_order():
    """Both serving mixes replay one schedule: a seed changes no length and
    no arrival (PERF.md section 2 says why)."""
    import json
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name in ("chat", "code-turns"):
        with open(os.path.join(here, "traffic", name + ".json")) as f:
            mix = json.load(f)
        a = gen.generate(mix, 11, 51.0, 32000)
        b = gen.generate(mix, 2**31 + 12, 51.0, 32000)
        assert np.array_equal(a.due_s, b.due_s)
        assert [len(p) for p in a.prompts] == [len(p) for p in b.prompts]
        assert np.array_equal(a.output_lens, b.output_lens)
