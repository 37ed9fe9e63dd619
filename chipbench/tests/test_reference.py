"""The plain reference against the system at a tiny size on the CPU, where
both compute true float32: the served tokens are the reference's own best
(gap 0) and the seeded weights are the same numbers. Then the control: the
lower precision the configuration names (bfloat16) reads far above the
sound run, and a broken timed path comes out not correct."""

import json

import jax
import numpy as np
import pytest

from helpers import fixture, run


@pytest.fixture(scope="module")
def serve_cfg():
    return fixture("tiny-serve.json")


# the offline-batch mix PERF.md keeps for a later PR (section 7, row 1), at
# a size a test can hold: a traffic file's contents, and nothing else
BACKLOG = {
    "generator": "backlog", "requests": 40, "attempted": "admitted",
    "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                   "min": 16, "max": 96},
    "output_len": {"dist": "uniform", "min": 6, "max": 10},
    "drain_s": 20,
}


def test_seeded_weights_are_the_programs(serve_cfg):
    from chipbench.reference import mixtral as ref
    from chipbench.runners import serve
    from uccl_tpu.models import moe_inference

    key = jax.random.PRNGKey(2**31 + 9)
    rec = serve.sp.Recorder(annotate=False)
    _, backend, _ = serve.build(serve_cfg, 2**31 + 9, rec)
    mine = ref.init_weights(key, serve_cfg, 12)
    theirs = moe_inference.init_params(key, backend.server.cfg)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        # the same draws; a fused scale may round the last bit differently
        assert np.allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=0)
    # and the runner hands the backend those numbers, placed for serving
    placed = backend.server.shard_params(theirs)
    for a, b in zip(jax.tree.leaves(placed), jax.tree.leaves(backend.params)):
        assert a.shape == b.shape
        assert np.allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=0)
    assert backend.server.cfg.rope_theta == 1e6
    assert backend.server.cfg.norm_eps == 1e-5


def test_served_tokens_are_the_references_best_and_bf16_is_not(serve_cfg):
    lines = []
    out = run("tiny.chat", serve_cfg, fixture("tiny-chat.json"),
              controls=("bf16",), lines=lines)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 9
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]  # the compared numbers last
    assert all(set(c) == {"value", "limit"} for c in out["compared"].values())
    assert out["correct"] == all(c["value"] <= c["limit"]
                                 for c in out["compared"].values())
    json.dumps(out)  # the line prints: no numpy number in it
    assert set(out["metrics"]) == {"setup_s", "ttft_mean_ms", "serve_tok_s"}
    compares = [l for l in lines if " compare " in l]
    assert len(compares) >= 6 and all("limit=" in l for l in compares)
    numbers = [l for l in lines if '"control_numbers"' in l][0]
    rec = json.loads(numbers.split("chipbench: ", 1)[1])
    limits = serve_cfg["correct"]["limits"]
    low = rec["control_numbers"]["bf16"]
    for r in ("published", "stated"):
        assert rec["numbers"][r]["gap_max"] == 0.0
    assert low["published"]["gap_p99"] > 3 * limits["published_gap_p99"]
    assert low["stated"]["clear_miss_share"] \
        > 3 * limits["stated_clear_miss_share"]


def test_backlog_mix_is_data_only(serve_cfg):
    """The cell PERF.md keeps for later runs from a traffic file alone."""
    out = run("tiny.backlog", serve_cfg, BACKLOG, seconds=0.05)
    # what the window closed on is withdrawn, not failed
    assert out["correct"] is True and out["failed"] == 0
    assert 4 <= out["attempted"] < 40
    assert out["metrics"]["serve_tok_s"]["value"] > 0


class _Req:
    """As much of the engine's ``Request`` as ``reduce_window`` reads."""
    t_admit = t_submit = None
    finish_reason = "length"

    def __init__(self, n_prompt, done=True):
        self.prompt = np.zeros(n_prompt, np.int32)
        self._done = done

    def is_done(self):
        return self._done


def test_a_request_without_a_first_token_has_a_finite_ttft():
    from chipbench.runners import serve

    served = [serve.Served(due_s=1.0, prompt_tokens=4, submit_s=1.0,
                           req=_Req(4, done=False)),
              # never offered: the window closed
              serve.Served(due_s=2.0, prompt_tokens=8)]
    e2e = serve.reduce_window(served, 3.0, "due", end_s=5.0)
    assert e2e["attempted"] == 2 and e2e["failed"] == 2
    assert e2e["ttft_mean_ms"] == pytest.approx(1e3 * (4.0 + 3.0) / 2)
    assert np.isfinite(e2e["ttft_p90_ms"])
    # 4 s over 4 tokens and 3 s over 8: 1,000,000 and 375,000 ms a thousand
    assert e2e["ttft_per_ktok_p50_ms"] == pytest.approx((1e6 + 3.75e5) / 2)
    assert e2e["requests"] == [[4, 4000.0, 0], [8, 3000.0, 0]]


def test_ttft_per_thousand_prompt_tokens_is_the_median_over_requests():
    """Chunk 128: a prompt of one chunk, three of 4-16 chunks at 40 ms a
    chunk step, one that met a slow step, and one that never got its first
    token (counted with the time it had waited when the drain ended)."""
    from chipbench.runners import serve

    def one(due, n_prompt, ttft_s=None):
        stamps = [] if ttft_s is None else [due + ttft_s, due + ttft_s + 0.02]
        return serve.Served(due_s=due, prompt_tokens=n_prompt, submit_s=due,
                            req=_Req(n_prompt, done=ttft_s is not None),
                            stamps=stamps)

    served = [one(0.0, 100, 0.040),    # 400 ms a thousand: a single chunk
              one(1.0, 512, 0.160),    # 312.5
              one(2.0, 1024, 0.320),   # 312.5
              one(3.0, 2048, 0.640),   # 312.5
              one(4.0, 1000, 1.500),   # 1,500: the pool-wide program's step
              one(5.0, 2000)]          # no first token by 9.0: 4 s, 2,000
    e2e = serve.reduce_window(served, 6.0, "due", end_s=9.0)
    assert e2e["attempted"] == 6 and e2e["failed"] == 1
    assert e2e["n_first_tokens"] == 5
    per_ktok = sorted([400.0, 312.5, 312.5, 312.5, 1500.0, 2000.0])
    assert e2e["ttft_per_ktok_p50_ms"] == pytest.approx(
        (per_ktok[2] + per_ktok[3]) / 2)  # 356.25: the two far ones weigh 0
    # the mean follows them: the same six requests
    assert e2e["ttft_mean_ms"] == pytest.approx(
        1e3 * (0.04 + 0.16 + 0.32 + 0.64 + 1.5 + 4.0) / 6)
    assert [r[0] for r in e2e["requests"]] == [100, 512, 1024, 2048, 1000, 2000]
    assert [r[2] for r in e2e["requests"]] == [1, 1, 1, 1, 1, 0]
    assert e2e["requests"][-1][1] == pytest.approx(4000.0)


def test_the_90th_percentile_gap_lies_inside_a_kind_of_step_the_95th_on_its_edge():
    """100 gaps: 70 behind a decode program alone (22 ms), 25 behind a
    one-row prefill program (46 ms), 5 or 6 behind a slower one (60 ms). One
    gap more of the last kind lifts the 95th percentile by a quarter and
    leaves the 90th where it was: why ``.code-turns`` is judged on the 90th."""
    from chipbench.runners import serve

    def window(n_slow):
        gaps = [0.022] * 70 + [0.046] * (30 - n_slow) + [0.060] * n_slow
        stamps = list(np.cumsum([1.0] + gaps))
        return serve.reduce_window(
            [serve.Served(due_s=0.5, prompt_tokens=128, submit_s=0.5,
                          req=_Req(128), stamps=stamps)], 60.0, "due")

    few, more = window(5), window(7)
    assert few["n_itl"] == more["n_itl"] == 100
    assert few["itl_p90_ms"] == more["itl_p90_ms"] == pytest.approx(46.0)
    assert few["itl_p95_ms"] < 47.0 and more["itl_p95_ms"] == pytest.approx(60.0)


def test_steps_a_busy_host_delays_move_the_95th_percentile_gap_not_the_90th():
    """``.chat``'s shares: 82 gaps behind a decode-only step (20.4 ms), 16
    behind a one-row prefill program (34.3), 2 behind a wider one (36.5). A
    host that delays a quarter of the one-row steps by 2 ms (PERF.md section
    6: 13 busy processes beside the run) lifts the 95th percentile and leaves
    the 90th: why ``.chat`` is judged on the 90th too, with the 95th on the
    record as ``itl_p95_ms.chat``, and what ``itl_p80_to_p99_ms`` logs."""
    from chipbench import run as R
    from chipbench.runners import serve

    def window(delayed):
        gaps = ([0.0204] * 82 + [0.0343] * (16 - delayed)
                + [0.0363] * delayed + [0.0365] * 2)
        stamps = list(np.cumsum([1.0] + gaps))
        return serve.reduce_window(
            [serve.Served(due_s=0.5, prompt_tokens=128, submit_s=0.5,
                          req=_Req(128), stamps=stamps)], 60.0, "due")

    quiet, busy = window(0), window(4)
    assert quiet["itl_p90_ms"] == busy["itl_p90_ms"] == pytest.approx(34.3)
    assert quiet["itl_p95_ms"] == pytest.approx(34.3)
    assert busy["itl_p95_ms"] > 36.0
    tail = busy["itl_p80_to_p99_ms"]
    assert len(tail) == 20 and tail == sorted(tail)
    assert tail[10] == pytest.approx(busy["itl_p90_ms"], abs=1e-3)
    assert tail[15] == pytest.approx(busy["itl_p95_ms"], abs=1e-3)

    class View:
        record = {"e2e": busy}

    assert R.load_reader("itl_p95_ms").read(View) == busy["itl_p95_ms"]


def test_an_altered_token_is_not_correct(serve_cfg, monkeypatch):
    from uccl_tpu.serving import MoEBackend

    real = MoEBackend.decode

    def broken(self, tokens, active, **kw):
        out = np.array(real(self, tokens, active, **kw))
        out[active] = (out[active] + 1) % 256  # altered where it is produced
        return out

    monkeypatch.setattr(MoEBackend, "decode", broken)
    out = run("tiny.chat", serve_cfg, fixture("tiny-chat.json"))
    assert out["correct"] is False
