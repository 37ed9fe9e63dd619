"""The plain reference against the system at a tiny size on the CPU, where
both compute true float32: the served tokens are the reference's own best
(gap 0) and the seeded weights are the same numbers. Then the control: the
lower precision the configuration names (bfloat16) reads far above the
sound run, and a broken timed path comes out not correct."""

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
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(out["metrics"]) == {"setup_s", "ttft_mean_ms", "serve_tok_s"}
    compares = [l for l in lines if " compare " in l]
    assert len(compares) >= 6 and all("limit=" in l for l in compares)
    numbers = [l for l in lines if '"control_numbers"' in l][0]
    import json
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


def test_a_request_without_a_first_token_has_a_finite_ttft():
    from chipbench.runners import serve

    class Req:
        t_admit = t_submit = None
        prompt = np.zeros(4, np.int32)

        def is_done(self):
            return False

    served = [serve.Served(due_s=1.0, submit_s=1.0, req=Req()),
              serve.Served(due_s=2.0)]  # never offered: the window closed
    e2e = serve.reduce_window(served, 3.0, "due", end_s=5.0)
    assert e2e["attempted"] == 2 and e2e["failed"] == 2
    assert e2e["ttft_mean_ms"] == pytest.approx(1e3 * (4.0 + 3.0) / 2)
    assert np.isfinite(e2e["ttft_p90_ms"])


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
