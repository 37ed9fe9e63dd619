"""The ``glm4_moe_lite`` configuration's pieces at a tiny size on the CPU,
where program and reference both compute true float32: the seeded weights
are the same numbers, the served tokens are the reference's own best, the
bfloat16-activation control reads far above the sound run, a broken timed
path comes out not correct; and the arithmetic of ``flops_glm4`` and the
scope groups of ``families/glm4_moe_lite``."""

import json
import os

import jax
import numpy as np
import pytest

from chipbench import families
from chipbench import run as R
from helpers import clear_trace_caches, fixture, readings_of, run

CELL = "glm-4.7-flash-serve.code-turns"


@pytest.fixture(scope="module")
def cfg():
    return fixture("tiny-glm4.json")


def test_seeded_weights_are_the_programs(cfg):
    from chipbench.reference import glm4_moe_lite as ref
    from chipbench.runners import serve_glm4
    from uccl_tpu.models import moe_inference

    seed = 2**31 + 9
    key = jax.random.PRNGKey(seed)
    rec = serve_glm4.sp.Recorder(annotate=False)
    _, backend, vocab = serve_glm4.build(cfg, seed, rec)
    assert vocab == 256
    mcfg = backend.server.cfg
    assert (mcfg.attn, mcfg.gate, mcfg.first_k_dense, mcfg.shared_ffn,
            mcfg.routed_scale, mcfg.param_dtype) == (
        "mla", "sigmoid_bias", 1, 24, 1.8, "bfloat16")
    mine = ref.init_weights(key, cfg)
    theirs = moe_inference.init_params(key, mcfg)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        # the same draws, stored alike: bfloat16 matrices, float32 vectors
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    assert theirs["blocks"]["we_gate"].dtype == np.dtype("bfloat16")
    assert theirs["blocks"]["router_bias"].dtype == np.float32
    assert float(np.std(np.asarray(theirs["blocks"]["router_bias"]))) \
        == pytest.approx(0.01, rel=0.5)


def test_served_tokens_are_the_references_best_and_bf16_is_not(cfg):
    lines = []
    out = run("tiny.chat", cfg, fixture("tiny-chat.json"),
              controls=("bf16",), lines=lines)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 9
    rec = json.loads([l for l in lines if '"control_numbers"' in l][0]
                     .split("chipbench: ", 1)[1])
    limits = cfg["correct"]["limits"]
    low = rec["control_numbers"]["bf16"]
    for r in ("published", "stated"):
        # absorbed attention and a sorted dispatch against the expanded,
        # expert-by-expert reference: summation order only
        assert rec["numbers"][r]["gap_max"] <= 1e-5
    assert low["published"]["gap_p99"] > 3 * limits["published_gap_p99"]
    assert low["stated"]["clear_miss_share"] \
        > 3 * limits["stated_clear_miss_share"]


def test_an_altered_token_is_not_correct(cfg, monkeypatch):
    from uccl_tpu.serving import MoEBackend

    real = MoEBackend.decode

    def broken(self, tokens, active, **kw):
        out = np.array(real(self, tokens, active, **kw))
        out[active] = (out[active] + 1) % 256  # altered where it is produced
        return out

    monkeypatch.setattr(MoEBackend, "decode", broken)
    out = run("tiny.chat", cfg, fixture("tiny-chat.json"))
    assert out["correct"] is False


def published():
    return R.load_json(os.path.join(
        R.HERE, "configs", "glm-4.7-flash-serve.json"))


def test_flops_glm4_counts_the_published_block():
    from chipbench import flops_glm4 as f

    c = published()
    assert f.latent_row(c) == 576
    assert f.attention_params(c) == 21_757_952  # ISSUE 26's 21.76 M less norms
    assert f.expert_params(c) == 3 * 2048 * 1536
    # 8 slots full to 8192 positions, 6 layers, 576 float32 numbers a row
    assert f.latent_cache_bytes(c, 8 * 8192) == 4 * 6 * 65536 * 576
    # every expert reached, no cache: the weights a step reads but the
    # embedding, 7.79 GB less the 0.63 GB embedding
    all_w = f.decode_step_bytes(c, 64, 0)
    assert 7.10e9 < all_w < 7.20e9
    one = f.decode_step_bytes(c, 1, 0)
    assert all_w - one == pytest.approx(2 * 5 * 63 * f.expert_params(c))
    # a [8, 128] prefill program routes 4,096 rows through 5 layers
    assert f.routed_expert_flops(c, 1024) == pytest.approx(
        5 * 1024 * 4 * 2 * 3 * 2048 * 1536)


def test_new_scopes_are_read_and_a_scopeless_program_reads_none():
    from chipbench import program_trace as pt
    from chipbench.families import glm4_moe_lite as fam

    path = "jit(uccl_moe_verify_slots)/attn.latent_kv/dot_general:"
    assert pt.scope_of(path) is None  # not among the first model's twelve
    assert pt.scope_of(path, fam.SCOPES) == "attn.latent_kv"
    assert pt.scope_of("jit(f)/moe.shared/mul:", fam.SCOPES) == "moe.shared"
    assert pt.scope_of("jit(f)/ffn.dense/dot:", fam.SCOPES) == "ffn.dense"
    assert pt.scope_of("jit(f)/attn.core/dot:", fam.SCOPES) == "attn.core"

    class View:  # a traced run of a program without spans: no trace read
        record = {"trace_path": None, "e2e": {}, "compiles_in_window": 0}
        window = None
        cfg = published()
        family = families.of(cfg)
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}

    mine = [m["name"] for m in readings_of(CELL)]
    assert {"decode_latent_attention_dev_ms", "prefill_expert_mxu_share",
            "decode_latent_attention_roofline_share"} <= set(mine)
    for name in mine:
        if name.split(".")[0] in ("decode_step_dev_ms", "prefill_step_dev_ms",
                                  "device_idle_share"):
            continue  # these read the benchmark's own spans (a full view)
        got = R.load_reader(name).read(View)
        assert got is None or name.startswith("compiles_in_window")


def test_scope_rows_and_the_roofline_readers_on_hand_made_events(monkeypatch):
    from chipbench import program_trace as pt
    from chipbench.families import glm4_moe_lite as fam

    ms = 1e6
    spans = [(pt.DECODE, 0.0, 10 * ms, {"n": 2, "kv_rows": 4096}),
             (pt.PREFILL, 20 * ms, 30 * ms, {"n": 1})]
    j = "jit(p)/"
    ops = [("a", 1 * ms, 2 * ms, j + "attn.core/dot_general:"),
           ("b", 3 * ms, 1 * ms, j + "attn.latent_q/dot_general:"),
           ("c", 4 * ms, 3 * ms, j + "moe.experts/dot_general:"),
           ("d", 7 * ms, 1 * ms, j + "moe.shared/dot_general:"),
           ("e", 8 * ms, 1 * ms, ""),
           ("f", 21 * ms, 20 * ms, j + "moe.experts/dot_general:"),
           ("g", 41 * ms, 5 * ms, j + "ffn.dense/dot_general:")]
    trace = pt.ProgramTrace(spans, [ops])
    monkeypatch.setattr(pt, "load", lambda path: trace)
    clear_trace_caches()

    class View:
        record = {"trace_path": "hand-made"}
        window = (0.0, 60 * ms)
        cfg = published()
        family = families.of(cfg)
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}

    def read(name):
        return R.load_reader(name).read(View)

    assert read("decode_latent_attention_dev_ms") == 3.0
    assert read("decode_shared_dense_ffn_dev_ms") == 1.0
    assert read("prefill_moe_experts_dev_ms") == 20.0
    assert read("unscoped_dev_share") == pytest.approx(100 * 1 / 33)
    # a group the family has not: no reading, nothing raised
    assert read("decode_window_attention_dev_ms") is None
    assert read("decode_conv_roofline_share") is None
    share = read("decode_latent_attention_roofline_share")
    # 4096 rows x 576 x 4 B x 6 layers over 819 GB/s is 69 us of the 2 ms
    assert share == pytest.approx(100 * (4096 * 576 * 4 * 6 / 819e9) / 2e-3)
    # a prefill span without ``rows`` (a program before PR 28) is the pool's
    mxu = R.load_reader("prefill_expert_mxu_share").read(View)
    assert mxu == pytest.approx(
        100 * (5 * 1024 * 4 * 6 * 2048 * 1536 / 197e12) / 20e-3)
    clear_trace_caches()


def test_expert_mxu_share_takes_each_programs_rows_from_its_span(monkeypatch):
    """One-row, two-row and pool programs in one window: each quotient over
    the rows its own span names, the median of the quotients (not the
    median time over the pool's rows, which read 24 % where 3 % was true)."""
    from chipbench import program_trace as pt
    from chipbench.families import glm4_moe_lite as fam

    ms = 1e6
    j = "jit(uccl_moe_prefill_slots)/"
    programs = [(1, 8.0), (1, 8.2), (2, 8.5), (8, 41.6), (1, 8.1)]
    spans, ops = [], []
    for k, (rows, experts_ms) in enumerate(programs):
        t = k * 100 * ms
        spans.append((pt.PREFILL, t, 60 * ms,
                      {"n": min(rows, 3), "chunk": "128", "rows": rows}))
        ops += [("attn", t + 1 * ms, 4 * ms, j + "attn.core/dot_general:"),
                ("experts", t + 5 * ms, experts_ms * ms,
                 j + "moe.experts/ebf,efh->ebh/dot_general:")]
    spans.append((pt.PREFILL, 500 * ms, 10 * ms, {"n": 1, "rows": 1}))  # empty
    trace = pt.ProgramTrace(spans, [ops])
    monkeypatch.setattr(pt, "load", lambda path: trace)
    clear_trace_caches()

    class View:
        record = {"trace_path": "hand-made"}
        window = (0.0, 600 * ms)
        cfg = published()
        family = families.of(cfg)
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}

    def share(rows, experts_ms):
        flops = 5 * rows * 128 * 4 * 6 * 2048 * 1536
        return 100 * flops / 197e12 / (experts_ms / 1e3)

    got = R.load_reader("prefill_expert_mxu_share").read(View)
    quotients = sorted(share(r, t) for r, t in programs)
    assert got == pytest.approx(quotients[2])
    assert got == pytest.approx(share(1, 8.0))  # 3.07 %: a one-row program
    assert 2.9 < got < 3.2
    assert share(8, 41.6) == pytest.approx(4.72, abs=0.01)  # PR 26's reading
    clear_trace_caches()
