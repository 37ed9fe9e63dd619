"""The ``mimo_v2_flash`` configuration's pieces at a tiny size on the CPU,
where program and reference both compute true float32: the seeded weights
are the same numbers, the served tokens are the reference's own best
(through rings that wrap), the bfloat16-activation control reads far above
the sound run, a broken timed path comes out not correct; and the
arithmetic of ``flops_mimo``, the scope groups of ``families/mimo_v2_flash`` and the
readers on hand-made events."""

import json
import os

import jax
import numpy as np
import pytest

from chipbench import families
from chipbench import run as R
from helpers import clear_trace_caches, fixture, readings_of, run

CELL = "mimo-v2-flash-serve.long-short"


@pytest.fixture(scope="module")
def cfg():
    return fixture("tiny-mimo.json")


def test_seeded_weights_are_the_programs(cfg):
    from chipbench.reference import mimo_v2_flash as ref
    from chipbench.runners import serve_mimo
    from uccl_tpu.models import moe_inference

    seed = 2**31 + 9
    key = jax.random.PRNGKey(seed)
    rec = serve_mimo.sp.Recorder(annotate=False)
    _, backend, vocab = serve_mimo.build(cfg, seed, rec)
    assert vocab == 256
    mcfg = backend.server.cfg
    assert mcfg.layer_kinds == ("full", "window", "window", "window",
                                "window", "full", "window")
    assert (mcfg.moe_experts, mcfg.experts_held, mcfg.first_expert,
            mcfg.capacity_factor, mcfg.first_k_dense, mcfg.window, mcfg.ring,
            mcfg.rotary_dim, mcfg.sink, mcfg.param_dtype) == (
        16, 4, 4, 4.0, 1, 8, 16, 4, ("window",), "bfloat16")
    assert serve_mimo.kv_pool_bytes() == {
        "full": 2 * 4 * 128 * 2 * 20 * 4, "window": 5 * 4 * 16 * 4 * 20 * 4}
    mine = ref.init_weights(key, cfg)
    theirs = moe_inference.init_params(key, mcfg)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        # the same draws, stored alike: bfloat16 matrices, float32 vectors
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    assert theirs["window_blocks"]["we_gate"].shape == (5, 4, 48, 24)
    assert theirs["window_blocks"]["sink"].dtype == np.float32
    assert [l[:3] for l in ref.layers(cfg)] == [
        (g, i, k) for (g, i), k in zip(mcfg.param_groups(), mcfg.layer_kinds)]


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
        # grouped heads over a ring and sorted held queues against a loop
        # over heads and experts: summation order only
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
        R.HERE, "configs", "mimo-v2-flash-serve.json"))


def test_the_configuration_is_the_catalog_row_less_its_cuts():
    c = published()
    assert set(c["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                 "vocab_size"}
    assert c["published"] == {"num_hidden_layers": 48,
                              "n_routed_experts": 256, "vocab_size": 152576}
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["router_experts"], c["vocab_size"]) == (7, 16, 256, 19072)
    # the two per-layer lists stand whole; the run reads their first seven
    assert len(c["hybrid_layer_pattern"]) == len(c["moe_layer_freq"]) == 48
    assert c["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert c["moe_layer_freq"][:7] == [0, 1, 1, 1, 1, 1, 1]
    mix = R.load_json(os.path.join(R.HERE, "traffic", "long-short.json"))
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= c["serving"]["max_seq"]
    # every padded length of the reference is whole query blocks
    from chipbench.reference import mimo_v2_flash as ref
    from chipbench.runners import serve_mimo

    for n in serve_mimo.pad_lengths(mix["prompt_len"]["max"]
                                    + mix["output_len"]["max"]):
        assert n % ref.QUERY_BLOCK == 0


def test_flops_mimo_counts_the_published_block():
    from chipbench import flops_mimo as f

    c = published()
    assert f.layer_counts(c) == {"full": 2, "window": 5, "dense": 1, "moe": 6}
    assert f.kv_row(c, "full") == 1280 and f.kv_row(c, "window") == 2560
    # ISSUE 36's 89.1 M and 94.4 M
    assert f.attention_params(c, "full") == 4096 * (12288 + 768 + 512) \
        + 8192 * 4096 == 89_128_960
    assert f.attention_params(c, "window") == 94_371_840
    assert f.expert_params(c) == 3 * 4096 * 2048
    # 8 slots full to 16,384 positions: ISSUE 36's 1.34 GB and 0.10 GB
    assert f.full_cache_bytes(c, 8 * 16384) == 4 * 2 * 131072 * 1280
    assert f.window_cache_bytes(c, 8, 8 * 16384) == 4 * 5 * 8 * 128 * 2560
    assert f.window_cache_bytes(c, 8, 100) == 4 * 5 * 100 * 2560
    assert f.held_experts_reached(c, 1) == pytest.approx(16 * 8 / 256)
    assert 3.4 < f.held_experts_reached(c, 8) < 3.6
    # every held expert reached, no cache: the weights a step reads but the
    # embedding, 6.86 GB less the 0.156 GB embedding slice
    far = f.decode_step_bytes(c, 10**6, 0)
    assert 6.69e9 < far < 6.72e9
    one = f.decode_step_bytes(c, 1, 0)
    assert far - one == pytest.approx(2 * 6 * 15.5 * f.expert_params(c))


def test_scopes_by_kind_are_read_and_a_scopeless_program_reads_none():
    from chipbench import program_trace as pt
    from chipbench.families import mimo_v2_flash as fam

    path = "jit(uccl_moe_verify_slots)/attn.core.window/dot_general:"
    assert pt.scope_of(path) is None  # not among the first model's twelve
    assert pt.scope_of(path, fam.SCOPES) == "attn.core.window"
    assert pt.scope_of("jit(f)/attn.qkv.full/mul:", fam.SCOPES) \
        == "attn.qkv.full"
    assert pt.scope_of("jit(f)/ffn.dense/dot:", fam.SCOPES) == "ffn.dense"
    assert pt.scope_of("jit(f)/moe.experts/dot:", fam.SCOPES) == "moe.experts"
    assert len(fam.SCOPES) == 12 + 8 + 1

    class View:  # a traced run of a program without spans: no trace read
        record = {"trace_path": None, "e2e": {}, "compiles_in_window": 0}
        window = None
        cfg = published()
        family = families.of(cfg)
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}

    mine = readings_of(CELL)
    assert {"decode_window_attention_roofline_share", "kv_pool_ring_share",
            "prefill_full_attention_dev_ms"} <= {m["name"] for m in mine}
    for m in mine:
        if m["name"].split(".")[0] in ("decode_step_dev_ms",
                                       "prefill_step_dev_ms"):
            continue  # these read the benchmark's own spans (a full view)
        got = R.load_reader(m["name"]).read(View)
        assert got is None or m["name"].startswith("compiles_in_window")


def test_readers_on_hand_made_events(monkeypatch):
    from chipbench import flops_mimo as f
    from chipbench import program_trace as pt

    ms = 1e6
    spans = [(pt.DECODE, 0.0, 12 * ms, {"n": 8, "kv_rows": 24000}),
             (pt.PREFILL, 20 * ms, 30 * ms, {"n": 1, "rows": 1})]
    j = "jit(p)/"
    ops = [("a", 1 * ms, 2 * ms, j + "attn.core.full/dot_general:"),
           ("b", 3 * ms, 1 * ms, j + "attn.qkv.full/dot_general:"),
           ("c", 4 * ms, 0.5 * ms, j + "attn.core.window/dot_general:"),
           ("c2", 4.5 * ms, 0.25 * ms, j + "attn.kv_write.window/copy:"),
           ("d", 5 * ms, 3 * ms, j + "moe.experts/dot_general:"),
           ("e", 8 * ms, 1 * ms, j + "moe.route/sort:"),
           ("f", 9 * ms, 1 * ms, ""),
           ("g", 21 * ms, 20 * ms, j + "attn.core.full/dot_general:"),
           ("h", 41 * ms, 5 * ms, j + "ffn.dense/dot_general:")]
    trace = pt.ProgramTrace(spans, [ops])
    monkeypatch.setattr(pt, "load", lambda path: trace)
    clear_trace_caches()

    class View:
        record = {"trace_path": "hand-made",
                  "kv_pool_bytes": {"full": 1342177280.0,
                                    "window": 104857600.0}}
        window = (0.0, 60 * ms)
        cfg = published()
        family = families.of(cfg)
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}

    def read(name):
        return R.load_reader(name).read(View)

    assert read("decode_full_attention_dev_ms") == 3.0
    assert read("decode_window_attention_dev_ms") == 0.75
    assert read("prefill_full_attention_dev_ms") == 20.0
    assert read("decode_moe_experts_dev_ms") == 3.0
    assert read("decode_moe_exchange_dev_ms") == 1.0
    assert read("unscoped_dev_share") == pytest.approx(100 * 1 / 33.75)
    c = View.cfg
    assert read("decode_full_attention_roofline_share") == pytest.approx(
        100 * (4 * 2 * 24000 * 1280 / 819e9) / 2e-3)
    assert read("decode_window_attention_roofline_share") == pytest.approx(
        100 * (4 * 5 * 8 * 128 * 2560 / 819e9) / 0.75e-3)
    # the whole program's 8.75 ms of operations against every byte it must read
    assert read("decode_conv_dev_ms") is None  # no such group in this family
    assert read("decode_hbm_roofline_share") == pytest.approx(
        100 * f.decode_step_bytes(c, 8, 24000) / 819e9 / 8.75e-3)
    assert read("kv_pool_ring_share") == pytest.approx(
        100 * 104857600 / (1342177280 + 104857600))
    clear_trace_caches()
