"""The ``lfm2_moe`` configuration's pieces (LFM2-24B-A2B) at a tiny size on
the CPU, where program and reference both compute true float32: the seeded
weights are the same numbers, the served tokens are the reference's own best
(through conv rings that wrap, over slots re-admitted), the
bfloat16-activation control reads far above the sound run, a broken timed
path comes out not correct; and the arithmetic of ``flops_lfm2``, the scope
groups of ``families/lfm2_moe`` and the readers on hand-made events."""

import json
import os

import jax
import numpy as np
import pytest

from chipbench import families
from chipbench import run as R
from helpers import clear_trace_caches, fixture, readings_of, run

CELL = "lfm2-24b-a2b-serve.long-answers"


@pytest.fixture(scope="module")
def cfg():
    return fixture("tiny-lfm2.json")


def test_seeded_weights_are_the_programs(cfg):
    from chipbench.reference import lfm2_moe as ref
    from chipbench.runners import serve_lfm2
    from uccl_tpu import obs
    from uccl_tpu.models import moe_inference

    seed = 2**31 + 9
    key = jax.random.PRNGKey(seed)
    rec = serve_lfm2.sp.Recorder(annotate=False)
    _, backend, vocab = serve_lfm2.build(cfg, seed, rec)
    assert vocab == 256
    mcfg = backend.server.cfg
    assert mcfg.layer_kinds == ("conv", "conv", "full", "conv", "conv",
                                "conv", "full", "conv", "conv")
    assert (mcfg.moe_experts, mcfg.experts_held, mcfg.capacity_factor,
            mcfg.first_k_dense, mcfg.conv_taps, mcfg.ring_rows("conv"),
            mcfg.shared_ffn, mcfg.qk_norm, mcfg.attn_gate, mcfg.post_norms,
            mcfg.tie_head, mcfg.routed_scale, mcfg.rope_theta,
            mcfg.param_dtype) == (
        8, 0, 4.0, 1, 3, 10, 0, True, False, False, True, 1.0, 1e6,
        "bfloat16")
    pool = obs.gauge("serving_kv_pool_bytes")
    assert (pool.get(group="full"), pool.get(group="conv")) == (
        2 * 4 * 128 * 2 * 16 * 4, 7 * 4 * 10 * 32 * 4)
    assert obs.gauge("serving_kv_ring_rows").get(group="conv") == 10
    mine = ref.init_weights(key, cfg)
    theirs = moe_inference.init_params(key, mcfg)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        # the same draws, stored alike: bfloat16 matrices, float32 vectors
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    assert "head" not in theirs  # tied: the embedding is the head
    assert theirs["conv_blocks"]["w_in"].shape == (6, 32, 96)
    assert theirs["conv_blocks"]["w_conv"].shape == (6, 32, 3)
    assert theirs["blocks"]["q_norm"].dtype == np.float32
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
        # a filter over a ring and sorted queues against loops over taps,
        # heads and experts: summation order only
        assert rec["numbers"][r]["gap_max"] <= 1e-5
    assert low["published"]["gap_p99"] > 3 * limits["published_gap_p99"]
    assert low["stated"]["clear_miss_share"] \
        > 3 * limits["stated_clear_miss_share"]
    # the conv ring of 10 rows wrapped under most of the sample, and nine
    # requests went through four slots: slots were re-admitted
    sample = json.loads([l for l in lines if '"sample_past_ring"' in l][0]
                        .split("chipbench: ", 1)[1])
    assert sample["conv_ring"] == 10 and sample["sample_past_ring"] >= 3


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
        R.HERE, "configs", "lfm2-24b-a2b-serve.json"))


def test_the_configuration_is_the_catalog_row_less_its_cuts():
    c = published()
    assert set(c["reduced"]) == {"num_hidden_layers", "num_dense_layers"}
    assert c["published"] == {"num_hidden_layers": 40, "num_dense_layers": 2}
    assert (c["num_hidden_layers"], c["num_dense_layers"]) == (9, 1)
    # no width is cut; every expert and the whole vocabulary are held
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], c["conv_L_cache"], c["vocab_size"],
            c["norm_eps"], c["routed_scaling_factor"],
            c["rope_parameters"]) == (
        2048, 32, 8, 11776, 1536, 64, 4, 3, 65536, 1e-5, 1,
        {"rope_theta": 1000000, "rope_type": "default"})
    assert "router_experts" not in c and "first_expert" not in c
    # layer_types stands whole; the run reads its first nine: layer 0 and
    # two whole periods of (conv, full, conv, conv)
    assert len(c["layer_types"]) == 40
    assert c["layer_types"].count("full_attention") == 10
    assert c["layer_types"][:9] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv"]
    s = c["serving"]
    # the ring: taps - 1 + a prefill chunk, in whole 8s
    assert s["conv_ring"] == 72 and s["conv_ring"] % 8 == 0
    assert 0 <= s["conv_ring"] - (c["conv_L_cache"] - 1
                                  + s["prefill_chunk"]) < 8
    mix = R.load_json(os.path.join(R.HERE, "traffic", "long-answers.json"))
    longest = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    assert longest <= s["max_seq"]
    assert mix["rate_rps"] == pytest.approx(0.8 * mix["knee_rps"])
    # the longest answer fits behind the window's last arrival
    assert mix["output_len"]["max"] == 768 and mix["drain_s"] == 25
    # every padded length of the reference is whole query blocks
    from chipbench.reference import lfm2_moe as ref
    from chipbench.runners import serve_lfm2

    for n in serve_lfm2.pad_lengths(longest):
        assert n % ref.QUERY_BLOCK == 0
    # the description the program reads from the file
    from uccl_tpu.models.moe_inference import MoEServeConfig

    m = MoEServeConfig.from_hf(c, conv_ring=s["conv_ring"])
    assert (m.n_layers, m.first_k_dense, m.moe_experts, m.n_held, m.vocab,
            m.ring_rows("conv"), m.conv_taps, m.head_dim, m.tie_head) == (
        9, 1, 64, 64, 65536, 72, 3, 64, True)
    assert (m.layer_kinds.count("conv"), m.layer_kinds.count("full")) \
        == (7, 2)


def test_flops_lfm2_counts_the_published_block():
    from chipbench import flops_lfm2 as f

    c = published()
    assert f.layer_counts(c) == {"conv": 7, "full": 2, "dense": 1, "moe": 8}
    assert f.kv_row(c) == 1024
    # ISSUE 42's arithmetic: a conv operator 16.8 M, an attention 10.5 M,
    # an expert 9.44 M
    assert f.conv_params(c) == 2048 * 6144 + 2048 * 2048 + 2048 * 3 \
        == 16_783_360
    assert f.attention_params(c) == 2 * 2048 * 2048 + 2 * 2048 * 512 \
        == 10_485_760
    assert f.expert_params(c) == 3 * 2048 * 1536 == 9_437_184
    # 16 slots full to 8,192 positions: ISSUE 42's 1.07 GB
    assert f.full_cache_bytes(c, 16 * 8192) == 4 * 2 * 131072 * 1024 \
        == 1_073_741_824
    # three ring rows of 2,048 float32 numbers a decoding row and conv layer
    assert f.conv_state_bytes(c, 12) == 4 * 7 * 12 * 3 * 2048
    assert f.conv_decode_bytes(c, 12) == 2 * 7 * 16_783_360 \
        + f.conv_state_bytes(c, 12)
    # nothing reached, no cache: conv and attention layers, the dense FFN,
    # eight routers and the embedding as the head
    bare = f.decode_step_bytes(c, 1, 0, 0) - f.conv_state_bytes(c, 1)
    assert bare == 2 * (7 * 16_783_360 + 2 * 10_485_760
                        + 3 * 2048 * 11776 + 8 * 2048 * 64 + 2048 * 65536)
    # every expert of every layer reached: the whole 10.36 GB but the
    # three ring rows
    assert 10.35e9 < f.decode_step_bytes(c, 1, 0, 8 * 64) \
        - f.conv_state_bytes(c, 1) < 10.36e9
    assert f.decode_step_bytes(c, 12, 9000, 280) \
        - f.decode_step_bytes(c, 12, 0, 0) \
        == 2 * 280 * 9_437_184 + 4 * 2 * 9000 * 1024
    # a [1, 64] prefill program: 64 x 4 routed rows a layer
    assert f.routed_expert_flops(c, 64) == 8 * 256 * 2 * 9_437_184


def test_scopes_by_kind_are_read_and_a_scopeless_program_reads_none():
    from chipbench import program_trace as pt
    from chipbench.families import lfm2_moe as fam

    path = "jit(uccl_moe_verify_slots)/conv.state/scatter:"
    assert pt.scope_of(path) is None  # not among the first model's twelve
    assert pt.scope_of(path, fam.SCOPES) == "conv.state"
    assert pt.scope_of("jit(f)/conv.mix/mul:", fam.SCOPES) == "conv.mix"
    assert pt.scope_of("jit(f)/attn.qkv.full/mul:", fam.SCOPES) \
        == "attn.qkv.full"
    assert pt.scope_of("jit(f)/attn.core.window/dot:", fam.SCOPES) is None
    assert len(fam.SCOPES) == len(set(fam.SCOPES)) == 12 + 4 + 4 + 1

    class View:  # a traced run of a program without spans: no trace read
        record = {"trace_path": None, "e2e": {}, "compiles_in_window": 0}
        window = None
        cfg = published()
        family = families.of(cfg)
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}

    b = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    mine = readings_of(CELL)
    assert len(b["per_layer"]) <= 128  # the benchmark's cap
    # the nine of PR 42 under their readings' names, and the conv layers'
    # two times by program that had no room then
    assert {"decode_step_dev_ms", "prefill_step_dev_ms",
            "decode_experts_read_share", "decode_hbm_roofline_share",
            "decode_conv_roofline_share",
            "decode_full_attention_roofline_share",
            "prefill_expert_mxu_share", "device_idle_share",
            "unscoped_dev_share", "decode_conv_dev_ms",
            "prefill_conv_dev_ms"} <= {m["name"] for m in mine}
    for m in mine:
        if m["name"].split(".")[0] in ("decode_step_dev_ms",
                                       "prefill_step_dev_ms"):
            continue  # these read the benchmark's own spans (a full view)
        got = R.load_reader(m["name"]).read(View)
        assert got is None or m["name"].startswith("compiles_in_window")
    for e in b["end_to_end"]:
        assert "workloads" not in e or CELL in e["workloads"]
    cell = [w for w in b["workloads"] if w["name"] == CELL][0]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


def test_readers_on_hand_made_events(monkeypatch):
    from chipbench import flops_lfm2 as f
    from chipbench import program_trace as pt
    from chipbench.experts_read import EXPERTS

    ms = 1e6
    spans = [(pt.DECODE, 0.0, 12 * ms, {"n": 12, "kv_rows": 9000}),
             (EXPERTS, 11 * ms, 0.0,
              {"experts_read": 280, "experts_held": 512}),
             (pt.PREFILL, 20 * ms, 30 * ms, {"n": 1, "rows": 1,
                                             "chunk": 64})]
    j = "jit(p)/"
    ops = [("a", 1 * ms, 2 * ms, j + "attn.core.full/dot_general:"),
           ("b", 3 * ms, 1 * ms, j + "attn.qkv.full/dot_general:"),
           ("c", 4 * ms, 0.5 * ms, j + "conv.in_proj/dot_general:"),
           ("c2", 4.5 * ms, 0.25 * ms, j + "conv.state/scatter:"),
           ("c3", 4.75 * ms, 0.125 * ms, j + "conv.mix/mul:"),
           ("c4", 4.875 * ms, 0.125 * ms, j + "conv.out_proj/dot_general:"),
           # the expert loop: a ``while`` the trace shows under no scope,
           # around its body's operation
           ("w", 5 * ms, 3 * ms, ""),
           ("d", 5 * ms, 3 * ms, j + "moe.experts/dot_general:"),
           ("e", 8 * ms, 1 * ms, j + "moe.route/sort:"),
           ("s", 9 * ms, 0.5 * ms, j + "ffn.dense/dot_general:"),
           ("f", 10 * ms, 1 * ms, ""),
           ("g", 21 * ms, 20 * ms, j + "attn.core.full/dot_general:"),
           ("g2", 41 * ms, 2 * ms, j + "conv.in_proj/dot_general:"),
           ("h", 43 * ms, 5 * ms, j + "moe.experts/dot_general:")]
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

    # device ms by scope group
    for name, want in (("decode_full_attention_dev_ms", 3.0),
                       ("decode_conv_dev_ms", 1.0),
                       ("prefill_conv_dev_ms", 2.0),
                       ("prefill_full_attention_dev_ms", 20.0),
                       ("decode_moe_experts_dev_ms", 3.0),
                       ("decode_moe_exchange_dev_ms", 1.0),
                       ("prefill_moe_experts_dev_ms", 5.0)):
        assert read(name) == want
    assert read("decode_window_attention_dev_ms") is None  # no such group
    assert read("unscoped_dev_share") == pytest.approx(100 * 1 / 36.5)
    assert read("decode_experts_read_share") == pytest.approx(
        100 * 280 / 512)
    c = View.cfg
    assert read("decode_full_attention_roofline_share") == pytest.approx(
        100 * (4 * 2 * 9000 * 1024 / 819e9) / 2e-3)
    assert read("decode_conv_roofline_share") == pytest.approx(
        100 * f.conv_decode_bytes(c, 12) / 819e9 / 1e-3)
    # the whole program's 9.5 ms of operations (the loop and its body
    # counted once) against every byte it must read, with the experts the
    # program itself counted
    assert read("decode_hbm_roofline_share") == pytest.approx(
        100 * f.decode_step_bytes(c, 12, 9000, 280) / 819e9 / 9.5e-3)
    assert read("prefill_expert_mxu_share") == pytest.approx(
        100 * f.routed_expert_flops(c, 64) / 197e12 / 5e-3)
    # a program that reports no count: the whole step's share is not read,
    # the others are
    trace.spans[:] = [sp for sp in spans if sp[0] != EXPERTS]
    clear_trace_caches()
    assert read("decode_hbm_roofline_share") is None
    assert read("decode_conv_roofline_share") is not None
    clear_trace_caches()
