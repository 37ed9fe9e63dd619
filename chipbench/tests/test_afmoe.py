"""The ``afmoe`` configuration's pieces (Trinity) at a tiny size on the CPU,
where program and reference both compute true float32: the seeded weights
are the same numbers, the served tokens are the reference's own best
(through rings that wrap), the bfloat16-activation control reads far above
the sound run, a broken timed path comes out not correct; and the
arithmetic of ``flops_afmoe``, the scope groups of ``families/afmoe`` and the
readers on hand-made events."""

import json
import os

import jax
import numpy as np
import pytest

from chipbench import families
from chipbench import run as R
from helpers import clear_trace_caches, fixture, readings_of, run

CELL = "trinity-large-preview-serve.doc-turns"


@pytest.fixture(scope="module")
def cfg():
    return fixture("tiny-afmoe.json")


def test_seeded_weights_are_the_programs(cfg):
    from chipbench.reference import afmoe as ref
    from chipbench.runners import serve_afmoe
    from uccl_tpu import obs
    from uccl_tpu.models import moe_inference

    seed = 2**31 + 9
    key = jax.random.PRNGKey(seed)
    rec = serve_afmoe.sp.Recorder(annotate=False)
    _, backend, vocab = serve_afmoe.build(cfg, seed, rec)
    assert vocab == 256
    mcfg = backend.server.cfg
    assert mcfg.layer_kinds == ("window", "window", "window", "full",
                                "window")
    assert (mcfg.moe_experts, mcfg.experts_held, mcfg.first_expert,
            mcfg.capacity_factor, mcfg.first_k_dense, mcfg.window, mcfg.ring,
            mcfg.shared_ffn, mcfg.unrotated, mcfg.qk_norm, mcfg.attn_gate,
            mcfg.post_norms, mcfg.routed_scale, mcfg.param_dtype) == (
        16, 4, 4, 4.0, 1, 8, 15, 24, ("full",), True, True, True, 2.448,
        "bfloat16")
    assert mcfg.embed_scale == pytest.approx(48 ** 0.5)
    assert serve_afmoe.kv_pool_bytes() == {
        "full": 1 * 4 * 128 * 2 * 16 * 4, "window": 4 * 4 * 15 * 2 * 16 * 4}
    assert obs.gauge("serving_kv_ring_rows").get() == 15
    mine = ref.init_weights(key, cfg)
    theirs = moe_inference.init_params(key, mcfg)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        # the same draws, stored alike: bfloat16 matrices, float32 vectors
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    assert theirs["window_blocks"]["we_gate"].shape == (3, 4, 48, 24)
    assert theirs["window_blocks"]["wg"].shape == (3, 48, 48)
    assert theirs["window_blocks"]["q_norm"].dtype == np.float32
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
    # the ring of 15 rows wrapped under most of the sample
    sample = json.loads([l for l in lines if '"sample_past_ring"' in l][0]
                        .split("chipbench: ", 1)[1])
    assert sample["window_ring"] == 15 and sample["sample_past_ring"] >= 3


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
        R.HERE, "configs", "trinity-large-preview-serve.json"))


def test_the_configuration_is_the_catalog_row_less_its_cuts():
    c = published()
    assert set(c["reduced"]) == {"num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"}
    assert c["published"] == {"num_hidden_layers": 60, "num_dense_layers": 6,
                              "num_experts": 256, "vocab_size": 200192}
    assert (c["num_hidden_layers"], c["num_dense_layers"], c["num_experts"],
            c["router_experts"], c["first_expert"], c["vocab_size"]) == (
        5, 1, 32, 256, 0, 25024)
    # no width is cut
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["num_shared_experts"], c["sliding_window"], c["route_scale"],
            c["rope_theta"], c["mup_enabled"]) == (
        3072, 48, 8, 128, 12288, 3072, 4, 1, 4096, 2.448, 10000, True)
    # layer_types stands whole; the run reads its first five
    assert len(c["layer_types"]) == 60
    assert c["layer_types"][:5] == ["sliding_attention"] * 3 \
        + ["full_attention", "sliding_attention"]
    s = c["serving"]
    # the ring: window - 1 + a prefill chunk, in whole 128s
    assert s["window_ring"] == 4224 and s["window_ring"] % 128 == 0
    assert 0 <= s["window_ring"] - (c["sliding_window"] - 1
                                    + s["prefill_chunk"]) < 128
    mix = R.load_json(os.path.join(R.HERE, "traffic", "doc-turns.json"))
    longest = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    assert longest <= s["max_seq"]
    assert mix["rate_rps"] == pytest.approx(0.8 * mix["knee_rps"])
    # every padded length of the reference is whole query blocks
    from chipbench.reference import afmoe as ref
    from chipbench.runners import serve_afmoe

    for n in serve_afmoe.pad_lengths(longest):
        assert n % ref.QUERY_BLOCK == 0
    # the description the program reads from the file
    from uccl_tpu.models.moe_inference import MoEServeConfig

    m = MoEServeConfig.from_hf(c, window_ring=s["window_ring"])
    assert (m.n_layers, m.first_k_dense, m.moe_experts, m.n_held, m.vocab,
            m.ring, m.window) == (5, 1, 256, 32, 25024, 4224, 4096)
    assert m.layer_kinds.count("window") == 4


def test_flops_afmoe_counts_the_published_block():
    from chipbench import flops_afmoe as f

    c = published()
    assert f.layer_counts(c) == {"full": 1, "window": 4, "dense": 1, "moe": 4}
    assert f.kv_row(c) == 2048
    # ISSUE 38's 62.9 M (q, gate, o 3072 x 6144 each; k, v 3072 x 1024 each)
    assert f.attention_params(c) == 3 * 3072 * 6144 + 2 * 3072 * 1024 \
        == 62_914_560
    assert f.expert_params(c) == 3 * 3072 * 3072 == 28_311_552
    # 8 slots full to 16,384 positions: ISSUE 38's 1.07 GB; the rings' rows
    # in use, 8 x 4,096 a layer
    assert f.full_cache_bytes(c, 8 * 16384) == 4 * 131072 * 2048
    assert f.window_cache_bytes(c, 8 * 4096) == 4 * 4 * 32768 * 2048
    assert f.held_experts_reached(c, 1) == pytest.approx(32 * 4 / 256)
    assert 3.7 < f.held_experts_reached(c, 8) < 3.8
    # every held expert reached, no cache: the weights a step reads but the
    # embedding's slice, ISSUE 38's 8.64 GB less 0.154 GB
    far = f.decode_step_bytes(c, 10**6, 0, 0)
    assert 8.47e9 < far < 8.50e9
    one = f.decode_step_bytes(c, 1, 0, 0)
    assert far - one == pytest.approx(2 * 4 * 31.5 * f.expert_params(c))
    assert f.decode_step_bytes(c, 1, 1000, 600) - one == \
        4 * 2048 * (1000 + 4 * 600)
    # a [1, 128] prefill program: 128 x 4 x 32 / 256 = 64 routed rows a layer
    assert f.routed_expert_flops(c, 128) == 4 * 64 * 2 * 28_311_552


def test_scopes_by_kind_are_read_and_a_scopeless_program_reads_none():
    from chipbench import program_trace as pt
    from chipbench.families import afmoe as fam

    path = "jit(uccl_moe_verify_slots)/attn.gate.window/dot_general:"
    assert pt.scope_of(path) is None  # not among the first model's twelve
    assert pt.scope_of(path, fam.SCOPES) == "attn.gate.window"
    assert pt.scope_of("jit(f)/attn.qkv.full/mul:", fam.SCOPES) \
        == "attn.qkv.full"
    assert pt.scope_of("jit(f)/ffn.post_norm/mul:", fam.SCOPES) \
        == "ffn.post_norm"
    assert pt.scope_of("jit(f)/moe.shared/dot:", fam.SCOPES) == "moe.shared"
    assert len(fam.SCOPES) == len(set(fam.SCOPES)) == 12 + 8 + 1 + 2 + 2

    class View:  # a traced run of a program without spans: no trace read
        record = {"trace_path": None, "e2e": {}, "compiles_in_window": 0}
        window = None
        cfg = published()
        family = families.of(cfg)
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}

    mine = readings_of(CELL)
    assert {"decode_moe_shared_dev_ms", "prefill_expert_mxu_share",
            "kv_pool_ring_share"} <= {m["name"] for m in mine}
    for m in mine:
        if m["name"].split(".")[0] in ("decode_step_dev_ms",
                                       "prefill_step_dev_ms"):
            continue  # these read the benchmark's own spans (a full view)
        got = R.load_reader(m["name"]).read(View)
        assert got is None or m["name"].startswith("compiles_in_window")
    b = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    for e in b["end_to_end"]:
        assert "workloads" not in e or CELL in e["workloads"]


def test_readers_on_hand_made_events(monkeypatch):
    from chipbench import flops_afmoe as f
    from chipbench import program_trace as pt

    ms = 1e6
    spans = [(pt.DECODE, 0.0, 12 * ms,
              {"n": 8, "kv_rows": 40000, "window_rows": 24000}),
             (pt.PREFILL, 20 * ms, 30 * ms, {"n": 1, "rows": 1,
                                             "chunk": 128})]
    j = "jit(p)/"
    ops = [("a", 1 * ms, 2 * ms, j + "attn.core.full/dot_general:"),
           ("b", 3 * ms, 1 * ms, j + "attn.qkv.full/dot_general:"),
           ("c", 4 * ms, 0.5 * ms, j + "attn.core.window/dot_general:"),
           ("c2", 4.5 * ms, 0.25 * ms, j + "attn.kv_write.window/copy:"),
           ("c3", 4.75 * ms, 0.25 * ms, j + "attn.gate.window/dot_general:"),
           ("d", 5 * ms, 3 * ms, j + "moe.experts/dot_general:"),
           ("e", 8 * ms, 1 * ms, j + "moe.route/sort:"),
           ("s", 9 * ms, 0.5 * ms, j + "moe.shared/dot_general:"),
           ("n", 9.5 * ms, 0.25 * ms, j + "ffn.post_norm/mul:"),
           ("f", 10 * ms, 1 * ms, ""),
           ("g", 21 * ms, 20 * ms, j + "attn.core.full/dot_general:"),
           ("h", 41 * ms, 5 * ms, j + "moe.experts/dot_general:")]
    trace = pt.ProgramTrace(spans, [ops])
    monkeypatch.setattr(pt, "load", lambda path: trace)
    clear_trace_caches()

    class View:
        record = {"trace_path": "hand-made",
                  "kv_pool_bytes": {"full": 1073741824.0,
                                    "window": 1107296256.0}}
        window = (0.0, 60 * ms)
        cfg = published()
        family = families.of(cfg)
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}

    def read(name):
        return R.load_reader(name).read(View)

    assert read("decode_full_attention_dev_ms") == 3.0
    assert read("decode_window_attention_dev_ms") == 1.0
    # the gate's scopes count with their kind's attention (the reading of
    # the gate alone is retired: the decode program's gate lies fused under
    # ``attn.out.<kind>`` since PR 41)
    assert read("prefill_full_attention_dev_ms") == 20.0
    assert read("decode_moe_experts_dev_ms") == 3.0
    assert read("decode_moe_exchange_dev_ms") == 1.0
    assert read("decode_moe_shared_dev_ms") == 0.5
    assert read("prefill_moe_experts_dev_ms") == 5.0
    assert read("unscoped_dev_share") == pytest.approx(100 * 1 / 34.75)
    c = View.cfg
    assert read("decode_full_attention_roofline_share") == pytest.approx(
        100 * (4 * 40000 * 2048 / 819e9) / 2e-3)
    assert read("decode_window_attention_roofline_share") == pytest.approx(
        100 * (4 * 4 * 24000 * 2048 / 819e9) / 1e-3)
    # the whole program's 9.75 ms of operations against every byte it must
    # read
    assert read("decode_hbm_roofline_share") == pytest.approx(
        100 * f.decode_step_bytes(c, 8, 40000, 24000) / 819e9 / 9.75e-3)
    assert read("prefill_expert_mxu_share") == pytest.approx(
        100 * f.routed_expert_flops(c, 128) / 197e12 / 5e-3)
    assert read("kv_pool_ring_share") == pytest.approx(
        100 * 1107296256 / (1073741824 + 1107296256))
    clear_trace_caches()
