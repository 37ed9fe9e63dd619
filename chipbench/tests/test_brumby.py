"""The ``brumby`` configuration's pieces (Brumby-14B-Base) at a tiny size on
the CPU, where program and reference both compute true float32: the seeded
weights are the same numbers, the benchmark's reference is the tier-1
reference, the served tokens are the reference's own best (through chunked
prefill with padded last chunks, over slots re-admitted), the
bfloat16-activation control reads far above the sound run, a broken timed
path comes out not correct; and the arithmetic of ``flops_brumby``, the
scope groups of ``families/brumby`` and the readers on hand-made events."""

import json
import os
import re

import jax
import numpy as np
import pytest

from chipbench import families
from chipbench import run as R
from helpers import clear_trace_caches, fixture, readings_of, run

CELL = "brumby-14b-base-serve.doc-answers"
NEW = ("decode_retention_dev_ms", "prefill_retention_dev_ms",
       "decode_retention_roofline_share", "prefill_retention_roofline_share",
       "prefill_step_mfu", "decode_active_rows_share", "state_pool_share")
JOINED = ("decode_step_dev_ms", "prefill_step_dev_ms",
          "decode_hbm_roofline_share", "decode_shared_dense_ffn_dev_ms",
          "device_idle_share", "unscoped_dev_share", "queue_wait_p90_ms",
          "itl_p95_ms", "itl_mean_ms", "ttft_p90_ms", "ttft_per_ktok_p50_ms",
          "serve_tok_s", "compiles_in_window")

# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = dict(
    attention_bias=False, head_dim=128, hidden_act="silu", hidden_size=5120,
    intermediate_size=17408, max_position_embeddings=32768,
    max_window_layers=40, model_type="brumby", num_attention_heads=40,
    num_hidden_layers=40, num_key_value_heads=8, rms_norm_eps=1e-06,
    rope_scaling=None, rope_theta=1000000, sliding_window=None,
    tie_word_embeddings=False, use_sliding_window=False, vocab_size=151936)


@pytest.fixture(scope="module")
def cfg():
    return fixture("tiny-brumby.json")


def published():
    return R.load_json(os.path.join(
        R.HERE, "configs", "brumby-14b-base-serve.json"))


def test_seeded_weights_are_the_programs(cfg):
    from chipbench.reference import brumby as ref
    from chipbench.runners import serve_brumby
    from uccl_tpu import obs
    from uccl_tpu.models import moe_inference

    seed = 2**31 + 9
    key = jax.random.PRNGKey(seed)
    rec = serve_brumby.sp.Recorder(annotate=False)
    _, backend, vocab = serve_brumby.build(cfg, seed, rec)
    assert vocab == 2048
    mcfg = backend.server.cfg
    assert mcfg.layer_kinds == ("retention",) * 3
    assert (mcfg.n_moe_layers, mcfg.first_k_dense, mcfg.dense_ffn,
            mcfg.qk_norm, mcfg.tie_head, mcfg.rope_theta, mcfg.norm_eps,
            mcfg.param_dtype) == (0, 3, 64, True, False, 1e6, 1e-6,
                                  "bfloat16")
    assert backend.experts_held == 0
    per_slot = 2 * 48 * 8 * 4 + 2 * 48 * 4
    assert obs.gauge("serving_kv_pool_bytes").get(group="retention") \
        == 3 * 4 * per_slot
    assert serve_brumby.kv_pool_bytes(("retention",)) == {
        "retention": 3 * 4 * per_slot}
    mine = ref.init_weights(key, cfg)
    theirs = moe_inference.init_params(key, mcfg)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        # the same draws, stored alike: bfloat16 matrices, float32 vectors
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    g = theirs[ref.GROUP]
    assert g["wg"].shape == (3, 32, 2) and g["bg"].dtype == np.float32
    assert theirs["head"].shape == (32, 2048)


def test_the_benchmarks_reference_is_the_tier_1_reference(cfg):
    from chipbench.reference import brumby as ref
    from uccl_tpu.models import reference_hybrid_moe as plain
    from uccl_tpu.models.moe_inference import MoEServeConfig, init_params

    key = jax.random.PRNGKey(5)
    mcfg = MoEServeConfig.from_hf(cfg, param_dtype="bfloat16")
    toks = np.random.default_rng(3).integers(0, 96, 37).astype(np.int32)
    want = np.asarray(plain.forward_logits(init_params(key, mcfg), toks,
                                           mcfg))
    got = np.asarray(ref.forward_logits(ref.init_weights(key, cfg), toks,
                                        cfg))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=2e-5)
    rows = np.array([0, 9, 36])
    np.testing.assert_allclose(
        np.asarray(ref.forward_logits(ref.init_weights(key, cfg), toks, cfg,
                                      rows=rows)), want[rows], atol=2e-5)


def test_served_tokens_are_the_references_best_and_bf16_is_not(cfg):
    lines = []
    out = run("tiny.chat", cfg, fixture("tiny-chat.json"),
              controls=("bf16",), lines=lines)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 9
    rec = json.loads([l for l in lines if '"control_numbers"' in l][0]
                     .split("chipbench: ", 1)[1])
    limits = cfg["correct"]["limits"]
    low = rec["control_numbers"]["bf16"]["stated"]
    # a state carried from chunk to chunk against one [T, T] matrix a head:
    # summation order only, so every served token is the reference's best
    # or loses a tie by less than the clear gap
    assert rec["numbers"]["stated"]["gap_max"] <= 1e-4
    assert rec["numbers"]["stated"]["clear_miss_share"] == 0.0
    assert low["clear_miss_share"] > 3 * limits["stated_clear_miss_share"]
    assert low["off_best_share"] > limits["stated_off_best_share"]
    pool = json.loads([l for l in lines if '"kv_pool_bytes"' in l][0]
                      .split("chipbench: ", 1)[1])["kv_pool_bytes"]
    assert set(pool) == {"retention"}


def test_an_altered_token_is_not_correct(cfg, monkeypatch):
    from uccl_tpu.serving import MoEBackend

    real = MoEBackend.decode

    def broken(self, tokens, active, **kw):
        out = np.array(real(self, tokens, active, **kw))
        out[active] = (out[active] + 1) % 2048  # altered where it is produced
        return out

    monkeypatch.setattr(MoEBackend, "decode", broken)
    out = run("tiny.chat", cfg, fixture("tiny-chat.json"))
    assert out["correct"] is False


def test_the_configuration_is_the_catalog_row_less_its_cut():
    c = published()
    differs = {k for k, v in CATALOG.items() if c.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} == set(c["reduced"])
    assert c["published"] == {"num_hidden_layers": 40}
    assert c["num_hidden_layers"] == 8
    assert set(c["precision"]) >= {"weights", "state", "activations",
                                   "matmul", "control"}
    assert (c["precision"]["weights"], c["precision"]["state"],
            c["precision"]["matmul"]) == ("bfloat16", "float32", "default")
    s = c["serving"]
    assert (s["world"], s["slots"], s["max_seq"], s["prefill_chunk"]) == (
        1, 16, 32768, 128)
    mix = R.load_json(os.path.join(R.HERE, "traffic", "doc-answers.json"))
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1536,
                                 "sigma": 1.0, "min": 128, "max": 16384}
    assert mix["output_len"] == {"dist": "lognormal", "median": 320,
                                 "sigma": 0.6, "min": 64, "max": 640}
    assert mix["drain_s"] == 25 and "order_seed" in mix
    longest = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    assert longest <= s["max_seq"]
    assert mix["rate_rps"] == pytest.approx(0.8 * mix["knee_rps"])
    # every padded length of the reference is whole query blocks
    from chipbench.reference import brumby as ref
    from chipbench.runners import serve_brumby

    for n in serve_brumby.pad_lengths(longest):
        assert n % ref.QUERY_BLOCK == 0
    # the description the program reads from the file
    from uccl_tpu.models import inference
    from uccl_tpu.models.moe_inference import MoEServeConfig

    m = MoEServeConfig.from_hf(c, param_dtype=c["precision"]["weights"])
    assert (m.n_layers, m.first_k_dense, m.n_moe_layers, m.vocab, m.dim,
            m.n_heads, m.n_kv_heads, m.head_dim, m.dense_ffn, m.norm_eps,
            m.rope_theta, m.tie_head) == (
        8, 8, 0, 151936, 5120, 40, 8, 128, 17408, 1e-6, 1e6, False)
    assert m.layer_kinds == ("retention",) * 8
    # the state as held: between the model's 8,256 features and 9,216
    held = inference.kv_row_shapes(m, "retention")[0][1]
    assert 8256 <= held <= 9216


def test_flops_brumby_counts_the_published_block():
    from chipbench import flops_brumby as f

    c = published()
    assert f.state_features(c) == 128 * 129 // 2 == 8256
    # ISSUE 45's arithmetic: a layer 330.3 M parameters, of which the
    # retention's q, k, v, g, o are 62.95 M; embedding or head 0.778 B
    assert f.retention_params(c) == 2 * 5120 * 5120 + 2 * 5120 * 1024 \
        + 5120 * 8 == 62_955_520
    assert f.layer_params(c) == 62_955_520 + 3 * 5120 * 17408 == 330_342_400
    assert f.head_params(c) == 5120 * 151936 == 777_912_320
    # a slot's state 33.8 MB a layer (+ 0.26 MB of z)
    assert f.state_bytes_per_slot(c) == 4 * 8 * 8256 * 129 == 34_080_768
    assert 4 * 8 * 8256 * 128 == 33_816_576
    # a decode step: 5.29 GB of layers + 1.56 GB of head, and 0.545 GB of
    # state read and written a decoding row
    assert f.state_step_bytes(c, 1) == 2 * 8 * 34_080_768 == 545_292_288
    bare = f.decode_step_bytes(c, 0)
    assert bare == 2 * (8 * 330_342_400 + 777_912_320) == 6_841_303_040
    assert f.decode_step_bytes(c, 8) == bare + 8 * 545_292_288
    assert 11.1e9 < f.decode_step_bytes(c, 8) < 11.3e9
    assert 15.5e9 < f.decode_step_bytes(c, 16) < 15.7e9
    assert f.retention_decode_bytes(c, 8) == 2 * 8 * 62_955_520 \
        + 8 * 545_292_288
    # a prefill token at 8 layers: 5.29 GFLOP of matrices + 0.82 of the
    # retention's own terms + 0.02 of the chunk's scores = 6.1 GFLOP
    own = 8 * (2 * 40 * 8256 * 129 + 2 * 8 * 8256 * 129
               + 2 * 2 * 40 * 128 * 128)
    assert f.retention_token_flops(c, 128) == own
    assert 0.81e9 < own - 8 * 4 * 40 * 128 * 128 < 0.83e9
    per_token = 2 * 8 * 330_342_400 + own
    assert 6.09e9 < per_token < 6.14e9
    assert f.prefill_flops(c, 128, 1, 128) == 128 * per_token \
        + 2 * 777_912_320
    assert f.retention_prefill_flops(c, 100, 128) == 100 * (
        2 * 8 * 62_955_520 + own)
    assert f.retention_prefill_bytes(c, 2) == f.retention_decode_bytes(c, 2)
    # the tiny fixture: 4 / 2 heads of 8, 36 monomials a head, 3 layers
    t = fixture("tiny-brumby.json")
    assert f.state_features(t) == 36
    assert f.retention_params(t) == 2 * 32 * 32 + 2 * 32 * 16 + 32 * 2
    assert f.state_bytes_per_slot(t) == 4 * 2 * 36 * 9
    assert f.decode_step_bytes(t, 3) == 2 * (3 * (3136 + 3 * 32 * 64)
                                             + 32 * 2048) + 2 * 3 * 3 * 2592


def test_entries_are_within_the_contracts_form():
    b = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    conf = [c for c in b["configs"] if c["name"] == "brumby-14b-base-serve"]
    cell = [w for w in b["workloads"] if w["name"] == CELL]
    assert len(conf) == len(cell) == 1
    conf, cell = conf[0], cell[0]
    assert conf == b["configs"][-1] and cell == b["workloads"][-1]
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["source"] == published()["source"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "brumby-14b-base-serve", "doc-answers", 1)
    for text in (conf["why"], conf["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for n in (conf["name"], cell["name"], cell["traffic"], *conf["reduced"]):
        assert name.match(n)
    mine = {m["name"]: m for m in readings_of(CELL)}
    assert set(mine) == set(NEW) | set(JOINED)
    assert [m["name"] for m in b["per_layer"][-7:]] == list(NEW)
    for n in NEW:
        m = mine[n]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"] == [CELL] and name.match(n)
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["moves"] in ("ttft_mean_ms", "itl_p90_ms")
        assert os.path.exists(os.path.join(R.HERE, "layer_metrics",
                                           n + ".py"))
    for n in JOINED:
        assert mine[n]["workloads"][-1] == CELL
    for e in b["end_to_end"]:
        assert "workloads" not in e or e["workloads"][-1] == CELL
    assert len(b["per_layer"]) == 69 <= 128 and len(b["workloads"]) == 6
    assert os.path.getsize(os.path.join(R.ROOT, "BENCHMARK.json")) < 65536


def test_readers_on_hand_made_events_and_on_another_family(monkeypatch):
    from chipbench import flops_brumby as f
    from chipbench import program_trace as pt
    from chipbench.families import brumby as fam

    assert pt.scope_of("jit(f)/ret.state/dot_general:", fam.SCOPES) \
        == "ret.state"
    assert pt.scope_of("jit(f)/ret.state/dot_general:") is None
    assert pt.scope_of("jit(f)/attn.core/dot:", fam.SCOPES) is None
    assert len(fam.SCOPES) == len(set(fam.SCOPES)) == 8
    ms = 1e6
    spans = [(pt.DECODE, 0.0, 30 * ms, {"n": 8, "kv_rows": 20000}),
             (pt.PREFILL, 40 * ms, 30 * ms,
              {"n": 2, "rows": 2, "chunk": 128, "tokens": 200})]
    j = "jit(p)/"
    ops = [("a", 1 * ms, 2 * ms, j + "ret.qkv/dot_general:"),
           ("b", 3 * ms, 1 * ms, j + "ret.gate/dot_general:"),
           ("c", 4 * ms, 1 * ms, j + "ret.intra/dot_general:"),
           ("d", 5 * ms, 12 * ms, j + "ret.state/dot_general:"),
           ("e", 17 * ms, 2 * ms, j + "ret.out/dot_general:"),
           ("s", 19 * ms, 6 * ms, j + "ffn.dense/dot_general:"),
           ("h", 25 * ms, 2 * ms, j + "head/dot_general:"),
           ("u", 27 * ms, 1 * ms, ""),
           ("g", 41 * ms, 4 * ms, j + "ret.state/dot_general:"),
           ("g2", 45 * ms, 1 * ms, j + "ret.qkv/dot_general:"),
           ("g3", 46 * ms, 15 * ms, j + "ffn.dense/dot_general:")]
    trace = pt.ProgramTrace(spans, [ops])
    monkeypatch.setattr(pt, "load", lambda path: trace)
    clear_trace_caches()
    pool = 8 * 16 * 38_043_648

    class View:
        record = {"trace_path": "hand-made", "e2e": {},
                  "compiles_in_window": 0, "memory_peak_bytes": 15e9,
                  "kv_pool_bytes": {"retention": pool}}
        window = (0.0, 80 * ms)
        cfg = published()
        family = families.of(cfg)
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}

    def read(name, view=View):
        return R.load_reader(name).read(view)

    c = View.cfg
    assert read("decode_retention_dev_ms") == 18.0
    assert read("prefill_retention_dev_ms") == 5.0
    assert read("decode_shared_dense_ffn_dev_ms") == 6.0
    assert read("unscoped_dev_share") == pytest.approx(100 * 1 / 47)
    assert read("decode_retention_roofline_share") == pytest.approx(
        100 * f.retention_decode_bytes(c, 8) / 819e9 / 18e-3)
    assert read("decode_hbm_roofline_share") == pytest.approx(
        100 * f.decode_step_bytes(c, 8) / 819e9 / 27e-3)
    # two rows' state round trip is more time than 200 tokens' FLOPs
    least = max(f.retention_prefill_flops(c, 200, 128) / 197e12,
                f.retention_prefill_bytes(c, 2) / 819e9)
    assert least == f.retention_prefill_bytes(c, 2) / 819e9
    assert read("prefill_retention_roofline_share") == pytest.approx(
        100 * least / 5e-3)
    assert read("prefill_step_mfu") == pytest.approx(
        100 * f.prefill_flops(c, 200, 2, 128) / 197e12 / 20e-3)
    assert read("decode_active_rows_share") == 50.0
    assert read("state_pool_share") == pytest.approx(100 * pool / 15e9)
    for n in NEW + ("decode_hbm_roofline_share",):
        assert read(n) < 100.0
    # a span of an engine that does not say its real tokens: n x chunk
    trace.spans[1] = (pt.PREFILL, 40 * ms, 30 * ms,
                      {"n": 2, "rows": 2, "chunk": 128})
    clear_trace_caches()
    assert read("prefill_step_mfu") == pytest.approx(
        100 * f.prefill_flops(c, 256, 2, 128) / 197e12 / 20e-3)

    # every new reader on a view of ANOTHER family, and on one with no
    # trace at all: None, nothing raised
    class Other(View):
        cfg = R.load_json(os.path.join(R.HERE, "configs",
                                       "lfm2-24b-a2b-serve.json"))
        family = families.of(cfg)
        record = {"trace_path": "hand-made", "e2e": {}}

    class Bare(View):
        record = {"trace_path": None, "e2e": {}}
        window = None

    clear_trace_caches()
    for n in NEW:
        assert read(n, Other) is None, n
        assert read(n, Bare) is None, n
    clear_trace_caches()
