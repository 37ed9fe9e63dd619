"""The serving stack's description of the LFM2 (``lfm2_moe``) block against
the plain reference (``models/reference_hybrid_moe.py``), at a tiny size on
the CPU: ``layer_types`` ``[conv, conv, full, conv, conv, conv, full, conv,
conv]`` (the published pattern's first nine), one leading dense layer, a
gated short convolution of 3 taps whose state lives in a ring of 6 rows a
slot (= 3 - 1 + a chunk of 4), 4 query heads over 2 KV heads of 8 numbers
with QK-norm, 8 sigmoid-routed experts top-2 all held, no shared expert, the
head tied to the embedding, bfloat16 weights. The description is what
``from_hf`` reads from the published keys.

Tolerances. Program and reference hold the SAME bfloat16-valued weights and
seeded float32 gains and compute in float32 under ``highest``: they differ
by summation order only — the filter over a ring's rows and the call's own
against a loop over taps on a zero-padded sequence, grouped heads against a
loop over heads, sorted queues against a loop over experts. Logits of
magnitude ~0.3 (a tied head: 0.02-scale embedding rows against unit-RMS
states of 32 numbers) agree to ``LOGIT_TOL`` = 2e-5 (measured 6e-7); two
program paths over the same rows agree to ``PATH_TOL`` = 1e-5. What the
tolerance must catch is orders larger: a missing ``b`` or ``c`` gate, a tap
left out, the taps reversed, a stale row read before position 0, a missing
QK-norm or ``expert_bias`` (each measured 0.3-0.6: these weights are
random, and a wrong operator is another model), a head scaled by 1.25
(0.09), an untied head (4.1) or a bfloat16 product each move a logit by
2e-3 or more (``test_what_the_tolerance_catches``,
``test_a_readmitted_slot_serves_what_a_fresh_pool_serves``). Served tokens
against one-shot ``generate`` are compared exactly: the engine's oracle
guarantee.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import test_afmoe_serving as afmoe
from test_hybrid_moe_serving import (
    _lowered_programs, _pool_rows, _slot_logits,
)
from uccl_tpu import obs
from uccl_tpu.models import inference
from uccl_tpu.models import moe_inference as mi
from uccl_tpu.models import reference_hybrid_moe as ref
from uccl_tpu.models.moe_inference import (
    MoEServeConfig, MoEServer, MoESlotCache, init_params,
)
from uccl_tpu.serving import MoEBackend, ServingEngine

LOGIT_TOL = 2e-5
PATH_TOL = 1e-5
MAX_SEQ = 64
VOCAB = 48
KINDS = ("conv", "conv", "full", "conv", "conv", "conv", "full", "conv",
         "conv")
OVERRIDES = dict(capacity_factor=4.0, param_dtype="bfloat16")

# the published pattern: three conv layers to an attention layer, from the
# second layer on
LAYER_TYPES = ["conv", "conv", "full_attention"] + \
    ["conv", "conv", "conv", "full_attention"] * 9 + ["conv"]

# the model's own keys at a tiny size, as ``from_hf`` reads them
TINY = dict(
    model_type="lfm2_moe", conv_L_cache=3, conv_bias=False, hidden_size=32,
    intermediate_size=40, layer_types=LAYER_TYPES[:12],
    max_position_embeddings=128000, moe_intermediate_size=24, norm_eps=1e-05,
    norm_topk_prob=True, num_attention_heads=4, num_dense_layers=1,
    num_experts=8, num_experts_per_tok=2, num_hidden_layers=9,
    num_key_value_heads=2,
    rope_parameters=dict(rope_theta=1000000, rope_type="default"),
    routed_scaling_factor=1, use_expert_bias=True, vocab_size=VOCAB,
)

# the published keys (the catalog row's ``config``)
PUBLISHED = dict(
    conv_L_cache=3, conv_bias=False, hidden_size=2048,
    intermediate_size=11776, layer_types=LAYER_TYPES,
    max_position_embeddings=128000, model_type="lfm2_moe",
    moe_intermediate_size=1536, norm_eps=1e-05, norm_topk_prob=True,
    num_attention_heads=32, num_dense_layers=2, num_experts=64,
    num_experts_per_tok=4, num_hidden_layers=40, num_key_value_heads=8,
    rope_parameters=dict(rope_theta=1000000, rope_type="default"),
    routed_scaling_factor=1, use_expert_bias=True, vocab_size=65536,
)


def _server(devices, cfg):
    return MoEServer(cfg, Mesh(np.array(devices[:1]), ("dp",)))


@pytest.fixture(scope="module")
def model(devices):
    cfg = MoEServeConfig.from_hf(TINY, **OVERRIDES)
    params = init_params(jax.random.PRNGKey(13), cfg)
    srv = _server(devices, cfg)
    return cfg, params, srv, srv.shard_params(params)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


# -- the description, the tree and the pool ----------------------------------

def test_description_tree_and_pool(model):
    cfg, params, srv, placed = model
    assert cfg == MoEServeConfig(
        vocab=VOCAB, dim=32, n_layers=9, n_heads=4, n_kv_heads=2, head_dim=8,
        rope_theta=1e6, norm_eps=1e-5, moe_experts=8, moe_topk=2, moe_ffn=24,
        capacity_factor=4.0, layer_kinds=KINDS, conv_taps=3, qk_norm=True,
        tie_head=True, norm_gain_scale=0.1, first_k_dense=1, dense_ffn=40,
        gate="sigmoid_bias", routed_scale=1.0, param_dtype="bfloat16")
    assert (cfg.ring_rows("conv"), cfg.reach("conv")) == (6, 3)
    assert cfg.param_groups() == [
        ("dense_conv_blocks", 0), ("conv_blocks", 0), ("blocks", 0),
        ("conv_blocks", 1), ("conv_blocks", 2), ("conv_blocks", 3),
        ("blocks", 1), ("conv_blocks", 4), ("conv_blocks", 5)]
    assert inference.cache_groups(cfg) == [
        ("conv", 0), ("conv", 1), ("full", 0), ("conv", 2), ("conv", 3),
        ("conv", 4), ("full", 1), ("conv", 5), ("conv", 6)]
    # a tied description has no head leaf
    assert set(params) == {"embed", "dense_conv_blocks", "conv_blocks",
                           "blocks", "final_norm"}
    conv, full, dense = (params[g] for g in (
        "conv_blocks", "blocks", "dense_conv_blocks"))
    for group, n in ((conv, 6), (dense, 1)):
        assert group["w_in"].shape == (n, 32, 96)
        assert group["w_out"].shape == (n, 32, 32)
        assert group["w_conv"].shape == (n, 32, 3)
        assert group["w_in"].dtype == group["w_conv"].dtype == jnp.bfloat16
        assert not {"wq", "wk", "wv", "wo", "q_norm", "k_norm"} & set(group)
        assert group["ln1"].shape == group["ln2"].shape == (n, 32)
    # the filter's taps are drawn at 1/sqrt(3): none is negligible
    assert 0.4 < float(jnp.std(conv["w_conv"].astype(jnp.float32))) < 0.75
    assert full["wq"].shape == (2, 32, 4 * 8)
    assert full["wk"].shape == full["wv"].shape == (2, 32, 2 * 8)
    assert full["q_norm"].shape == full["k_norm"].shape == (2, 8)
    assert "wg" not in full and "ln1_post" not in full and "sink" not in full
    assert "router" not in dense and dense["w_gate"].shape == (1, 32, 40)
    assert conv["router"].shape == (6, 32, 8)
    assert conv["router_bias"].shape == (6, 8)
    assert conv["we_gate"].shape == (6, 8, 32, 24)
    assert "ws_gate" not in conv
    gains = np.concatenate([np.asarray(conv[leaf]).ravel()
                            for leaf in ("ln1", "ln2")])
    assert 0.05 < float(np.std(gains)) < 0.15
    # the pool: every position of the two attention layers, a ring of 6
    # rows of y for each of the seven conv layers, and no value rows for them
    cache = srv.slot_cache(2, MAX_SEQ)
    assert cache.k["full"].shape == cache.v["full"].shape \
        == (1, 2, 2, MAX_SEQ, 2 * 8)
    assert cache.k["conv"].shape == (1, 7, 2, 6, 32)
    assert set(cache.v) == {"full"}
    assert obs.gauge("serving_kv_ring_rows").get(group="conv") == 6
    row = obs.gauge("serving_kv_row_bytes")
    assert row.get(kind="full") == 2 * 16 * 4
    assert row.get(kind="conv") == 32 * 4
    pool = obs.gauge("serving_kv_pool_bytes")
    assert pool.get(group="full") == 2 * 2 * MAX_SEQ * 2 * 16 * 4
    assert pool.get(group="conv") == 7 * 2 * 6 * 32 * 4
    # the one-shot cache keeps every position of both groups
    once = mi.MoEKVCache.empty(cfg, 1, 1, MAX_SEQ)
    assert once.k["conv"].shape == (1, 7, 1, MAX_SEQ, 32)
    assert set(once.v) == {"full"}


def test_from_hf_reads_the_published_keys():
    cfg = MoEServeConfig.from_hf(PUBLISHED, param_dtype="bfloat16")
    assert (cfg.attn, cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.v_head_dim, cfg.vocab) == (
        "gqa", 40, 2048, 32, 8, 64, 0, 65536)
    assert cfg.layer_kinds[:9] == KINDS and len(cfg.layer_kinds) == 40
    assert (cfg.layer_kinds.count("conv"),
            cfg.layer_kinds.count("full")) == (30, 10)
    assert (cfg.conv_taps, cfg.ring_rows("conv"), cfg.window, cfg.rope_theta,
            cfg.norm_eps, cfg.qk_norm, cfg.attn_gate, cfg.post_norms,
            cfg.unrotated, cfg.sink, cfg.tie_head, cfg.embed_scale) == (
        3, 6, 0, 1e6, 1e-5, True, False, False, (), (), True, 1.0)
    assert (cfg.moe_experts, cfg.n_held, cfg.experts_held, cfg.moe_topk,
            cfg.moe_ffn, cfg.first_k_dense, cfg.dense_ffn, cfg.gate,
            cfg.routed_scale, cfg.shared_ffn) == (
        64, 64, 0, 4, 1536, 2, 11776, "sigmoid_bias", 1.0, 0)
    # the benchmark's cut: layer_types is read up to the depth, the ring is
    # what the file states
    cut = MoEServeConfig.from_hf(dict(
        PUBLISHED, num_hidden_layers=9, num_dense_layers=1), conv_ring=72)
    assert cut.layer_kinds == KINDS and cut.first_k_dense == 1
    assert cut.ring_rows("conv") == 72 and cut.n_moe_layers == 8
    for keys, match in (
            (dict(conv_bias=True), "conv_bias true"),
            (dict(norm_topk_prob=False), "norm_topk_prob false"),
            (dict(use_expert_bias=False), "use_expert_bias false"),
            (dict(rope_parameters=dict(rope_theta=1e6, rope_type="yarn")),
             "rope_type 'yarn'"),
            (dict(n_group=8), "group-limited"),
            (dict(layer_types=["conv", "sliding_attention"] * 20),
             "sliding_attention"),
            (dict(layer_types=["conv"] * 4), "got 4 entries")):
        with pytest.raises(ValueError, match=match):
            MoEServeConfig.from_hf(dict(PUBLISHED, **keys))
    # what belongs to conv layers is refused without them, and they need it
    for field in (dict(conv_taps=3), dict(conv_ring=8)):
        with pytest.raises(ValueError, match="belong to layer_kinds"):
            MoEServeConfig(**field)
    with pytest.raises(ValueError, match="conv layers need conv_taps"):
        MoEServeConfig.from_hf(TINY, conv_taps=0)
    with pytest.raises(ValueError, match="conv layers need conv_taps"):
        MoEServeConfig.from_hf(dict(afmoe.TINY), conv_taps=3)
    with pytest.raises(ValueError, match="must hold a filter's taps"):
        MoEServeConfig.from_hf(TINY, conv_ring=2)


def test_the_entry_point_builds_the_example_configuration():
    """``uccl_tpu/serve.py --model-config examples/configs/
    lfm2_moe_tiny.json``: the description ``_moe_cfg`` reads, its conv ring
    sized for the widest write the flags ask for."""
    import argparse
    import os

    from uccl_tpu import serve

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples", "configs",
        "lfm2_moe_tiny.json")
    args = argparse.Namespace(model_config=path, ckpt_dir="",
                              prefill_chunk=4, spec_k=6)
    cfg = serve._moe_cfg(args)
    assert cfg.layer_kinds == KINDS and cfg.tie_head and cfg.conv_taps == 3
    assert (cfg.dim, cfg.moe_experts, cfg.moe_topk, cfg.capacity_factor,
            cfg.param_dtype) == (64, 8, 2, 8.0, "bfloat16")
    assert cfg.ring_rows("conv") == 3 - 1 + 7  # a verify window of 6 + 1
    args.spec_k = 0
    assert serve._moe_cfg(args).ring_rows("conv") == 6  # 3 - 1 + a chunk


# -- program against reference, through every program ------------------------

def test_full_forward_is_the_reference(model):
    cfg, params, srv, placed = model
    toks = _tokens(29)
    want = np.asarray(ref.forward_logits(params, toks, cfg))
    cache = mi.MoEKVCache.empty(cfg, 1, 1, MAX_SEQ)
    got, _ = srv._forward(placed, jnp.asarray(toks)[None, None], cache,
                          "sort")
    assert np.abs(want).max() > 0.2  # the tolerance is against real logits
    np.testing.assert_allclose(np.asarray(got)[0, 0], want, atol=LOGIT_TOL)


def test_sort_is_dense(model):
    cfg, params, srv, placed = model
    toks = jnp.asarray(_tokens(29))[None, None]
    out = [np.asarray(srv._forward(
        placed, toks, mi.MoEKVCache.empty(cfg, 1, 1, MAX_SEQ), impl)[0])
        for impl in ("sort", "dense")]
    np.testing.assert_allclose(out[0], out[1], atol=PATH_TOL)


def test_prefill_then_cached_decode_past_ring_wraps(model):
    """Chunked prefill into the slot pool, then one token at a time until
    the conv layers' ring of 6 has wrapped eight times."""
    cfg, params, srv, placed = model
    toks = _tokens(56, seed=1)
    want = np.asarray(ref.forward_logits(params, toks, cfg))
    cache = srv.slot_cache(2, MAX_SEQ)
    on = np.array([True, False])
    both = np.zeros((2, 56), np.int32)
    both[0] = toks
    for lo in range(0, 8, 4):
        part, cache = _slot_logits(srv, placed, both[:, lo:lo + 4], cache,
                                   [lo, 0], on)
        np.testing.assert_allclose(part[0], want[lo:lo + 4], atol=LOGIT_TOL)
    for i in range(8, 56):
        one, cache = _slot_logits(srv, placed, both[:, i:i + 1], cache,
                                  [i, 0], on)
        np.testing.assert_allclose(one[0, 0], want[i], atol=LOGIT_TOL)


def test_chunked_prefill_with_a_padded_last_chunk_is_one_shot(model):
    """Prompts of 19 and 30 in chunks of 4 (the widest write a ring of 6
    takes at 3 taps), the last right-padded with token 0: the padding's
    rows of ``y`` lie past the prompt in the ring and the first decode steps
    rewrite them before a tap reaches them."""
    cfg, params, srv, placed = model
    a, b = _tokens(19 + 6, seed=2), _tokens(30 + 6, seed=3)
    want = [np.asarray(ref.forward_logits(params, t, cfg)) for t in (a, b)]
    lens = (19, 30)
    padded = np.zeros((2, 32), np.int32)
    padded[0, :19], padded[1, :30] = a[:19], b[:30]
    cache = srv.slot_cache(2, MAX_SEQ)
    on = np.ones(2, bool)
    parts = []
    for lo in range(0, 32, 4):
        live = np.array([lo < n for n in lens])
        part, cache = _slot_logits(srv, placed, padded[:, lo:lo + 4], cache,
                                   [lo, lo], live)
        parts.append(part)
    got = np.concatenate(parts, axis=1)
    for r, n in enumerate(lens):
        np.testing.assert_allclose(got[r, :n], want[r][:n], atol=LOGIT_TOL)
    for j in range(6):
        tok = np.array([[a[19 + j]], [b[30 + j]]], np.int32)
        one, cache = _slot_logits(srv, placed, tok, cache,
                                  [19 + j, 30 + j], on)
        np.testing.assert_allclose(one[0, 0], want[0][19 + j],
                                   atol=LOGIT_TOL)
        np.testing.assert_allclose(one[1, 0], want[1][30 + j],
                                   atol=LOGIT_TOL)


def test_verify_window_is_single_steps_with_rejected_rows(model):
    """A 4-wide verify window from position 20 = four single steps = the
    reference; then, with only two of its rows accepted, the next window
    starts at 22 over the rejected rows' leavings in the ring and is still
    the reference's; a masked neighbour's rows are untouched throughout."""
    cfg, params, srv, placed = model
    toks = _tokens(40, seed=4)
    want = np.asarray(ref.forward_logits(params, toks, cfg))
    both = np.zeros((2, 40), np.int32)
    both[0] = toks
    both[1] = _tokens(40, seed=5)
    cache = srv.slot_cache(2, MAX_SEQ)
    for lo in range(0, 20, 4):
        _, cache = _slot_logits(srv, placed, both[:, lo:lo + 4], cache,
                                [lo, lo], np.ones(2, bool))
    neighbour = _pool_rows(cache, 1)
    only0 = np.array([True, False])
    window, after = _slot_logits(srv, placed, both[:, 20:24], cache,
                                 [20, 20], only0)
    steps, c = [], cache
    for i in range(20, 24):
        one, c = _slot_logits(srv, placed, both[:, i:i + 1], c, [i, i],
                              only0)
        steps.append(one)
    np.testing.assert_allclose(np.concatenate(steps, axis=1)[0], window[0],
                               atol=PATH_TOL)
    np.testing.assert_allclose(window[0], want[20:24], atol=LOGIT_TOL)
    other = toks.copy()
    other[22:] = _tokens(18, seed=6)
    want2 = np.asarray(ref.forward_logits(params, other, cfg))
    redo = np.zeros((2, 4), np.int32)
    redo[0] = other[22:26]
    window2, after2 = _slot_logits(srv, placed, redo, after, [22, 20], only0)
    np.testing.assert_allclose(window2[0], want2[22:26], atol=LOGIT_TOL)
    # the rejected rows left other numbers behind than the ones now read
    assert np.abs(want2[22:26] - want[22:26]).max() > 100 * LOGIT_TOL
    for a, b in zip(_pool_rows(after2, 1), neighbour):
        assert np.array_equal(a, b)


def test_compact_rungs_are_the_pool_wide_rung(model):
    """The [1 | 2, chunk] compact programs over named slots against the
    pool-wide program: the same logits (a compact row reads its own slot's
    ring), and slots not named untouched."""
    cfg, params, srv, placed = model
    prompts = [_tokens(12, seed=7 + i) for i in range(3)]
    three = np.stack(prompts)
    on = np.ones(3, bool)
    wide = srv.slot_cache(3, MAX_SEQ)
    logits_wide = []
    for lo in (0, 4, 8):
        part, wide = _slot_logits(srv, placed, three[:, lo:lo + 4], wide,
                                  [lo] * 3, on)
        logits_wide.append(part)
    logits_wide = np.concatenate(logits_wide, axis=1)
    compact = srv.slot_cache(3, MAX_SEQ)
    got = {}
    for slots in ([2], [0, 1]):  # a one-row rung, then a two-row rung
        parts = []
        for lo in (0, 4, 8):
            untouched = [s for s in range(3) if s not in slots]
            before = [_pool_rows(compact, s) for s in untouched]
            part, compact = _slot_logits(
                srv, placed, three[slots, lo:lo + 4], compact,
                [lo] * len(slots), np.ones(len(slots), bool), slots=slots)
            for s, rows in zip(untouched, before):
                for a, b in zip(_pool_rows(compact, s), rows):
                    assert np.array_equal(a, b)
            parts.append(part)
        for r, s in enumerate(slots):
            got[s] = np.concatenate(parts, axis=1)[r]
    for s in range(3):
        np.testing.assert_allclose(got[s], logits_wide[s], atol=PATH_TOL)
        want = np.asarray(ref.forward_logits(params, prompts[s], cfg))
        np.testing.assert_allclose(got[s], want, atol=LOGIT_TOL)


def test_a_readmitted_slot_serves_what_a_fresh_pool_serves(model):
    """A slot's next occupant starts at position 0 over the rows a LONGER
    occupant left in every ring and in the full group: its first taps reach
    before position 0 and read zero, not the last occupant's ``y``. The
    logits are the reference's and, bit for bit, a fresh pool's."""
    cfg, params, srv, placed = model
    long, short = _tokens(23, seed=30), _tokens(10, seed=31)
    want = np.asarray(ref.forward_logits(params, short, cfg))
    on = np.array([True, False])

    def serve(cache, toks):
        pad = np.zeros((2, -(-len(toks) // 4) * 4), np.int32)
        pad[0, :len(toks)] = toks
        parts = []
        for lo in range(0, pad.shape[1], 4):
            part, cache = _slot_logits(srv, placed, pad[:, lo:lo + 4], cache,
                                       [lo, 0], on)
            parts.append(part[0])
        return np.concatenate(parts)[:len(toks)], cache

    fresh, _ = serve(srv.slot_cache(2, MAX_SEQ), short)
    _, used = serve(srv.slot_cache(2, MAX_SEQ), long)
    # every row of slot 0's rings holds the longer occupant's numbers
    rings = np.asarray(used.k["conv"])[0, :, 0]  # [7, 6, 32]
    assert np.all(np.any(rings != 0, axis=-1))
    again, _ = serve(used, short)
    np.testing.assert_array_equal(again, fresh)
    np.testing.assert_allclose(again, want, atol=LOGIT_TOL)
    # a tap that read the ring's stale row would be told apart: the first
    # position's logits with the last occupant's y standing before it
    stale = np.concatenate([long[-2:], short])
    moved = np.asarray(ref.forward_logits(params, stale, cfg))[2:]
    assert np.abs(moved[0] - want[0]).max() > 100 * LOGIT_TOL


def _serves_generates_tokens(srv, placed, lens_and_new, **engine_kw):
    backend = MoEBackend(srv, placed, batch_local=2, max_seq=MAX_SEQ,
                         decode_impl="sort")
    eng = ServingEngine(backend, **engine_kw)
    reqs = [eng.submit(_tokens(n, seed=20 + n), max_new_tokens=m)
            for n, m in lens_and_new]
    eng.drain()
    for r in reqs:
        want = srv.generate(placed, jnp.asarray(r.prompt)[None, None],
                            r.max_new_tokens, MAX_SEQ, impl="sort")
        assert r.out_tokens == np.asarray(want)[0, 0].tolist(), r.rid
    assert eng.pool.leaked() == 0


def test_engine_served_tokens_are_generates(model):
    """Five requests through two slots: every slot is re-admitted over a
    previous occupant's rings at least once."""
    cfg, params, srv, placed = model
    _serves_generates_tokens(
        srv, placed, ((5, 24), (23, 20), (11, 30), (3, 9), (17, 12)),
        prefill_chunk=4)


# -- the ring's rule, said once for both ring kinds ----------------------------

def _ring_model(devices, kind, ring):
    """(server, placed params, reach) of the kind's tiny model with a ring
    of ``ring`` rows: Trinity's for "window" (window 8), this file's for
    "conv" (3 taps)."""
    hf, over = (afmoe.TINY, afmoe.OVERRIDES) if kind == "window" \
        else (TINY, OVERRIDES)
    cfg = MoEServeConfig.from_hf(hf, **{**over, kind + "_ring": ring})
    srv = _server(devices, cfg)
    params = init_params(jax.random.PRNGKey(11), cfg)
    return srv, srv.shard_params(params), cfg.reach(kind)


@pytest.mark.parametrize("write", ["chunk", "verify_window"])
@pytest.mark.parametrize("fits", [True, False], ids=["fits", "a_row_short"])
@pytest.mark.parametrize("kind", ["window", "conv"])
def test_a_ring_holds_its_reach_less_one_and_the_widest_write(
        devices, kind, fits, write):
    """THE ring's rule, for both ring kinds: with a widest write of 5 — a
    prefill chunk, or a verify window of 4 drafts and the committed token —
    a ring of ``reach - 1 + 5`` rows (window 8: 12; 3 taps: 7) serves
    ``generate``'s tokens exactly, through several wraps; one row fewer is
    refused where the write's width is known, with the sentence that says
    why."""
    reach = 8 if kind == "window" else 3
    srv, placed, got = _ring_model(devices, kind, reach - 1 + 5 - (not fits))
    assert got == reach
    engine_kw = dict(prefill_chunk=5) if write == "chunk" \
        else dict(prefill_chunk=2, spec_k=4)
    if fits:
        _serves_generates_tokens(srv, placed, ((9, 30), (23, 26)),
                                 **engine_kw)
        return
    backend = MoEBackend(srv, placed, batch_local=2, max_seq=MAX_SEQ,
                         decode_impl="sort")
    reads = "window" if kind == "window" else "taps"
    with pytest.raises(ValueError, match=rf"must hold {reads} - 1 \+ the "
                       rf"widest write \({reach} - 1 \+ 5\)"):
        ServingEngine(backend, **engine_kw)
    with pytest.raises(ValueError, match="cannot take a write of 5"):
        _slot_logits(srv, placed, np.zeros((2, 5), np.int32),
                     srv.slot_cache(2, MAX_SEQ), [0, 0], np.ones(2, bool))


# -- what a pool with conv rings cannot do yet ----------------------------------

@pytest.mark.parametrize("what", [
    "prefix_cache", "kv_tiers", "export_rows", "import_rows", "copy_prefix",
    "disagg", "preempt", "whole_prompt", "adapters", "lora"])
def test_conv_rings_refuse_what_they_cannot_do(model, what):
    from uccl_tpu.serving import PrefixCache
    from uccl_tpu.serving.adapters import AdapterStore
    from uccl_tpu.serving.disagg import wire_format_for
    from uccl_tpu.serving.kv_tiers import TieredKVCache

    cfg, params, srv, placed = model
    backend = MoEBackend(srv, placed, batch_local=2, max_seq=MAX_SEQ,
                         decode_impl="sort")
    rows = np.zeros((9, 4, 32), np.float32)
    calls = {
        "prefix_cache": lambda: ServingEngine(
            backend, prefill_chunk=4, prefix_cache=PrefixCache(4)),
        "kv_tiers": lambda: ServingEngine(
            backend, prefill_chunk=4, prefix_cache=PrefixCache(4),
            kv_tiers=TieredKVCache(host_bytes=1 << 20)),
        "export_rows": lambda: backend.export_slot_kv(0, 0, 4),
        "import_rows": lambda: backend.import_slot_kv(0, rows, rows,
                                                      length=4),
        "copy_prefix": lambda: backend.copy_slot_prefix(1, 0, 4),
        "disagg": lambda: wire_format_for(backend),
        "preempt": lambda: ServingEngine(
            backend, prefill_chunk=4, priority_classes=True, preempt=True),
    }
    if what in calls:
        with pytest.raises(ValueError, match="ring groups"):
            calls[what]()
    elif what == "whole_prompt":
        with pytest.raises(ValueError, match="requires prefill_chunk"):
            ServingEngine(backend)
    elif what == "adapters":
        store = AdapterStore.__new__(AdapterStore)  # refused before any use
        with pytest.raises(ValueError, match="LoRA adapters beside conv"):
            ServingEngine(backend, prefill_chunk=4, adapters=store)
    else:
        with pytest.raises(ValueError, match="LoRA"):
            inference._short_conv(None, None, None, None, None, None, None,
                                  cfg, lora=lambda h, t: h)


# -- what the tolerance catches ----------------------------------------------

def _program_logits(devices, cfg, params, toks):
    srv = _server(devices, cfg)
    cache = mi.MoEKVCache.empty(cfg, 1, 1, MAX_SEQ)
    got, _ = srv._forward(srv.shard_params(params),
                          jnp.asarray(toks)[None, None], cache, "sort")
    return np.asarray(got)[0, 0]


def _leaf(name, change):
    """``change(leaf)`` over ``name`` in every layer group that has it."""
    return lambda g: {k: change(v) if k == name else v for k, v in g.items()}


def _gate_ones(part):
    """The three gates' projection with part ``part`` (0: b, 1: c) reading
    one everywhere, as a model WITHOUT that gate: a reference-side fault
    (no weight makes a product of the normed input constant)."""
    real = ref.jnp.split

    def split(a, n, axis=-1):
        parts = real(a, n, axis=axis)
        return [jnp.ones_like(p) if i == part else p
                for i, p in enumerate(parts)]
    return split


FAULTS = {
    # a leaf the program reads, left out or altered
    "a_tap_left_out": _leaf("w_conv", lambda w: w.at[..., 0].set(0)),
    "the_position_itself_left_out": _leaf(
        "w_conv", lambda w: w.at[..., 2].set(0)),
    "taps_reversed": _leaf("w_conv", lambda w: w[..., ::-1]),
    "b_and_c_exchanged": _leaf("w_in", lambda w: jnp.concatenate(
        [w[..., 32:64], w[..., :32], w[..., 64:]], axis=-1)),
    "no_qk_norm": afmoe._without("q_norm", "k_norm"),
    "unit_qk_gains": afmoe._unit("q_norm", "k_norm"),
    "unit_norm_gains": afmoe._unit("ln1", "ln2"),
    "no_expert_bias": _leaf("router_bias", jnp.zeros_like),
    # the head
    "untied_head": "head",
    "head_altered": "head_scaled",
    # a gate the reference computes, left out there
    "no_b_gate": 0,
    "no_c_gate": 1,
    "bf16_product": None,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_what_the_tolerance_catches(model, devices, fault, monkeypatch):
    """Each way the program could be this model almost: program and
    reference move apart by far more than LOGIT_TOL."""
    cfg, params, srv, placed = model
    toks = _tokens(29)
    wrong = params
    how = FAULTS[fault]
    if callable(how):
        wrong = afmoe._groups_with(params, how)
    elif how == "head":
        wrong = dict(params, head=init_params(
            jax.random.PRNGKey(13),
            dataclasses.replace(cfg, tie_head=False))["head"])
    elif how == "head_scaled":
        wrong = dict(params, head=(params["embed"].T * 1.25))
    elif how is None:
        # the CPU computes every product in float32 whatever it is asked:
        # round the projections' activation operand as a bfloat16 product
        # would (the weights are bfloat16-valued already)
        real = inference.rms_norm
        monkeypatch.setattr(
            inference, "rms_norm", lambda *a, **kw: real(*a, **kw).astype(
                jnp.bfloat16).astype(jnp.float32))
    got = _program_logits(devices, cfg, wrong, toks)
    if how in (0, 1):  # after the program was traced: the reference alone
        monkeypatch.setattr(ref.jnp, "split", _gate_ones(how))
    want = np.asarray(ref.forward_logits(params, toks, cfg))
    assert np.abs(got - want).max() > 100 * LOGIT_TOL, fault


# -- the reached-experts decode ----------------------------------------------

def test_the_reached_experts_decode_is_the_batched_layer_at_full_reach(
        model):
    """The server's own decode program (PR 39's loop over the experts the
    decoding rows reach) over a pool of 16 slots all decoding: the tokens
    are the argmax of the batched slot forward's logits, row for row, and
    with 16 rows x top-2 of 8 experts a layer some step reads every expert
    the 8 expert layers hold."""
    cfg, params, srv, placed = model
    slots = 16
    held = cfg.n_held * cfg.n_moe_layers
    cache = srv.slot_cache(slots, MAX_SEQ)
    prompts = np.stack([_tokens(4, seed=40 + s) for s in range(slots)])
    on = np.ones(slots, bool)
    _, cache = _slot_logits(srv, placed, prompts, cache, [0] * slots, on)
    cache = MoESlotCache(cache.k, cache.v, jnp.full((1, slots), 4, jnp.int32))
    tok = _tokens(slots, seed=60)
    most = 0
    for step in range(6):
        want, _ = _slot_logits(srv, placed, tok[:, None], cache,
                               [4 + step] * slots, on)
        got, read, cache = srv.decode_step_slots(
            placed, jnp.asarray(tok)[None], jnp.asarray(on)[None], cache,
            impl="sort")
        assert np.array_equal(np.asarray(got)[0],
                              np.argmax(want[:, 0], axis=-1))
        most = max(most, int(np.asarray(read)[0]))
        assert 0 < int(np.asarray(read)[0]) <= held
        tok = np.asarray(got)[0]
    assert most == held


# -- the scopes per kind in the compiled programs -----------------------------

LFM2_SCOPES = tuple(f"attn.{part}.full"
                    for part in ("qkv", "kv_write", "core", "out")) + (
    "conv.in_proj", "conv.state", "conv.mix", "conv.out_proj", "embed",
    "ffn.dense", "moe.router", "moe.route", "moe.dispatch", "moe.experts",
    "moe.combine", "head")


@pytest.fixture(scope="module")
def program_text(model):
    cfg, params, srv, placed = model
    return {name: low.compile().as_text()
            for name, low in _lowered_programs(srv, placed).items()}


@pytest.mark.parametrize("scope", LFM2_SCOPES)
@pytest.mark.parametrize("program", ("decode", "prefill"))
def test_programs_carry_their_scopes(program_text, program, scope):
    assert f"/{scope}/" in program_text[program], (
        f"{scope} is in no op_name of the compiled {program} program")


@pytest.mark.parametrize("scope", ("attn.core.window", "attn.gate",
                                   "ffn.post_norm", "moe.shared"))
@pytest.mark.parametrize("program", ("decode", "prefill"))
def test_programs_carry_nothing_of_another_block(program_text, program,
                                                 scope):
    assert f"/{scope}" not in program_text[program]
