"""The serving stack's description of the Trinity (``afmoe``) block against
the plain reference (``models/reference_hybrid_moe.py``), at a tiny size on
the CPU: ``layer_types`` ``[sliding, sliding, sliding, full, sliding]``, one
leading dense layer, 6 query heads over 2 KV heads of 8 numbers in both
kinds, rotary on the window layers only, each query and key head RMS-normed,
the heads' output gated, each branch's output normed before the residual
(sandwich norms), the embedding times sqrt(32), window 8 in a ring of 12
(= 8 - 1 + a chunk of 5), 16 sigmoid-routed experts top-4 of which 4 are
held beside a shared expert, the routed sum times 2.448, a sliced
vocabulary, bfloat16 weights. The description is what ``from_hf`` reads
from the published keys.

Tolerances. Program and reference hold the SAME bfloat16-valued weights and
seeded float32 gains (every norm's gain is 1 + 0.1 x a normal, so a gain
left out shows) and compute in float32 under ``highest``: they differ by
summation order only — grouped heads over a ring against a loop over heads
with a banded mask, sorted held queues against a loop over experts. Logits
of magnitude ~3 agree to ``LOGIT_TOL`` = 5e-5 (measured under 1e-5); two
program paths over the same rows agree to ``PATH_TOL`` = 2e-5. What the
tolerance must catch is orders larger: a missing gate, QK-norm, closing
norm, shared expert or embedding scale, a rotated full layer, an unscaled
routed sum, a 7-wide window or a bfloat16 product each move a logit by
5e-3 or more (``test_what_the_tolerance_catches``). Served tokens against
one-shot ``generate`` are compared exactly: the engine's oracle guarantee.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from test_hybrid_moe_serving import (
    _layer, _lowered_programs, _pool_rows, _slot_logits,
)
from uccl_tpu import obs
from uccl_tpu.models import inference
from uccl_tpu.models import moe_inference as mi
from uccl_tpu.models import reference_hybrid_moe as ref
from uccl_tpu.models.moe_inference import (
    MoEServeConfig, MoEServer, init_params,
)
from uccl_tpu.serving import MoEBackend, ServingEngine

LOGIT_TOL = 5e-5
PATH_TOL = 2e-5
MAX_SEQ = 64
VOCAB = 48  # an eighth of a published 384: ids are drawn below it
KINDS = ("window", "window", "window", "full", "window")
OVERRIDES = dict(capacity_factor=4.0, param_dtype="bfloat16", window_ring=12)

# the model's own keys at a tiny size, as ``from_hf`` reads them
TINY = dict(
    model_type="afmoe", hidden_size=32, num_attention_heads=6,
    num_key_value_heads=2, head_dim=8, num_hidden_layers=5,
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    global_attn_every_n_layers=4, num_dense_layers=1, intermediate_size=40,
    moe_intermediate_size=24, num_experts=4, router_experts=16,
    first_expert=8, num_experts_per_tok=4, num_shared_experts=1,
    score_func="sigmoid", route_norm=True, route_scale=2.448, n_group=1,
    topk_group=1, sliding_window=8, mup_enabled=True, rope_theta=10000,
    rope_scaling=None, rms_norm_eps=1e-05, vocab_size=VOCAB,
    tie_word_embeddings=False,
)

# the published keys (the catalog row's ``config``)
PUBLISHED = dict(
    global_attn_every_n_layers=4, head_dim=128, hidden_act="silu",
    hidden_size=3072, intermediate_size=12288,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 15,
    load_balance_coeff=5e-05, max_position_embeddings=262144,
    model_type="afmoe", moe_intermediate_size=3072, mup_enabled=True,
    n_group=1, num_attention_heads=48, num_dense_layers=6,
    num_expert_groups=1, num_experts=256, num_experts_per_tok=4,
    num_hidden_layers=60, num_key_value_heads=8, num_limited_groups=1,
    num_shared_experts=1, rms_norm_eps=1e-05, rope_scaling=None,
    rope_theta=10000, route_norm=True, route_scale=2.448,
    score_func="sigmoid", sliding_window=4096, tie_word_embeddings=False,
    topk_group=1, use_grouped_mm=True, vocab_size=200192,
)


def _server(devices, cfg):
    return MoEServer(cfg, Mesh(np.array(devices[:1]), ("dp",)))


@pytest.fixture(scope="module")
def model(devices):
    cfg = MoEServeConfig.from_hf(TINY, **OVERRIDES)
    params = init_params(jax.random.PRNGKey(11), cfg)
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
        vocab=VOCAB, dim=32, n_layers=5, n_heads=6, n_kv_heads=2, head_dim=8,
        rope_theta=1e4, norm_eps=1e-5, moe_experts=16, moe_topk=4,
        moe_ffn=24, capacity_factor=4.0, layer_kinds=KINDS, window=8,
        window_kv_heads=2, window_rope_theta=1e4, window_ring=12,
        unrotated=("full",), qk_norm=True, attn_gate=True, post_norms=True,
        embed_scale=math.sqrt(32), norm_gain_scale=0.1, experts_held=4,
        first_expert=8, first_k_dense=1, dense_ffn=40, shared_ffn=24,
        gate="sigmoid_bias", routed_scale=2.448, param_dtype="bfloat16")
    assert cfg.param_groups() == [
        ("dense_window_blocks", 0), ("window_blocks", 0),
        ("window_blocks", 1), ("blocks", 0), ("window_blocks", 2)]
    assert inference.cache_groups(cfg) == [
        ("window", 0), ("window", 1), ("window", 2), ("full", 0),
        ("window", 3)]
    assert set(params) == {"embed", "dense_window_blocks", "blocks",
                           "window_blocks", "final_norm", "head"}
    full, win, dense = (params[g] for g in (
        "blocks", "window_blocks", "dense_window_blocks"))
    for group, n in ((full, 1), (win, 3), (dense, 1)):
        assert group["wq"].shape == group["wg"].shape == (n, 32, 6 * 8)
        assert group["wk"].shape == group["wv"].shape == (n, 32, 2 * 8)
        assert group["wo"].shape == (n, 6 * 8, 32)
        assert group["wg"].dtype == jnp.bfloat16
        assert group["q_norm"].shape == group["k_norm"].shape == (n, 8)
        for leaf in ("ln1", "ln2", "ln1_post", "ln2_post"):
            assert group[leaf].shape == (n, 32)
        assert "sink" not in group
    # every gain of a layer is a seeded draw about one, float32
    gains = np.concatenate([np.asarray(win[leaf]).ravel() for leaf in (
        "ln1", "ln2", "ln1_post", "ln2_post", "q_norm", "k_norm")])
    assert win["q_norm"].dtype == jnp.float32
    assert 0.05 < float(np.std(gains)) < 0.15
    assert abs(float(np.mean(gains)) - 1.0) < 0.05
    assert not np.array_equal(np.asarray(win["ln1"]),
                              np.asarray(win["ln1_post"]))
    # the router scores all 16; the 4 held experts' leaves and the shared
    # expert are here
    assert "router" not in dense and dense["w_gate"].shape == (1, 32, 40)
    assert win["router"].shape == (3, 32, 16)
    assert win["router_bias"].shape == (3, 16)
    assert win["we_gate"].shape == (3, 4, 32, 24)
    assert win["ws_gate"].shape == win["ws_up"].shape == (3, 32, 24)
    assert full["ws_down"].shape == (1, 24, 32)
    assert params["embed"].shape == (VOCAB, 32)
    cache = srv.slot_cache(2, MAX_SEQ)
    assert cache.k["full"].shape == cache.v["full"].shape \
        == (1, 1, 2, MAX_SEQ, 2 * 8)
    assert cache.k["window"].shape == cache.v["window"].shape \
        == (1, 4, 2, 12, 2 * 8)
    assert obs.gauge("serving_kv_ring_rows").get() == 12
    row = obs.gauge("serving_kv_row_bytes")
    assert row.get(kind="full") == row.get(kind="window") == 2 * 16 * 4
    pool = obs.gauge("serving_kv_pool_bytes")
    assert pool.get(group="full") == 1 * 2 * MAX_SEQ * 2 * 16 * 4
    assert pool.get(group="window") == 4 * 2 * 12 * 2 * 16 * 4


def test_from_hf_reads_the_published_keys():
    cfg = MoEServeConfig.from_hf(PUBLISHED, param_dtype="bfloat16")
    assert (cfg.attn, cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
            cfg.window_kv_heads, cfg.head_dim, cfg.v_head_dim, cfg.vocab) == (
        "gqa", 60, 3072, 48, 8, 8, 128, 0, 200192)
    assert cfg.layer_kinds[:5] == KINDS and len(cfg.layer_kinds) == 60
    assert cfg.layer_kinds.count("full") == 15
    assert (cfg.window, cfg.ring, cfg.rotary_dim, cfg.rope_theta,
            cfg.window_rope_theta, cfg.value_scale, cfg.sink, cfg.norm_eps,
            cfg.unrotated, cfg.qk_norm, cfg.attn_gate, cfg.post_norms,
            cfg.embed_scale) == (
        4096, 8192, 0, 1e4, 1e4, 1.0, (), 1e-5, ("full",), True, True, True,
        math.sqrt(3072))
    assert (cfg.moe_experts, cfg.n_held, cfg.experts_held, cfg.moe_topk,
            cfg.moe_ffn, cfg.first_k_dense, cfg.dense_ffn, cfg.gate,
            cfg.routed_scale, cfg.shared_ffn) == (
        256, 256, 0, 4, 3072, 6, 12288, "sigmoid_bias", 2.448, 3072)
    # a member's share as the benchmark's file states it: the depth cut reads
    # layer_types' first entries, num_experts is what is held, the router
    # keeps its width, the ring is window - 1 + a chunk of 128 in whole 128s
    cut = MoEServeConfig.from_hf(dict(
        PUBLISHED, num_hidden_layers=5, num_dense_layers=1, num_experts=32,
        router_experts=256, first_expert=0, vocab_size=25024),
        window_ring=4224)
    assert cut.layer_kinds == KINDS and cut.first_k_dense == 1
    assert (cut.moe_experts, cut.experts_held, cut.first_expert, cut.vocab,
            cut.ring) == (256, 32, 0, 25024, 4224)
    assert MoEServeConfig.from_hf(dict(PUBLISHED, mup_enabled=False)) \
        .embed_scale == 1.0
    for keys, match in (
            (dict(score_func="softmax"), "score_func 'softmax'"),
            (dict(route_norm=False), "route_norm false"),
            (dict(n_group=8), "group-limited"),
            (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
            (dict(layer_types=["sliding_attention", "linear_attention"] * 30),
             "linear_attention"),
            (dict(layer_types=["full_attention"] * 4), "got 4 entries")):
        with pytest.raises(ValueError, match=match):
            MoEServeConfig.from_hf(dict(PUBLISHED, **keys))
    # what belongs to layer kinds is refused without them
    for field in (dict(qk_norm=True), dict(attn_gate=True),
                  dict(post_norms=True), dict(unrotated=("full",))):
        with pytest.raises(ValueError, match="belong to layer_kinds"):
            MoEServeConfig(**field)
    with pytest.raises(ValueError, match="unrotated names layer kinds"):
        MoEServeConfig.from_hf(TINY, unrotated=("global",))


# -- program against reference, through every program ------------------------

def test_full_forward_is_the_reference(model):
    cfg, params, srv, placed = model
    toks = _tokens(29)
    want = np.asarray(ref.forward_logits(params, toks, cfg))
    cache = mi.MoEKVCache.empty(cfg, 1, 1, MAX_SEQ)
    got, _ = srv._forward(placed, jnp.asarray(toks)[None, None], cache,
                          "sort")
    assert np.abs(want).max() > 1.0  # the tolerance is against real logits
    np.testing.assert_allclose(np.asarray(got)[0, 0], want, atol=LOGIT_TOL)


def test_prefill_then_cached_decode_past_ring_wraps(model):
    """Chunked prefill into the slot pool, then one token at a time until
    the window layers' ring of 12 has wrapped four times."""
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
    """Prompts of 19 and 30 in chunks of 5 (the widest write a ring of 12
    takes at window 8), the last right-padded with token 0; then decoding
    continues as the reference's."""
    cfg, params, srv, placed = model
    a, b = _tokens(19 + 6, seed=2), _tokens(30 + 6, seed=3)
    want = [np.asarray(ref.forward_logits(params, t, cfg)) for t in (a, b)]
    lens = (19, 30)
    padded = np.zeros((2, 30), np.int32)
    padded[0, :19], padded[1, :30] = a[:19], b[:30]
    cache = srv.slot_cache(2, MAX_SEQ)
    on = np.ones(2, bool)
    parts = []
    for lo in range(0, 30, 5):
        live = np.array([lo < n for n in lens])
        part, cache = _slot_logits(srv, placed, padded[:, lo:lo + 5], cache,
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
    """A 5-wide verify window from position 20 = five single steps = the
    reference; then, with only two of its rows accepted, the next window
    starts at 22 over the rejected rows' leavings and is still the
    reference's; a masked neighbour's rows are untouched throughout."""
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
    window, after = _slot_logits(srv, placed, both[:, 20:25], cache,
                                 [20, 20], only0)
    steps, c = [], cache
    for i in range(20, 25):
        one, c = _slot_logits(srv, placed, both[:, i:i + 1], c, [i, i],
                              only0)
        steps.append(one)
    np.testing.assert_allclose(np.concatenate(steps, axis=1)[0], window[0],
                               atol=PATH_TOL)
    np.testing.assert_allclose(window[0], want[20:25], atol=LOGIT_TOL)
    other = toks.copy()
    other[22:] = _tokens(18, seed=6)
    want2 = np.asarray(ref.forward_logits(params, other, cfg))
    redo = np.zeros((2, 5), np.int32)
    redo[0] = other[22:27]
    window2, after2 = _slot_logits(srv, placed, redo, after, [22, 20], only0)
    np.testing.assert_allclose(window2[0], want2[22:27], atol=LOGIT_TOL)
    for a, b in zip(_pool_rows(after2, 1), neighbour):
        assert np.array_equal(a, b)


def test_compact_rungs_are_the_pool_wide_rung(model):
    """The [1 | 2, chunk] compact programs over named slots against the
    pool-wide program: the same logits, and slots not named untouched."""
    cfg, params, srv, placed = model
    prompts = [_tokens(15, seed=7 + i) for i in range(3)]
    three = np.stack(prompts)
    on = np.ones(3, bool)
    wide = srv.slot_cache(3, MAX_SEQ)
    logits_wide = []
    for lo in (0, 5, 10):
        part, wide = _slot_logits(srv, placed, three[:, lo:lo + 5], wide,
                                  [lo] * 3, on)
        logits_wide.append(part)
    logits_wide = np.concatenate(logits_wide, axis=1)
    compact = srv.slot_cache(3, MAX_SEQ)
    got = {}
    for slots in ([2], [0, 1]):  # a one-row rung, then a two-row rung
        parts = []
        for lo in (0, 5, 10):
            untouched = [s for s in range(3) if s not in slots]
            before = [_pool_rows(compact, s) for s in untouched]
            part, compact = _slot_logits(
                srv, placed, three[slots, lo:lo + 5], compact,
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
    cfg, params, srv, placed = model
    _serves_generates_tokens(srv, placed, ((5, 24), (23, 20), (11, 30)),
                             prefill_chunk=4)


# -- the ring's floor --------------------------------------------------------

# (reach - 1 + the widest write, for window and conv rings alike:
# tests/test_lfm2_serving.py::
# test_a_ring_holds_its_reach_less_one_and_the_widest_write)

def test_a_ring_under_a_window_is_refused_at_construction(model):
    cfg = model[0]
    dataclasses.replace(cfg, window_ring=8)  # a window's rows: the floor
    with pytest.raises(ValueError, match="must hold a window's rows"):
        dataclasses.replace(cfg, window_ring=7)


# -- what the tolerance catches ----------------------------------------------

def _program_logits(devices, cfg, params, toks):
    srv = _server(devices, cfg)
    cache = mi.MoEKVCache.empty(cfg, 1, 1, MAX_SEQ)
    got, _ = srv._forward(srv.shard_params(params),
                          jnp.asarray(toks)[None, None], cache, "sort")
    return np.asarray(got)[0, 0]


def _groups_with(params, change):
    """``params`` with ``change(group dict) -> group dict`` over every
    stacked layer group."""
    return {name: change(dict(leaf)) if isinstance(leaf, dict) else leaf
            for name, leaf in params.items()}


def _without(*leaves):
    return lambda g: {k: v for k, v in g.items() if k not in leaves}


def _unit(*leaves):
    return lambda g: {k: jnp.ones_like(v) if k in leaves else v
                      for k, v in g.items()}


FAULTS = {
    # a term the program reads from the layer's leaves, left out
    "no_gate": _without("wg"),
    "no_qk_norm": _without("q_norm", "k_norm"),
    "unit_qk_gains": _unit("q_norm", "k_norm"),
    "no_attention_closing_norm": _without("ln1_post"),
    "no_ffn_closing_norm": _without("ln2_post"),
    "unit_closing_gains": _unit("ln1_post", "ln2_post"),
    "no_shared_expert": _without("ws_gate", "ws_up", "ws_down"),
    # a number of the description, altered
    "unscaled_embedding": dict(embed_scale=1.0),
    "unscaled_routed_sum": dict(routed_scale=1.0),
    "rotated_full_layer": dict(unrotated=()),
    "unrotated_window_layers": dict(unrotated=("full", "window")),
    "window_7": dict(window=7),
    "full_layer_windowed": dict(layer_kinds=("window",) * 5),
    "bf16_product": None,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_what_the_tolerance_catches(model, devices, fault, monkeypatch):
    """Each way the program could be this model almost: the reference moves
    away by far more than LOGIT_TOL."""
    cfg, params, srv, placed = model
    toks = _tokens(29)
    want = np.asarray(ref.forward_logits(params, toks, cfg))
    wrong_cfg, wrong_params = cfg, params
    how = FAULTS[fault]
    if callable(how):
        wrong_params = _groups_with(params, how)
    elif how is not None:
        wrong_cfg = dataclasses.replace(cfg, **how)
        if "layer_kinds" in how:  # the full layer's leaves, stacked last
            wrong_params = dict(params)
            full = wrong_params.pop("blocks")
            wrong_params["window_blocks"] = jax.tree.map(
                lambda a, b: jnp.concatenate([a[:2], b, a[2:]]),
                params["window_blocks"], full)
    else:
        # the CPU computes every product in float32 whatever it is asked:
        # round the projections' activation operand as a bfloat16 product
        # would (the weights are bfloat16-valued already)
        real = inference.rms_norm
        monkeypatch.setattr(
            inference, "rms_norm", lambda *a, **kw: real(*a, **kw).astype(
                jnp.bfloat16).astype(jnp.float32))
    got = _program_logits(devices, wrong_cfg, wrong_params, toks)
    assert np.abs(got - want).max() > 100 * LOGIT_TOL, fault


# -- the held share beside the shared expert ---------------------------------

def _ffn_block(devices, cfg, h2, lp):
    """The program's FFN hook (``_moe_block``: held share + shared expert)
    of one layer's leaves on rows h2 [T, H], over a one-member mesh."""
    block = mi._moe_block(cfg, "sort")
    out = jax.jit(shard_map(
        lambda x, p: block(x, jax.tree.map(lambda a: a[0], p)),
        mesh=Mesh(np.array(devices[:1]), ("dp",)), in_specs=(P("dp"), P("dp")),
        out_specs=P("dp"), check_vma=False))(
        h2[None], jax.tree.map(lambda a: a[None], lp))
    return np.asarray(out)[0]


def test_the_shares_of_all_holders_with_the_shared_expert_once(devices):
    """THE test that ties the share to the model: 16 experts held four at a
    time by four members, a shared expert that every member computes alike.
    Each member's expert layer is its part of the routed sum plus the shared
    expert; the four parts and the shared expert COUNTED ONCE add up to the
    uncut reference's layer (16 experts and the shared one)."""
    rng = np.random.default_rng(3)
    t, h, f, e, held = 24, 16, 24, 16, 4
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(h, e)) / 4, jnp.float32)
    bias = jnp.asarray(rng.normal(size=e) * 0.3, jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(e, h, f)) / 4, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e, f, h)) / 5, jnp.float32)
    shared = dict(
        ws_gate=jnp.asarray(rng.normal(size=(h, f)) / 4, jnp.float32),
        ws_up=jnp.asarray(rng.normal(size=(h, f)) / 4, jnp.float32),
        ws_down=jnp.asarray(rng.normal(size=(f, h)) / 5, jnp.float32))
    uncut_cfg = MoEServeConfig(dim=h, moe_experts=e, moe_topk=4, moe_ffn=f,
                               shared_ffn=f, gate="sigmoid_bias",
                               routed_scale=2.448, capacity_factor=4.0)
    lp = dict(router=router, router_bias=bias, we_gate=wg, we_up=wu,
              we_down=wd, **shared)
    once = np.asarray(ref._swiglu(x, shared["ws_gate"], shared["ws_up"],
                                  shared["ws_down"]))
    uncut = np.asarray(ref.expert_layer_sum(x, lp, uncut_cfg)) + once
    assert np.abs(once).max() > 0.05
    parts = []
    for first in range(0, e, held):
        sl = slice(first, first + held)
        mine = dict(lp, we_gate=wg[sl], we_up=wu[sl], we_down=wd[sl])
        cfg = dataclasses.replace(uncut_cfg, experts_held=held,
                                  first_expert=first)
        # the program's layer on this member: its part + the shared expert
        layer = _ffn_block(devices, cfg, x, mine)
        part = _layer(devices, "sort", x, router, bias, wg[sl], wu[sl],
                      wd[sl], experts_held=held, first_expert=first,
                      routed_scale=2.448)
        np.testing.assert_allclose(layer - part, once, atol=PATH_TOL)
        share = np.asarray(ref.expert_layer_sum(x, mine, cfg))
        np.testing.assert_allclose(part, share, atol=PATH_TOL)
        # sort = dense on the held share
        dense = _layer(devices, "dense", x, router, bias, wg[sl], wu[sl],
                       wd[sl], experts_held=held, first_expert=first,
                       routed_scale=2.448)
        np.testing.assert_allclose(part, dense, atol=PATH_TOL)
        parts.append(part)
    assert min(np.abs(p).max() for p in parts) > 0.05  # every share works
    np.testing.assert_allclose(sum(parts) + once, uncut, atol=2 * PATH_TOL)
    # the program's own uncut layer: all 16 held, the shared expert beside
    whole = _ffn_block(devices, uncut_cfg, x, lp)
    np.testing.assert_allclose(whole, uncut, atol=2 * PATH_TOL)
    assert obs.gauge("ep_experts_held").get(what="moe_layer") == held


# -- the scopes per kind in the compiled programs -----------------------------

AFMOE_SCOPES = tuple(
    f"attn.{part}.{kind}" for kind in ("full", "window")
    for part in ("qkv", "kv_write", "core", "gate", "out")) + (
    "embed", "ffn.dense", "ffn.post_norm", "moe.router", "moe.route",
    "moe.dispatch", "moe.experts", "moe.combine", "moe.shared", "head")


@pytest.fixture(scope="module")
def program_text(model):
    cfg, params, srv, placed = model
    return {name: low.compile().as_text()
            for name, low in _lowered_programs(srv, placed).items()}


@pytest.mark.parametrize("scope", AFMOE_SCOPES)
@pytest.mark.parametrize("program", ("decode", "prefill"))
def test_programs_carry_their_scopes(program_text, program, scope):
    assert f"/{scope}/" in program_text[program], (
        f"{scope} is in no op_name of the compiled {program} program")
