"""The serving stack's description with LAYER KINDS (the MiMo-V2-Flash
block: window and full gqa layers with their own KV heads, thetas and cache
groups, keys wider than values, a partial rotary factor, scaled values, a
sink in the window softmax, a leading dense layer, a held share of
sigmoid-bias experts, a sliced vocabulary) against the plain reference
(``models/reference_hybrid_moe.py``), at a tiny size on the CPU: pattern
``[0,1,1,1,1,0,1]``, 8 query heads over 2 (full) / 4 (window) KV heads,
keys 12 wide and values 8, 4 of 12 numbers rotated, window 8 in a ring of
16, 16 experts top-4 of which 4 are held, bfloat16 weights.

Tolerances. Program and reference hold the SAME bfloat16-valued weights
(upcast alike) and compute in float32 under ``highest``, so they differ by
summation order only — grouped heads over a ring against a loop over heads
with a banded mask, a sorted dispatch over the held queues against a loop
over experts: logits of magnitude ~3 agree to ``LOGIT_TOL`` = 5e-5 (measured
3e-6 - 1e-5). Two program paths over the same rows (whole prompt against
chunks, a verify window against single steps, compact rungs against the
pool's, sort against dense) agree to ``PATH_TOL`` = 2e-5. What the
tolerance must catch is orders larger: a missing sink, the full layers'
theta in a window layer, a wrong KV-head grouping, an unscaled value, a
127-wide window or a bfloat16 product each move a logit by 1e-2 or more
(``test_what_the_tolerance_catches``). Served tokens against one-shot
``generate`` are compared exactly: the engine's oracle guarantee.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from uccl_tpu import obs
from uccl_tpu.ep import ops as ep_ops
from uccl_tpu.models import inference
from uccl_tpu.models import moe_inference as mi
from uccl_tpu.models import reference_hybrid_moe as ref
from uccl_tpu.models.inference import SlotKVCache, _forward_slots
from uccl_tpu.models.moe_inference import (
    MoEServeConfig, MoEServer, MoESlotCache, init_params,
)
from uccl_tpu.serving import MoEBackend, PrefixCache, ServingEngine

LOGIT_TOL = 5e-5
PATH_TOL = 2e-5
MAX_SEQ = 64
VOCAB = 48  # an eighth of a published 384: ids are drawn below it
PATTERN = ("full", "window", "window", "window", "window", "full", "window")

HYBRID = dict(
    vocab=VOCAB, dim=32, n_layers=7, n_heads=8, n_kv_heads=2, head_dim=12,
    v_head_dim=8, rope_theta=5e6, norm_eps=1e-5, moe_experts=16, moe_topk=4,
    moe_ffn=24, capacity_factor=4.0, layer_kinds=PATTERN, window=8,
    window_kv_heads=4, window_rope_theta=1e4, window_ring=16, rotary_dim=4,
    value_scale=0.707, sink=("window",), experts_held=4, first_expert=4,
    first_k_dense=1, dense_ffn=40, gate="sigmoid_bias",
    param_dtype="bfloat16",
)

# the published keys (the catalog row's ``config``), as ``from_hf`` reads them
PUBLISHED = dict(
    attention_value_scale=0.707, hidden_act="silu", hidden_size=4096,
    intermediate_size=16384, max_position_embeddings=262144,
    model_type="mimo_v2_flash", num_attention_heads=64, head_dim=192,
    num_hidden_layers=48, num_key_value_heads=4, layernorm_epsilon=1e-05,
    rope_theta=5000000, tie_word_embeddings=False, vocab_size=152576,
    partial_rotary_factor=0.334, sliding_window=128, swa_rope_theta=10000,
    attention_bias=False, v_head_dim=128,
    hybrid_layer_pattern=[0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0],
    add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
    sliding_window_size=128, attention_chunk_size=128,
    moe_layer_freq=[0] + [1] * 47, moe_intermediate_size=2048,
    n_routed_experts=256, n_shared_experts=None, num_experts_per_tok=8,
    norm_topk_prob=True, scoring_func="sigmoid", n_group=1, topk_group=1,
    topk_method="noaux_tc", routed_scaling_factor=None,
    swa_num_attention_heads=64, swa_num_key_value_heads=8, swa_head_dim=192,
    swa_v_head_dim=128,
)


@pytest.fixture(scope="module")
def model(devices):
    cfg = MoEServeConfig(**HYBRID)
    params = init_params(jax.random.PRNGKey(5), cfg)
    srv = MoEServer(cfg, Mesh(np.array(devices[:1]), ("dp",)))
    return cfg, params, srv, srv.shard_params(params)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def _slot_logits(srv, placed, tokens, cache, start, mask, impl="sort",
                 slots=None):
    """Logits [B, S, V] and the new cache of one masked slot forward —
    what prefill_slots / verify_slots reduce to tokens. One jitted function
    a (server, impl, compact or not): a step at a shape met before does not
    compile again."""
    cfg = srv.cfg

    def f(p, tok, kc, vc, ln, off, m, *idx):
        logits, out = _forward_slots(
            mi._strip_shard(p), tok[0],
            SlotKVCache(mi._member(kc), mi._member(vc), ln[0]),
            off[0], m[0], cfg, ffn=mi._moe_block(cfg, impl),
            slots=idx[0][0] if idx else None)
        return logits[None], mi._lead(out.k), mi._lead(out.v)

    extra = [] if slots is None else [jnp.asarray(slots, jnp.int32)[None]]
    fns = srv.__dict__.setdefault("_slot_logits_fns", {})
    key = (impl, len(extra))
    if key not in fns:
        fns[key] = jax.jit(shard_map(
            f, mesh=srv.mesh,
            in_specs=(srv._param_specs(placed),)
            + (P("dp"),) * (6 + len(extra)),
            out_specs=(P("dp"),) * 3, check_vma=False))
    fn = fns[key]
    logits, nk, nv = fn(placed, jnp.asarray(tokens)[None], cache.k, cache.v,
                        cache.lengths, jnp.asarray(start, jnp.int32)[None],
                        jnp.asarray(mask)[None], *extra)
    return np.asarray(logits)[0], MoESlotCache(nk, nv, cache.lengths)


def _pool_rows(cache, slot):
    """Host copies of one slot's rows in every group of a pool."""
    return [np.asarray(a)[0, :, slot]
            for a in jax.tree.leaves((cache.k, cache.v))]


# -- the description, the tree and the pool ----------------------------------

def test_description_tree_and_pool(model):
    cfg, params, srv, placed = model
    assert cfg.param_groups() == [
        ("dense_blocks", 0), ("window_blocks", 0), ("window_blocks", 1),
        ("window_blocks", 2), ("window_blocks", 3), ("blocks", 0),
        ("window_blocks", 4)]
    assert inference.cache_groups(cfg) == [
        ("full", 0), ("window", 0), ("window", 1), ("window", 2),
        ("window", 3), ("full", 1), ("window", 4)]
    assert set(params) == {"embed", "dense_blocks", "blocks",
                           "window_blocks", "final_norm", "head"}
    full, win, dense = (params[g] for g in ("blocks", "window_blocks",
                                             "dense_blocks"))
    assert "router" not in dense and "sink" not in dense
    assert dense["wk"].shape == (1, 32, 2 * 12)  # layer 0 is a full layer
    assert full["wq"].shape == (1, 32, 8 * 12)
    assert full["wk"].shape == (1, 32, 2 * 12)
    assert full["wv"].shape == (1, 32, 2 * 8)
    assert win["wk"].shape == (5, 32, 4 * 12)
    assert win["wv"].shape == (5, 32, 4 * 8)
    assert win["wo"].shape == (5, 8 * 8, 32)
    # the router scores all 16; the 4 held experts' leaves are here
    assert win["router"].shape == (5, 32, 16)
    assert win["router_bias"].shape == (5, 16)
    assert win["we_gate"].shape == (5, 4, 32, 24)
    assert win["we_gate"].dtype == jnp.bfloat16
    # a sink per query head in the window layers, float32, drawn at scale 1
    assert "sink" not in full and win["sink"].shape == (5, 8)
    assert win["sink"].dtype == jnp.float32
    assert 0.5 < float(np.std(np.asarray(win["sink"]))) < 1.5
    assert params["embed"].shape == (VOCAB, 32)
    cache = srv.slot_cache(2, MAX_SEQ)
    assert set(cache.k) == set(cache.v) == {"full", "window"}
    assert cache.k["full"].shape == (1, 2, 2, MAX_SEQ, 2 * 12)  # flat rows
    assert cache.v["full"].shape == (1, 2, 2, MAX_SEQ, 2 * 8)
    assert cache.k["window"].shape == (1, 5, 2, 16, 4 * 12)
    assert cache.v["window"].shape == (1, 5, 2, 16, 4 * 8)
    row = obs.gauge("serving_kv_row_bytes")
    assert row.get(kind="full") == 2 * (12 + 8) * 4
    assert row.get(kind="window") == 4 * (12 + 8) * 4
    pool = obs.gauge("serving_kv_pool_bytes")
    assert pool.get(group="full") == 2 * 2 * MAX_SEQ * 2 * 20 * 4
    assert pool.get(group="window") == 5 * 2 * 16 * 4 * 20 * 4
    # the one-shot cache keeps every position of a window layer too
    flat = mi.MoEKVCache.empty(cfg, 1, 1, MAX_SEQ)
    assert flat.k["window"].shape == (1, 5, 1, MAX_SEQ, 4 * 12)


def test_from_hf_reads_the_published_keys():
    cfg = MoEServeConfig.from_hf(PUBLISHED, param_dtype="bfloat16")
    assert (cfg.attn, cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
            cfg.window_kv_heads, cfg.head_dim, cfg.v_head_dim) == (
        "gqa", 48, 4096, 64, 4, 8, 192, 128)
    assert cfg.layer_kinds[:7] == PATTERN and len(cfg.layer_kinds) == 48
    assert cfg.layer_kinds.count("full") == 9
    assert (cfg.window, cfg.ring, cfg.rotary_dim, cfg.rope_theta,
            cfg.window_rope_theta, cfg.value_scale, cfg.sink,
            cfg.norm_eps) == (128, 256, 64, 5e6, 1e4, 0.707, ("window",),
                              1e-5)
    assert (cfg.moe_experts, cfg.n_held, cfg.experts_held, cfg.moe_topk,
            cfg.moe_ffn, cfg.first_k_dense, cfg.dense_ffn, cfg.gate,
            cfg.routed_scale, cfg.shared_ffn) == (
        256, 256, 0, 8, 2048, 1, 16384, "sigmoid_bias", 1.0, 0)
    # a member's share: the depth cut reads the lists' first entries, the
    # file's expert count is what is held, the router keeps its width
    cut = MoEServeConfig.from_hf(dict(
        PUBLISHED, num_hidden_layers=7, n_routed_experts=16,
        router_experts=256, vocab_size=19072))
    assert cut.layer_kinds == PATTERN and cut.first_k_dense == 1
    assert (cut.moe_experts, cut.experts_held, cut.first_expert,
            cut.vocab) == (256, 16, 0, 19072)
    with pytest.raises(ValueError, match="group-limited"):
        MoEServeConfig.from_hf(dict(PUBLISHED, n_group=8))
    with pytest.raises(ValueError, match="fewer than"):
        MoEServeConfig.from_hf(dict(PUBLISHED, hybrid_layer_pattern=[0, 1]))
    with pytest.raises(ValueError, match="own query heads"):
        MoEServeConfig.from_hf(dict(PUBLISHED, swa_head_dim=128))
    with pytest.raises(ValueError, match="after the first expert layer"):
        MoEServeConfig.from_hf(dict(PUBLISHED,
                                    moe_layer_freq=[0, 1, 0] + [1] * 45))
    with pytest.raises(ValueError, match="belong to layer_kinds"):
        MoEServeConfig(window=8)
    with pytest.raises(ValueError, match="for each of the"):
        MoEServeConfig(n_layers=2, layer_kinds=("full",))
    # the ring's floor is a window's rows (window - 1 + the widest write is
    # asked where the write is known: tests/test_afmoe_serving.py)
    MoEServeConfig(**dict(HYBRID, window_ring=12))
    with pytest.raises(ValueError, match="must hold a window's rows"):
        MoEServeConfig(**dict(HYBRID, window_ring=7))
    with pytest.raises(ValueError, match="not among"):
        MoEServeConfig(**dict(HYBRID, first_expert=14))


def test_a_held_share_is_one_members(devices):
    cfg = MoEServeConfig(**HYBRID)
    with pytest.raises(ValueError, match="ONE member's share"):
        MoEServer(cfg, Mesh(np.array(devices[:2]), ("dp",)))


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
    the window layers' ring of 16 has wrapped three times."""
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
    """Prompts of 19 and 30 in chunks of 8, the last right-padded with
    token 0 (a slot's rows past its prompt are dead, in a ring as in a flat
    pool); then decoding continues as the reference's."""
    cfg, params, srv, placed = model
    a, b = _tokens(19 + 6, seed=2), _tokens(30 + 6, seed=3)
    want = [np.asarray(ref.forward_logits(params, t, cfg)) for t in (a, b)]
    lens = (19, 30)
    padded = np.zeros((2, 32), np.int32)
    padded[0, :19], padded[1, :30] = a[:19], b[:30]
    cache = srv.slot_cache(2, MAX_SEQ)
    on = np.ones(2, bool)
    parts = []
    for lo in range(0, 32, 8):
        live = np.array([lo < n for n in lens])
        part, cache = _slot_logits(srv, placed, padded[:, lo:lo + 8], cache,
                                   [lo, lo], live)
        parts.append(part)
    got = np.concatenate(parts, axis=1)
    for r, n in enumerate(lens):
        np.testing.assert_allclose(got[r, :n], want[r][:n], atol=LOGIT_TOL)
    # decode from each prompt's own end: the padded rows are overwritten
    # before any query reaches them
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
    for lo in range(0, 20, 4):  # both slots prefilled to 20
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
    # rows 22..24 were rejected: the cursor goes to 22, nothing is scrubbed,
    # and other tokens now stand at those positions
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
    prompts = [_tokens(16, seed=7 + i) for i in range(3)]
    three = np.stack(prompts)
    cache = srv.slot_cache(3, MAX_SEQ)
    on = np.ones(3, bool)
    wide = cache
    logits_wide = []
    for lo in (0, 8):
        part, wide = _slot_logits(srv, placed, three[:, lo:lo + 8], wide,
                                  [lo] * 3, on)
        logits_wide.append(part)
    logits_wide = np.concatenate(logits_wide, axis=1)
    compact = srv.slot_cache(3, MAX_SEQ)
    got = {}
    for slots in ([2], [0, 1]):  # a one-row rung, then a two-row rung
        parts = []
        for lo in (0, 8):
            untouched = [s for s in range(3) if s not in slots]
            before = [_pool_rows(compact, s) for s in untouched]
            part, compact = _slot_logits(
                srv, placed, three[slots, lo:lo + 8], compact,
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
    # a padding row (an index past the pool) writes nothing
    before = [_pool_rows(compact, s) for s in range(3)]
    _, padded = _slot_logits(srv, placed, three[:1, :8], compact, [0],
                             np.zeros(1, bool), slots=[3])
    for s in range(3):
        for a, b in zip(_pool_rows(padded, s), before[s]):
            assert np.array_equal(a, b)


def test_row_at_a_time_attention_is_all_rows_at_once(model, monkeypatch):
    """The pool-wide rung over a deep pool attends one batch row at a time
    (``_SCORES_AT_ONCE``); the same numbers as all rows together."""
    cfg, params, srv, placed = model
    both = np.stack([_tokens(8, seed=11), _tokens(8, seed=12)])
    cache = srv.slot_cache(2, MAX_SEQ)
    on = np.ones(2, bool)
    at_once, _ = _slot_logits(srv, placed, both, cache, [0, 0], on)
    monkeypatch.setattr(inference, "_SCORES_AT_ONCE", 1)
    srv._slot_logits_fns.clear()  # trace again, under the new threshold
    by_row, _ = _slot_logits(srv, placed, both, srv.slot_cache(2, MAX_SEQ),
                             [0, 0], on)
    np.testing.assert_allclose(by_row, at_once, atol=PATH_TOL)
    srv._slot_logits_fns.clear()


def test_engine_served_tokens_are_generates(model):
    cfg, params, srv, placed = model
    backend = MoEBackend(srv, placed, batch_local=2, max_seq=MAX_SEQ,
                         decode_impl="sort")
    in_place = obs.counter("serving_pool_in_place_total")
    before = {k: in_place.get(program=k) for k in ("prefill", "decode")}
    calls, launch = {"prefill": 0, "decode": 0}, backend._launch

    def counted(kind, *a, **kw):
        calls[kind] += 1
        return launch(kind, *a, **kw)

    backend._launch = counted
    eng = ServingEngine(backend, prefill_chunk=4)
    reqs = [eng.submit(_tokens(n, seed=20 + n), max_new_tokens=m)
            for n, m in ((5, 24), (23, 20), (11, 30))]
    eng.drain()
    for r in reqs:
        want = srv.generate(placed, jnp.asarray(r.prompt)[None, None],
                            r.max_new_tokens, MAX_SEQ, impl="sort")
        assert r.out_tokens == np.asarray(want)[0, 0].tolist(), r.rid
    assert eng.pool.leaked() == 0
    # every call consumed the pool it was handed: ALL its groups donated
    assert calls["prefill"] and calls["decode"]
    for k, n in before.items():
        assert in_place.get(program=k) - n == calls[k], k
    assert not any(a.is_deleted() for a in jax.tree.leaves(
        (backend.cache.k, backend.cache.v)))


def test_engine_with_a_verify_window(model):
    """Speculative decoding over the ring: draft windows of 3, rejected
    rows and all, serve ``generate``'s tokens."""
    cfg, params, srv, placed = model
    backend = MoEBackend(srv, placed, batch_local=2, max_seq=MAX_SEQ,
                         decode_impl="sort")
    eng = ServingEngine(backend, prefill_chunk=4, spec_k=2)
    reqs = [eng.submit(_tokens(n, seed=40 + n), max_new_tokens=26)
            for n in (9, 17)]
    eng.drain()
    for r in reqs:
        want = srv.generate(placed, jnp.asarray(r.prompt)[None, None],
                            r.max_new_tokens, MAX_SEQ, impl="sort")
        assert r.out_tokens == np.asarray(want)[0, 0].tolist(), r.rid


# -- what the tolerance catches ----------------------------------------------

def _program_logits(devices, cfg, params, toks):
    srv = MoEServer(cfg, Mesh(np.array(devices[:1]), ("dp",)))
    cache = mi.MoEKVCache.empty(cfg, 1, 1, MAX_SEQ)
    got, _ = srv._forward(srv.shard_params(params),
                          jnp.asarray(toks)[None, None], cache, "sort")
    return np.asarray(got)[0, 0]


@pytest.mark.parametrize("fault", [
    "no_sink", "zero_sink", "full_theta_in_window", "window_127",
    "unscaled_value", "kv_grouping", "all_rotated", "bf16_product"])
def test_what_the_tolerance_catches(model, devices, fault, monkeypatch):
    """Each way the program could be this model almost: the reference moves
    away by far more than LOGIT_TOL."""
    cfg, params, srv, placed = model
    toks = _tokens(29)
    want = np.asarray(ref.forward_logits(params, toks, cfg))
    wrong_cfg, wrong_params = cfg, params
    if fault == "no_sink":
        wrong_cfg = dataclasses.replace(cfg, sink=())
    elif fault == "zero_sink":  # a sink column of logit 0 is not this one
        wrong_params = dict(params, window_blocks=dict(
            params["window_blocks"],
            sink=jnp.zeros_like(params["window_blocks"]["sink"])))
    elif fault == "full_theta_in_window":
        wrong_cfg = dataclasses.replace(cfg, window_rope_theta=cfg.rope_theta)
    elif fault == "window_127":
        wrong_cfg = dataclasses.replace(cfg, window=cfg.window - 1)
    elif fault == "unscaled_value":
        wrong_cfg = dataclasses.replace(cfg, value_scale=1.0)
    elif fault == "all_rotated":
        wrong_cfg = dataclasses.replace(cfg, rotary_dim=0)
    elif fault == "kv_grouping":
        # query head j on KV head j % Hkv instead of j // (H / Hkv): the
        # query heads (and their rows of wo, and their sinks) re-ordered so
        # that the program's grouping reads the other KV head
        def regroup(group, hkv):
            order = np.arange(8).reshape(8 // hkv, hkv).T.reshape(-1)
            n = group["wq"].shape[0]
            out = dict(group)
            out["wq"] = group["wq"].reshape(n, 32, 8, 12)[:, :, order] \
                .reshape(n, 32, 96)
            out["wo"] = group["wo"].reshape(n, 8, 8, 32)[:, order] \
                .reshape(n, 64, 32)
            if "sink" in group:
                out["sink"] = group["sink"][:, order]
            return out

        wrong_params = dict(
            params, dense_blocks=regroup(params["dense_blocks"], 2),
            blocks=regroup(params["blocks"], 2),
            window_blocks=regroup(params["window_blocks"], 4))
    elif fault == "bf16_product":
        # the CPU computes every product in float32 whatever it is asked:
        # round the projections' activation operand as a bfloat16 product
        # would (the weights are bfloat16-valued already)
        real = inference.rms_norm
        monkeypatch.setattr(
            inference, "rms_norm", lambda *a, **kw: real(*a, **kw).astype(
                jnp.bfloat16).astype(jnp.float32))
    got = _program_logits(devices, wrong_cfg, wrong_params, toks)
    assert np.abs(got - want).max() > 100 * LOGIT_TOL, fault


# -- the held share ----------------------------------------------------------

def _layer(devices, impl, x, router, bias, wg, wu, wd, **kw):
    mesh = Mesh(np.array(devices[:1]), ("dp",))

    def f_(x, wg, wu, wd):
        out, _, _ = ep_ops.moe_ffn(
            x[0], jnp.dot(x[0], router, precision="highest"), wg, wu, wd,
            "dp", num_selected=4, capacity_factor=4.0, impl=impl,
            gate="sigmoid_bias", gate_bias=bias, **kw)
        return out[None]

    return np.asarray(jax.jit(shard_map(
        f_, mesh=mesh, in_specs=(P("dp"),) * 4, out_specs=P("dp"),
        check_vma=False))(x[None], wg, wu, wd))[0]


def test_the_shares_of_all_holders_add_up_to_the_uncut_layer(devices):
    """THE test that ties the share to the model: 16 experts held four at a
    time by four members; each member's ``moe_ffn`` gives its part, and the
    four parts add up to the uncut reference's expert layer (and to the
    program's own uncut layer)."""
    rng = np.random.default_rng(3)
    t, h, f, e, held = 24, 16, 24, 16, 4
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(h, e)) / 4, jnp.float32)
    bias = jnp.asarray(rng.normal(size=e) * 0.3, jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(e, h, f)) / 4, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e, f, h)) / 5, jnp.float32)
    cfg = MoEServeConfig(moe_experts=e, moe_topk=4, gate="sigmoid_bias")
    lp = dict(router=router, router_bias=bias, we_gate=wg, we_up=wu,
              we_down=wd)
    uncut = np.asarray(ref.expert_layer_sum(x, lp, cfg))
    parts = []
    for first in range(0, e, held):
        sl = slice(first, first + held)
        part = _layer(devices, "sort", x, router, bias, wg[sl], wu[sl],
                      wd[sl], experts_held=held, first_expert=first)
        # the reference given the same share gives the same part
        share = np.asarray(ref.expert_layer_sum(
            x, dict(lp, we_gate=wg[sl], we_up=wu[sl], we_down=wd[sl]), cfg,
            first=first, held=held))
        np.testing.assert_allclose(part, share, atol=PATH_TOL)
        # sort = dense on a held share
        dense = _layer(devices, "dense", x, router, bias, wg[sl], wu[sl],
                       wd[sl], experts_held=held, first_expert=first)
        np.testing.assert_allclose(part, dense, atol=PATH_TOL)
        parts.append(part)
    assert min(np.abs(p).max() for p in parts) > 0.05  # every share works
    np.testing.assert_allclose(sum(parts), uncut, atol=PATH_TOL)
    whole = _layer(devices, "sort", x, router, bias, wg, wu, wd)
    np.testing.assert_allclose(sum(parts), whole, atol=PATH_TOL)
    # queues for the held experts only, at the drop-free capacity
    assert obs.gauge("ep_experts_held").get(what="moe_layer") == held
    assert obs.gauge("ep_expert_capacity").get(what="moe_layer") == t
    with pytest.raises(ValueError, match="held share"):
        _layer(devices, "ll", x, router, bias, wg[:4], wu[:4], wd[:4],
               experts_held=held, first_expert=0)


# -- what a pool with ring groups cannot do yet (window rings here; conv
# rings: tests/test_lfm2_serving.py) -----------------------------

@pytest.mark.parametrize("what", ["prefix_cache", "kv_tiers", "export_rows",
                                  "import_rows", "copy_prefix", "disagg",
                                  "whole_prompt", "narrow_ring", "lora"])
def test_window_groups_refuse_what_they_cannot_do(model, what):
    from uccl_tpu.serving.disagg import wire_format_for
    from uccl_tpu.serving.kv_tiers import TieredKVCache

    cfg, params, srv, placed = model
    backend = MoEBackend(srv, placed, batch_local=2, max_seq=MAX_SEQ,
                         decode_impl="sort")
    rows = np.zeros((7, 4, 24), np.float32)
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
    }
    if what in calls:
        with pytest.raises(ValueError, match="ring groups"):
            calls[what]()
    elif what == "whole_prompt":
        with pytest.raises(ValueError, match="requires prefill_chunk"):
            ServingEngine(backend)
    elif what == "narrow_ring":
        # window 8 in a ring of 16 takes writes up to 9 wide
        ServingEngine(backend, prefill_chunk=9)
        with pytest.raises(ValueError, match="widest write"):
            ServingEngine(backend, prefill_chunk=10)
        with pytest.raises(ValueError, match="widest write"):
            ServingEngine(backend, prefill_chunk=4, spec_k=9)
        with pytest.raises(ValueError, match="cannot take a write"):
            _slot_logits(srv, placed, np.zeros((2, 10), np.int32),
                         srv.slot_cache(2, MAX_SEQ), [0, 0],
                         np.ones(2, bool))
    else:
        with pytest.raises(ValueError, match="LoRA"):
            inference._grouped_attention(
                None, None, None, None, None, None, None, cfg,
                lora=lambda h, t: h, kind="window")


# -- the scopes per kind in the compiled programs -----------------------------

HYBRID_SCOPES = tuple(
    f"attn.{part}.{kind}" for kind in ("full", "window")
    for part in ("qkv", "kv_write", "core", "out")) + (
    "embed", "ffn.dense", "moe.router", "moe.route", "moe.dispatch",
    "moe.experts", "moe.combine", "head")


def _lowered_programs(srv, placed, chunk=4):
    """The server's own decode and pool-wide prefill programs over a pool of
    two slots, lowered: ``{"decode" | "prefill": jax.stages.Lowered}``."""
    cache = srv.slot_cache(2, MAX_SEQ)

    def decode(p, tok, act, k, v, ln):
        return srv.decode_step_slots(p, tok, act, MoESlotCache(k, v, ln),
                                     impl="sort")

    def prefill(p, tok, lens, mask, k, v, ln):
        return srv.prefill_slots(p, tok, lens, mask, MoESlotCache(k, v, ln))

    act = jnp.ones((1, 2), bool)
    return {
        "decode": jax.jit(decode).lower(
            placed, jnp.ones((1, 2), jnp.int32), act, *cache),
        "prefill": jax.jit(prefill).lower(
            placed, jnp.ones((1, 2, chunk), jnp.int32),
            jnp.full((1, 2), chunk, jnp.int32), act, *cache),
    }


@pytest.fixture(scope="module")
def hybrid_program_text(model):
    cfg, params, srv, placed = model
    return {name: low.compile().as_text()
            for name, low in _lowered_programs(srv, placed).items()}


@pytest.mark.parametrize("scope", HYBRID_SCOPES)
@pytest.mark.parametrize("program", ("decode", "prefill"))
def test_hybrid_programs_carry_their_scopes(hybrid_program_text, program,
                                            scope):
    assert f"/{scope}/" in hybrid_program_text[program], (
        f"{scope} is in no op_name of the compiled {program} program")


@pytest.mark.parametrize("scope", ("attn.gate", "ffn.post_norm",
                                   "moe.shared"))
@pytest.mark.parametrize("program", ("decode", "prefill"))
def test_hybrid_programs_carry_nothing_of_another_block(
        hybrid_program_text, program, scope):
    """What another description's layers carry (a gated output, a closing
    norm, a shared expert: tests/test_afmoe_serving.py) is read from a
    layer's own leaves and costs this description no operation."""
    assert f"/{scope}" not in hybrid_program_text[program]
