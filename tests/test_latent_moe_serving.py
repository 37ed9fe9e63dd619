"""The serving stack's latent-attention / dense-then-MoE description (the
GLM-4.7-Flash block: MLA over one 576-wide cache row, sigmoid-bias gate,
shared expert, a leading dense layer, bfloat16 weights) against the plain
reference (``models/reference_latent_moe.py``), at a tiny size with odd head
sizes on the CPU.

Tolerances. Program and reference hold the SAME bfloat16-valued weights
(upcast alike) and compute in float32 under ``highest``, so they differ by
summation order only — absorbed against expanded attention, a sorted
dispatch against a loop over experts: logits of magnitude ~3 agree to
``LOGIT_TOL`` = 5e-5 (measured 3e-6 - 8e-6). Two program paths over the
same rows (whole prompt against chunks, a verify window against single
steps, sort against dense against ll) are the same float32 operations per
row in another batching and agree to ``PATH_TOL`` = 2e-5. Served tokens
against one-shot ``generate`` are compared exactly: the engine's oracle
guarantee.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from uccl_tpu import obs
from uccl_tpu.ep import ops as ep_ops
from uccl_tpu.models import moe_inference as mi
from uccl_tpu.models import reference_latent_moe as ref
from uccl_tpu.models.inference import SlotKVCache, _forward_slots
from uccl_tpu.models.moe_inference import (
    MoEServeConfig, MoEServer, MoESlotCache, init_params,
)
from uccl_tpu.serving import MoEBackend, PrefixCache, ServingEngine

LOGIT_TOL = 5e-5
PATH_TOL = 2e-5
MAX_SEQ = 32
VOCAB = 61

LATENT = dict(
    vocab=VOCAB, dim=40, n_layers=2, n_heads=3, rope_theta=1e4,
    norm_eps=1e-5, moe_experts=8, moe_topk=2, moe_ffn=24,
    capacity_factor=4.0, attn="mla", q_lora_rank=16, kv_lora_rank=8,
    qk_nope_dim=5, qk_rope_dim=6, v_head_dim=7, n_kv_heads=3, head_dim=11,
    first_k_dense=1, dense_ffn=36, shared_ffn=24, gate="sigmoid_bias",
    routed_scale=1.8, param_dtype="bfloat16",
)
GQA = dict(vocab=VOCAB, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
           head_dim=8, moe_experts=8, moe_topk=2, moe_ffn=32)


@pytest.fixture(scope="module")
def model(devices):
    cfg = MoEServeConfig(**LATENT)
    params = init_params(jax.random.PRNGKey(5), cfg)
    srv = MoEServer(cfg, Mesh(np.array(devices[:1]), ("dp",)))
    return cfg, params, srv, srv.shard_params(params)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def _slot_logits(srv, placed, tokens, cache, start, mask, impl="sort"):
    """Logits [B, S, V] and the new cache of one masked slot forward —
    what prefill_slots / verify_slots reduce to tokens."""
    cfg = srv.cfg

    def f(p, tok, kc, vc, ln, off, m):
        logits, out = _forward_slots(
            mi._strip_shard(p), tok[0], SlotKVCache(kc[0], vc[0], ln[0]),
            off[0], m[0], cfg, ffn=mi._moe_block(cfg, impl))
        return logits[None], out.k[None], out.v[None]

    fn = jax.jit(shard_map(
        f, mesh=srv.mesh,
        in_specs=(srv._param_specs(placed),) + (P("dp"),) * 6,
        out_specs=(P("dp"),) * 3, check_vma=False))
    logits, nk, nv = fn(placed, jnp.asarray(tokens)[None], cache.k, cache.v,
                        cache.lengths, jnp.asarray(start, jnp.int32)[None],
                        jnp.asarray(mask)[None])
    return np.asarray(logits)[0], MoESlotCache(nk, nv, cache.lengths)


def test_description_and_tree(model):
    cfg, params, srv, placed = model
    assert cfg.n_moe_layers == 1
    assert set(params) == {"embed", "blocks", "dense_blocks", "final_norm",
                           "head"}
    assert "router" not in params["dense_blocks"]
    assert params["dense_blocks"]["w_gate"].shape == (1, 40, 36)
    assert params["blocks"]["wkv_a"].shape == (1, 40, 8 + 6)
    assert params["blocks"]["wkv_b"].shape == (1, 8, 3 * (5 + 7))
    assert params["blocks"]["we_gate"].dtype == jnp.bfloat16
    assert params["blocks"]["ln1"].dtype == jnp.float32
    assert params["blocks"]["router_bias"].dtype == jnp.float32
    cache = srv.slot_cache(2, MAX_SEQ)
    # one latent row: the compressed part in k, the shared rotary key in v
    assert cache.k.shape == (1, 2, 2, MAX_SEQ, 8)
    assert cache.v.shape == (1, 2, 2, MAX_SEQ, 6)
    assert obs.gauge("serving_kv_row_bytes").get(kind="mla") == (8 + 6) * 4
    # Mixtral stays a value of the same description
    uniform = MoEServeConfig(**GQA)
    assert (uniform.attn, uniform.gate, uniform.first_k_dense,
            uniform.shared_ffn, uniform.param_dtype) == (
        "gqa", "softmax", 0, 0, "float32")
    tree = init_params(jax.random.PRNGKey(0), uniform)
    assert set(tree["blocks"]) == {"ln1", "ln2", "wq", "wk", "wv", "wo",
                                   "router", "we_gate", "we_up", "we_down"}


def test_from_hf_reads_both_families():
    glm = MoEServeConfig.from_hf(dict(
        vocab_size=99, hidden_size=32, num_hidden_layers=3,
        num_attention_heads=2, num_experts_per_tok=2, kv_lora_rank=8,
        q_lora_rank=12, qk_nope_head_dim=6, qk_rope_head_dim=2,
        v_head_dim=10, n_routed_experts=8, moe_intermediate_size=16,
        first_k_dense_replace=1, intermediate_size=48, n_shared_experts=1,
        routed_scaling_factor=1.8, rope_theta=1000000, rms_norm_eps=1e-5,
        n_group=1, topk_group=1, norm_topk_prob=True, rope_scaling=None),
        capacity_factor=4.0, param_dtype="bfloat16")
    assert (glm.attn, glm.gate, glm.first_k_dense, glm.dense_ffn,
            glm.shared_ffn, glm.moe_ffn, glm.routed_scale, glm.rope_theta) \
        == ("mla", "sigmoid_bias", 1, 48, 16, 16, 1.8, 1e6)
    mixtral = MoEServeConfig.from_hf(dict(
        vocab_size=99, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=4, num_key_value_heads=2, num_local_experts=8,
        num_experts_per_tok=2, intermediate_size=64, rope_theta=1e6,
        rms_norm_eps=1e-5))
    assert (mixtral.attn, mixtral.head_dim, mixtral.moe_ffn) == ("gqa", 8, 64)
    with pytest.raises(ValueError, match="group-limited"):
        MoEServeConfig.from_hf(dict(
            vocab_size=9, hidden_size=8, num_hidden_layers=2,
            num_attention_heads=1, num_experts_per_tok=1, kv_lora_rank=2,
            n_group=2))
    with pytest.raises(ValueError, match="five widths"):
        MoEServeConfig(attn="mla")
    with pytest.raises(ValueError, match="gate"):
        MoEServeConfig(gate="tanh")


def test_full_forward_is_the_reference(model):
    cfg, params, srv, placed = model
    toks = _tokens(13)
    want = np.asarray(ref.forward_logits(params, toks, cfg))
    cache = mi.MoEKVCache.empty(cfg, 1, 1, MAX_SEQ)
    got, _ = srv._forward(placed, jnp.asarray(toks)[None, None], cache,
                          "sort")
    assert np.abs(want).max() > 1.0  # the tolerance is against real logits
    np.testing.assert_allclose(np.asarray(got)[0, 0], want, atol=LOGIT_TOL)


def test_prefill_then_cached_decode_is_the_reference(model):
    cfg, params, srv, placed = model
    toks = _tokens(12, seed=1)
    want = np.asarray(ref.forward_logits(params, toks, cfg))
    logits, cache = srv.prefill(placed, jnp.asarray(toks[:8])[None, None],
                                MAX_SEQ)
    np.testing.assert_allclose(np.asarray(logits)[0, 0], want[7],
                               atol=LOGIT_TOL)
    for i in range(8, 12):  # one token at a time through the latent cache
        logits, cache = srv.decode_step(
            placed, jnp.asarray(toks[i:i + 1])[None], cache, impl="sort")
        np.testing.assert_allclose(np.asarray(logits)[0, 0], want[i],
                                   atol=LOGIT_TOL)


def test_chunked_prefill_window_and_single_steps_agree(model):
    """Whole-prompt prefill = chunked prefill; the absorbed one-token decode
    step = the verify window over the same tokens = the reference."""
    cfg, params, srv, placed = model
    a, b = _tokens(11, seed=2), _tokens(9, seed=3)
    want = [np.asarray(ref.forward_logits(params, t, cfg)) for t in (a, b)]
    both = np.zeros((2, 12), np.int32)
    both[0, :11], both[1, :9] = a, b
    on = np.ones(2, bool)
    empty = srv.slot_cache(2, MAX_SEQ)
    whole, _ = _slot_logits(srv, placed, both, empty, [0, 0], on)
    np.testing.assert_allclose(whole[0, :11], want[0], atol=LOGIT_TOL)
    np.testing.assert_allclose(whole[1, :9], want[1], atol=LOGIT_TOL)
    # chunks of 4 through the cache; the rows past a prompt's end are dead
    cache, parts = empty, []
    for lo in range(0, 8, 4):
        part, cache = _slot_logits(srv, placed, both[:, lo:lo + 4], cache,
                                   [lo, lo], on)
        parts.append(part)
    np.testing.assert_allclose(np.concatenate(parts, axis=1), whole[:, :8],
                               atol=PATH_TOL)
    # from position 8 of slot 0: a 3-token window against three single steps
    lengths = jnp.asarray([[8, 8]], jnp.int32)
    base = MoESlotCache(cache.k, cache.v, lengths)
    window, _ = _slot_logits(srv, placed, both[:, 8:11], base, [8, 8],
                             [True, False])
    steps, c = [], base
    for i in range(8, 11):
        one, c = _slot_logits(srv, placed, both[:, i:i + 1], c, [i, i],
                              [True, False])
        steps.append(one)
    np.testing.assert_allclose(np.concatenate(steps, axis=1)[0], window[0],
                               atol=PATH_TOL)
    np.testing.assert_allclose(window[0], want[0][8:11], atol=LOGIT_TOL)
    # a masked slot's latent rows come back unchanged
    assert np.array_equal(np.asarray(c.k)[0, :, 1], np.asarray(base.k)[0, :, 1])
    assert np.array_equal(np.asarray(c.v)[0, :, 1], np.asarray(base.v)[0, :, 1])


def _numpy_gate(logits, bias, k, scale):
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    idx = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :k]
    vals = np.take_along_axis(s, idx, axis=-1)
    return scale * vals / (vals.sum(-1, keepdims=True) + 1e-20), idx


def test_sigmoid_bias_gate_alone():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(16, 8)).astype(np.float32)
    bias = (rng.normal(size=8) * 0.5).astype(np.float32)
    vals, idx, aux, z = ep_ops._gate_topk(
        jnp.asarray(logits), 3, True, "sigmoid_bias", jnp.asarray(bias), 1.8)
    want_vals, want_idx = _numpy_gate(logits, bias, 3, 1.8)
    assert np.array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(vals), want_vals, rtol=1e-6)
    assert float(aux) == 0.0 and float(z) == 0.0
    # renormalised over the chosen, then scaled
    np.testing.assert_allclose(np.asarray(vals).sum(-1), 1.8, rtol=1e-6)
    # the bias changes the choice ...
    _, idx0, _, _ = ep_ops._gate_topk(jnp.asarray(logits), 3, True,
                                      "sigmoid_bias", None, 1.8)
    assert not np.array_equal(np.asarray(idx0), np.asarray(idx))
    # ... and not the weights: where a shifted bias leaves the choice as it
    # was, the weights are the same numbers
    vals2, idx2, _, _ = ep_ops._gate_topk(
        jnp.asarray(logits), 3, True, "sigmoid_bias",
        jnp.asarray(bias + 7.0), 1.8)
    assert np.array_equal(np.asarray(idx2), np.asarray(idx))
    assert np.array_equal(np.asarray(vals2), np.asarray(vals))
    # unnormalised and unscaled: the bare sigmoid scores of the chosen
    raw, _, _, _ = ep_ops._gate_topk(jnp.asarray(logits), 3, False,
                                     "sigmoid_bias", jnp.asarray(bias))
    s = 1.0 / (1.0 + np.exp(-logits))
    np.testing.assert_allclose(
        np.asarray(raw), np.take_along_axis(s, want_idx, -1), rtol=1e-6)
    # the softmax gate is what it was, and takes the scale too
    v1, i1, a1, z1 = ep_ops._gate_topk(jnp.asarray(logits), 2, True)
    v2, i2, _, _ = ep_ops._gate_topk(jnp.asarray(logits), 2, True,
                                     "softmax", None, 2.0)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v2), 2.0 * np.asarray(v1),
                               rtol=1e-6)
    assert float(a1) > 0.0 and float(z1) > 0.0
    with pytest.raises(ValueError, match="unknown gate"):
        ep_ops._gate_topk(jnp.asarray(logits), 2, True, "tanh")


@pytest.mark.parametrize("world", [1, 2])
def test_sort_dense_ll_agree_under_the_sigmoid_gate(devices, world):
    rng = np.random.default_rng(world)
    t, h, f, e = 12, 16, 24, 8
    x = jnp.asarray(rng.normal(size=(world, t, h)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(h, e)) / 4, jnp.float32)
    bias = jnp.asarray(rng.normal(size=e) * 0.3, jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(e, h, f)) / 4, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e, f, h)) / 5, jnp.float32)
    mesh = Mesh(np.array(devices[:world]), ("dp",))

    def layer(impl):
        def f_(x, wg, wu, wd):
            out, _, _ = ep_ops.moe_ffn(
                x[0], x[0] @ router, wg, wu, wd, "dp", num_selected=2,
                capacity_factor=4.0, impl=impl, gate="sigmoid_bias",
                gate_bias=bias, routed_scale=1.8)
            return out[None]

        return np.asarray(jax.jit(shard_map(
            f_, mesh=mesh, in_specs=(P("dp"),) * 4, out_specs=P("dp"),
            check_vma=False))(x, wg, wu, wd))

    sort, dense, ll = layer("sort"), layer("dense"), layer("ll")
    np.testing.assert_allclose(sort, dense, atol=PATH_TOL)
    np.testing.assert_allclose(sort, ll, atol=PATH_TOL)
    # and they are the gate's weighted sum of the chosen experts
    w, idx = _numpy_gate(np.asarray(x[0] @ router), np.asarray(bias), 2, 1.8)
    x0 = np.asarray(x[0], np.float64)
    want = np.zeros((t, h))
    for tok in range(t):
        for j in range(2):
            ex = idx[tok, j]
            g = x0[tok] @ np.asarray(wg[ex], np.float64)
            act = g / (1 + np.exp(-g)) * (x0[tok] @ np.asarray(wu[ex],
                                                              np.float64))
            want[tok] += w[tok, j] * (act @ np.asarray(wd[ex], np.float64))
    np.testing.assert_allclose(sort[0], want, atol=5e-5)


def test_shared_expert_is_added_once_whatever_the_world(devices):
    """The same weights served on a 1-shard and a 2-shard mesh generate the
    same tokens: the shared expert and the dense layer are per-token work,
    never summed over the EP world."""
    cfg = MoEServeConfig(**LATENT)
    params = init_params(jax.random.PRNGKey(5), cfg)
    prompt = jnp.asarray(_tokens(2 * 7, seed=9).reshape(2, 1, 7))
    outs = []
    for w in (1, 2):
        srv = MoEServer(cfg, Mesh(np.array(devices[:w]), ("dp",)))
        out = srv.generate(srv.shard_params(params),
                           prompt.reshape(w, 2 // w, 7), 4, MAX_SEQ,
                           impl="sort")
        outs.append(np.asarray(out).reshape(2, 4))
    assert np.array_equal(outs[0], outs[1])


def test_engine_served_tokens_are_generates(model):
    cfg, params, srv, placed = model
    backend = MoEBackend(srv, placed, batch_local=2, max_seq=MAX_SEQ,
                         decode_impl="sort")
    eng = ServingEngine(backend, prefill_chunk=4)
    reqs = [eng.submit(_tokens(n, seed=20 + n), max_new_tokens=5)
            for n in (5, 9, 11)]
    eng.drain()
    for r in reqs:
        want = srv.generate(placed, jnp.asarray(r.prompt)[None, None],
                            r.max_new_tokens, MAX_SEQ, impl="sort")
        assert r.out_tokens == np.asarray(want)[0, 0].tolist(), r.rid
    assert eng.pool.leaked() == 0


def test_latent_attention_takes_no_lora(model):
    cfg, params, srv, placed = model
    from uccl_tpu.models.inference import _mla_attention

    with pytest.raises(ValueError, match="LoRA"):
        _mla_attention(None, None, None, None, None, None, None, cfg,
                       lora=lambda h, t: h)


# -- where rows move: the row's shape is guarded for both attention kinds ----

def _backend(devices, kind):
    cfg = MoEServeConfig(**(LATENT if kind == "mla" else GQA))
    params = init_params(jax.random.PRNGKey(1), cfg)
    srv = MoEServer(cfg, Mesh(np.array(devices[:1]), ("dp",)))
    placed = srv.shard_params(params)
    return cfg, srv, placed, functools.partial(
        MoEBackend, srv, placed, batch_local=2, max_seq=MAX_SEQ,
        decode_impl="sort")


def _oracle(srv, placed, r):
    want = srv.generate(placed, jnp.asarray(r.prompt)[None, None],
                        r.max_new_tokens, MAX_SEQ, impl="sort")
    return np.asarray(want)[0, 0].tolist()


@pytest.mark.parametrize("kind", ["gqa", "mla"])
def test_prefix_cache_copy_keeps_the_row(devices, kind):
    cfg, srv, placed, make = _backend(devices, kind)
    eng = ServingEngine(make(), prefill_chunk=3, prefix_cache=PrefixCache(3))
    p0 = _tokens(8, seed=30)
    share = np.concatenate([p0[:6], _tokens(2, seed=31)])
    reqs = []
    for p in (p0, share):
        reqs.append(eng.submit(p, max_new_tokens=4))
        eng.drain()
    assert reqs[1].cache_hit_len == 6
    for r in reqs:
        assert r.out_tokens == _oracle(srv, placed, r), r.rid
    assert eng.pool.leaked() == 0


@pytest.mark.parametrize("kind", ["gqa", "mla"])
def test_tier_demote_promote_keeps_the_row(devices, kind):
    from uccl_tpu.serving.kv_tiers import TieredKVCache

    cfg, srv, placed, make = _backend(devices, kind)
    tiers = TieredKVCache(host_bytes=1 << 20)
    eng = ServingEngine(make(), prefill_chunk=3, prefix_cache=PrefixCache(3),
                        kv_tiers=tiers)
    before = obs.counter("kv_tier_promotions_total").get(tier="t1")
    bases = [_tokens(8, seed=40 + i) for i in range(3)]
    reqs = []
    for _ in range(2):
        for p in bases:
            reqs.append(eng.submit(p.copy(), max_new_tokens=4))
            eng.drain()
    assert obs.counter("kv_tier_promotions_total").get(tier="t1") > before
    for r in reqs:
        assert r.cache_hit_exact is True
        assert r.out_tokens == _oracle(srv, placed, r), r.rid
    assert eng.pool.leaked() == 0


@pytest.mark.parametrize("kind", ["gqa", "mla"])
def test_export_import_moves_the_row_opaquely(devices, kind):
    """The disaggregated surface: a prefilled slot's rows leave one pool as
    two equal arrays, cross the at-rest codec and the wire format's byte
    layout, enter another pool's slot, and decoding continues as the
    oracle's."""
    from uccl_tpu.models.inference import kv_wire_dims
    from uccl_tpu.serving.disagg import wire_format_for
    from uccl_tpu.serving.kv_tiers import decode_entry, encode_entry

    cfg, srv, placed, make = _backend(devices, kind)
    src, dst = make(), make()
    prompt = _tokens(9, seed=50)
    toks = np.zeros((2, 12), np.int32)
    toks[1, :9] = prompt
    first = src.prefill(toks, np.array([0, 9], np.int32),
                        np.array([False, True]))
    k_rows, v_rows = src.export_slot_kv(1, 0, 9)
    heads, width = kv_wire_dims(cfg)
    assert k_rows.shape == v_rows.shape == (cfg.n_layers, 9, heads, width)
    fmt = wire_format_for(src)
    assert (fmt.n_kv_heads, fmt.head_dim) == (heads, width)
    assert 2 * fmt.row_bytes == obs.gauge("serving_kv_row_bytes").get(
        kind=cfg.attn)
    blob, meta = encode_entry(k_rows, v_rows)
    k2, v2 = decode_entry(blob, meta)
    assert np.array_equal(k2, k_rows) and np.array_equal(v2, v_rows)
    dst.import_slot_kv(0, k2, v2, length=9)
    got = [int(first[1])]
    tok = np.array([got[0], 0], np.int32)
    for _ in range(3):
        tok = dst.decode(tok, np.array([True, False]))
        got.append(int(tok[0]))
    want = srv.generate(placed, jnp.asarray(prompt)[None, None], 4, MAX_SEQ,
                        impl="sort")
    assert got == np.asarray(want)[0, 0].tolist()
    # a copy inside one pool keeps the whole row too
    src.copy_slot_prefix(0, 1, 9)
    a = src.export_slot_kv(0, 0, 9)
    assert np.array_equal(a[0], k_rows) and np.array_equal(a[1], v_rows)


# -- the new blocks' scopes in the compiled programs -------------------------

LATENT_SCOPES = ("embed", "attn.latent_q", "attn.latent_kv", "attn.qkv",
                 "attn.kv_write", "attn.core", "attn.out", "ffn.dense",
                 "moe.router", "moe.route", "moe.dispatch", "moe.experts",
                 "moe.combine", "moe.shared", "head")


@pytest.fixture(scope="module")
def latent_program_text(model):
    cfg, params, srv, placed = model
    cache = srv.slot_cache(2, MAX_SEQ)

    def decode(p, tok, act, k, v, ln):
        return srv.decode_step_slots(p, tok, act, MoESlotCache(k, v, ln),
                                     impl="sort")

    def prefill(p, tok, lens, mask, k, v, ln):
        return srv.prefill_slots(p, tok, lens, mask, MoESlotCache(k, v, ln))

    act = jnp.ones((1, 2), bool)
    return {
        "decode": jax.jit(decode).lower(
            placed, jnp.ones((1, 2), jnp.int32), act, *cache
        ).compile().as_text(),
        "prefill": jax.jit(prefill).lower(
            placed, jnp.ones((1, 2, 4), jnp.int32),
            jnp.full((1, 2), 4, jnp.int32), act, *cache
        ).compile().as_text(),
    }


@pytest.mark.parametrize("scope", LATENT_SCOPES)
@pytest.mark.parametrize("program", ("decode", "prefill"))
def test_latent_programs_carry_their_scopes(latent_program_text, program,
                                            scope):
    assert f"/{scope}/" in latent_program_text[program], (
        f"{scope} is in no op_name of the compiled {program} program")
