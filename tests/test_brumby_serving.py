"""The serving stack's description of the Brumby (``brumby``) block against
the plain reference (``models/reference_hybrid_moe.py``: the QUADRATIC form
of power retention, no state, no chunks, no feature map), at a tiny size on
the CPU: 3 layers, every one a retention layer with a dense SwiGLU of 64, 4
query heads over 2 KV heads of 8 numbers (a feature map of two blocks of 4:
48 numbers a head), dim 32, vocabulary 96, an untied head, bfloat16 weights,
chunks of 4. The description is what ``from_hf`` reads from the published
keys; it has NO expert layer (``first_k_dense == n_layers``).

Tolerances. Program and reference hold the SAME bfloat16-valued weights and
seeded float32 gains and biases and compute in float32 under ``highest``:
they differ by the form — a state of ``phi(k) v^T`` sums carried from chunk
to chunk and contracted with ``phi(q)`` against one ``[T, T]`` matrix of
squared scores a head. Logits of magnitude ~3 (an untied head at 1/sqrt(32))
agree to ``LOGIT_TOL`` = 2e-5 (measured 5e-6); two program paths over the
same rows agree to ``PATH_TOL`` = 1e-5. What the tolerance must catch is
orders larger: every fault of ``REFERENCE_FAULTS`` and ``PROGRAM_FAULTS``
moves a logit by 2e-3 or more.
Served tokens against one-shot ``generate`` are compared exactly: the
engine's oracle guarantee.
"""

import dataclasses
import functools
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import test_afmoe_serving as afmoe
import test_hybrid_moe_serving as hybrid
import test_latent_moe_serving as latent
import test_lfm2_serving as lfm2
from uccl_tpu import obs
from uccl_tpu.models import inference
from uccl_tpu.models import moe_inference as mi
from uccl_tpu.models import reference_hybrid_moe as ref
from uccl_tpu.models.inference import SlotKVCache, _forward_slots
from uccl_tpu.models.moe_inference import (
    MoEServeConfig, MoEServer, MoESlotCache, init_params,
)
from uccl_tpu.serving import MoEBackend, ServingEngine

LOGIT_TOL = 2e-5
PATH_TOL = 1e-5
# a state's numbers reach ~20 (sums of phi(k) v^T over a prefix): two paths'
# float32 sums in another order differ by a few 1e-5 there
STATE_TOL = dict(atol=1e-4, rtol=1e-4)
MAX_SEQ = 96
VOCAB = 96
OVERRIDES = dict(param_dtype="bfloat16")

# the model's own keys at a tiny size, as ``from_hf`` reads them
TINY = dict(
    model_type="brumby", attention_bias=False, head_dim=8, hidden_act="silu",
    hidden_size=32, intermediate_size=64, max_position_embeddings=32768,
    max_window_layers=3, num_attention_heads=4, num_hidden_layers=3,
    num_key_value_heads=2, rms_norm_eps=1e-06, rope_scaling=None,
    rope_theta=1000000, sliding_window=None, tie_word_embeddings=False,
    use_sliding_window=False, vocab_size=VOCAB,
)

# the published keys (the catalog row's ``config``)
PUBLISHED = dict(
    attention_bias=False, head_dim=128, hidden_act="silu", hidden_size=5120,
    intermediate_size=17408, max_position_embeddings=32768,
    max_window_layers=40, model_type="brumby", num_attention_heads=40,
    num_hidden_layers=40, num_key_value_heads=8, rms_norm_eps=1e-06,
    rope_scaling=None, rope_theta=1000000, sliding_window=None,
    tie_word_embeddings=False, use_sliding_window=False, vocab_size=151936,
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


def _slot_logits(srv, placed, tokens, cache, start, mask, valid=None,
                 slots=None):
    """Logits [B, S, V] and the new cache of one masked slot forward, with
    each row's count of real positions (``valid``: None = every position of
    a row in ``mask``). One jitted function a (server, valid or not, compact
    or not)."""
    cfg = srv.cfg

    def f(p, tok, kc, vc, ln, off, m, *rest):
        rest = [r[0] for r in rest]
        v = rest.pop(0) if valid is not None else None
        logits, out = _forward_slots(
            mi._strip_shard(p), tok[0],
            SlotKVCache(mi._member(kc), mi._member(vc), ln[0]),
            off[0], m[0], cfg, ffn=mi._moe_block(cfg, "sort"),
            slots=rest[0] if rest else None, valid=v)
        return logits[None], mi._lead(out.k), mi._lead(out.v)

    extra = [] if valid is None else [jnp.asarray(valid, jnp.int32)[None]]
    if slots is not None:
        extra.append(jnp.asarray(slots, jnp.int32)[None])
    fns = srv.__dict__.setdefault("_ret_logits_fns", {})
    key = (valid is not None, slots is not None)
    if key not in fns:
        fns[key] = jax.jit(shard_map(
            f, mesh=srv.mesh,
            in_specs=(srv._param_specs(placed),)
            + (P("dp"),) * (6 + len(extra)),
            out_specs=(P("dp"),) * 3, check_vma=False))
    logits, nk, nv = fns[key](
        placed, jnp.asarray(tokens)[None], cache.k, cache.v, cache.lengths,
        jnp.asarray(start, jnp.int32)[None], jnp.asarray(mask)[None], *extra)
    return np.asarray(logits)[0], MoESlotCache(nk, nv, cache.lengths)


def _states(cache, slot):
    """Host copies of one slot's state in every layer: S and z."""
    return [np.asarray(a)[0, slot]
            for a in jax.tree.leaves((cache.k, cache.v))]


def _serve(srv, placed, cache, toks, chunk=4, slot=0, rows=2):
    """A prompt through ``slot`` in chunks of ``chunk``, the last
    right-padded and told so; (its logits [T, V], the pool)."""
    n = len(toks)
    pad = np.zeros((rows, -(-n // chunk) * chunk), np.int32)
    pad[slot, :n] = toks
    on = np.arange(rows) == slot
    parts = []
    for lo in range(0, pad.shape[1], chunk):
        valid = np.where(on, np.clip(n - lo, 0, chunk), 0)
        part, cache = _slot_logits(srv, placed, pad[:, lo:lo + chunk], cache,
                                   np.where(on, lo, 0), on, valid=valid)
        parts.append(part[slot])
    return np.concatenate(parts)[:n], cache


# -- the description, the tree and the pool ----------------------------------

def test_description_tree_and_pool(model):
    cfg, params, srv, placed = model
    assert cfg == MoEServeConfig(
        vocab=VOCAB, dim=32, n_layers=3, n_heads=4, n_kv_heads=2, head_dim=8,
        rope_theta=1e6, norm_eps=1e-6, moe_experts=0, moe_topk=0, moe_ffn=0,
        layer_kinds=("retention",) * 3, qk_norm=True, norm_gain_scale=0.1,
        first_k_dense=3, dense_ffn=64, param_dtype="bfloat16")
    assert cfg.n_moe_layers == 0 and cfg.n_held == 0
    assert cfg.param_groups() == [("dense_retention_blocks", i)
                                  for i in range(3)]
    assert inference.cache_groups(cfg) == [("retention", i)
                                           for i in range(3)]
    assert set(params) == {"embed", "dense_retention_blocks", "final_norm",
                           "head"}
    g = params["dense_retention_blocks"]
    assert set(g) == {"wq", "wk", "wv", "wo", "wg", "bg", "q_norm", "k_norm",
                      "ln1", "ln2", "w_gate", "w_up", "w_down"}
    assert g["wq"].shape == g["wo"].shape == (3, 32, 32)
    assert g["wk"].shape == g["wv"].shape == (3, 32, 16)
    assert g["wg"].shape == (3, 32, 2) and g["wg"].dtype == jnp.bfloat16
    assert g["bg"].shape == (3, 2) and g["bg"].dtype == jnp.float32
    assert 4.0 <= float(g["bg"].min()) and float(g["bg"].max()) <= 7.0
    # the gate is drawn at 1/sqrt(dim): h wg moves the decay by a position
    assert 0.1 < float(jnp.std(g["wg"].astype(jnp.float32))) < 0.25
    assert g["q_norm"].shape == g["k_norm"].shape == (3, 8)
    assert g["w_gate"].shape == (3, 32, 64)
    gains = np.concatenate([np.asarray(g[leaf]).ravel()
                            for leaf in ("ln1", "ln2", "q_norm", "k_norm")])
    assert 0.05 < float(np.std(gains)) < 0.15
    # the pool: no position axis — one S [Hkv, F, Dv] and one z [Hkv, F] a
    # slot and layer, each layer its own array
    feats = inference.retention_features(8)
    assert feats == 48 and inference.retention_features(128) == 9216
    cache = srv.slot_cache(2, MAX_SEQ)
    assert set(cache.k) == set(cache.v) == {"retention"}
    assert [a.shape for a in cache.k["retention"]] == [(1, 2, 2, 48, 8)] * 3
    assert [a.shape for a in cache.v["retention"]] == [(1, 2, 2, 48)] * 3
    per_slot = 2 * 48 * 8 * 4 + 2 * 48 * 4
    assert obs.gauge("serving_state_bytes_per_slot").get(
        kind="retention") == per_slot
    assert obs.gauge("serving_kv_pool_bytes").get(
        group="retention") == 3 * 2 * per_slot
    once = mi.MoEKVCache.empty(cfg, 1, 1, MAX_SEQ)
    assert [a.shape for a in once.k["retention"]] == [(1, 1, 2, 48, 8)] * 3


def test_the_feature_map_squares_the_product():
    """phi(q) . phi(k) = (q . k)^2 at a head of 8 (two blocks of 4), of 128
    (eight of 16) and of 12 (two of 6)."""
    rng = np.random.default_rng(1)
    for d in (8, 128, 12):
        q, k = (jnp.asarray(rng.normal(size=(5, d)), jnp.float32)
                for _ in range(2))
        pq, pk = inference._phi(q), inference._phi(k)
        assert pq.shape == (5, inference.retention_features(d))
        np.testing.assert_allclose(np.sum(np.asarray(pq * pk), -1),
                                   np.sum(np.asarray(q * k), -1) ** 2,
                                   rtol=1e-5)


def test_from_hf_reads_the_published_keys():
    cfg = MoEServeConfig.from_hf(PUBLISHED, param_dtype="bfloat16")
    assert (cfg.attn, cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.v_head_dim, cfg.vocab) == (
        "gqa", 40, 5120, 40, 8, 128, 0, 151936)
    assert cfg.layer_kinds == ("retention",) * 40
    assert (cfg.rope_theta, cfg.norm_eps, cfg.qk_norm, cfg.tie_head,
            cfg.first_k_dense, cfg.dense_ffn, cfg.n_moe_layers,
            cfg.moe_experts, cfg.moe_topk) == (
        1e6, 1e-6, True, False, 40, 17408, 0, 0, 0)
    cut = MoEServeConfig.from_hf(dict(PUBLISHED, num_hidden_layers=8))
    assert cut.layer_kinds == ("retention",) * 8 and cut.first_k_dense == 8
    s_row, z_row = inference.kv_row_shapes(cut, "retention")
    assert s_row == (8, 9216, 128) and z_row == (8, 9216)
    for keys, match in (
            (dict(use_sliding_window=True), "use_sliding_window True"),
            (dict(rope_scaling=dict(type="yarn", factor=4.0)),
             "rope_scaling"),
            (dict(attention_bias=True), "attention_bias True"),
            (dict(tie_word_embeddings=True), "tie_word_embeddings True")):
        with pytest.raises(ValueError, match=match):
            MoEServeConfig.from_hf(dict(PUBLISHED, **keys))
    with pytest.raises(ValueError, match="retention layers beside another"):
        MoEServeConfig.from_hf(TINY, layer_kinds=("retention", "full",
                                                  "retention"))
    with pytest.raises(ValueError, match="first_k_dense 4 is not among"):
        MoEServeConfig.from_hf(TINY, first_k_dense=4)


def test_the_entry_point_builds_the_example_configuration():
    """``uccl_tpu/serve.py --model-config examples/configs/
    brumby_tiny.json``: a file with no expert key at all."""
    import argparse
    import os

    from uccl_tpu import serve

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples", "configs",
        "brumby_tiny.json")
    args = argparse.Namespace(model_config=path, ckpt_dir="",
                              prefill_chunk=4, spec_k=0)
    cfg = serve._moe_cfg(args)
    assert cfg.layer_kinds == ("retention",) * 3 and not cfg.tie_head
    assert (cfg.dim, cfg.head_dim, cfg.n_moe_layers, cfg.capacity_factor,
            cfg.param_dtype) == (64, 16, 0, 8.0, "bfloat16")


# -- every description's pool answers for itself ------------------------------

_RING = "a pool with ring groups keeps a slot's last reach - 1 positions"
_STATE = "a pool with a state group keeps ONE state a slot and layer"
_FREE = inference.PoolTraits()

# description (None: the hand-sized flags; a file under examples/configs or
# chipbench/configs) -> ``serve._moe_cfg``'s (capacity_factor, window_ring,
# conv_ring) at (--prefill-chunk 4) and at (--prefill-chunk 128 --spec-k 3),
# frozen from the parent of PR 48 (e5bbe6a), where serve.py sized the rings
# and re-read the expert keys itself; then the pool's traits at the first:
# how its ``rows_stay`` starts (None: a slot's rows may leave it) and the
# other fields.
POOLS = {
    None: ((8.0, 0, 0), (8.0, 0, 0), None, _FREE),
    "examples/configs/glm4_moe_lite_tiny.json":
        ((8.0, 0, 0), (8.0, 0, 0), None, _FREE),
    "examples/configs/mimo_v2_flash_tiny.json":
        ((8.0, 16, 0), (8.0, 135, 0), _RING, _FREE._replace(
            widest_write=9, tightest=("window", "window", 16, 8), window=8)),
    "examples/configs/afmoe_tiny.json":
        ((8.0, 16, 0), (8.0, 135, 0), _RING, _FREE._replace(
            widest_write=9, tightest=("window", "window", 16, 8), window=8)),
    "examples/configs/lfm2_moe_tiny.json":
        ((8.0, 0, 6), (8.0, 0, 130), _RING, _FREE._replace(
            projections=False, widest_write=4,
            tightest=("conv", "taps", 6, 3))),
    "examples/configs/brumby_tiny.json":
        ((8.0, 0, 0), (8.0, 0, 0), _STATE, _FREE._replace(
            rollback=False, projections=False)),
    "chipbench/configs/mixtral-8x7b-serve.json":
        ((8.0, 0, 0), (8.0, 0, 0), None, _FREE),
    "chipbench/configs/glm-4.7-flash-serve.json":
        ((16.0, 0, 0), (16.0, 0, 0), None, _FREE),
    "chipbench/configs/mimo-v2-flash-serve.json":
        ((32.0, 256, 0), (32.0, 256, 0), _RING, _FREE._replace(
            widest_write=129, tightest=("window", "window", 256, 128),
            window=128)),
    "chipbench/configs/trinity-large-preview-serve.json":
        ((64.0, 8192, 0), (64.0, 8192, 0), _RING, _FREE._replace(
            widest_write=4097, tightest=("window", "window", 8192, 4096),
            window=4096)),
    "chipbench/configs/lfm2-24b-a2b-serve.json":
        ((16.0, 0, 6), (16.0, 0, 130), _RING, _FREE._replace(
            projections=False, widest_write=4,
            tightest=("conv", "taps", 6, 3))),
    "chipbench/configs/brumby-14b-base-serve.json":
        ((8.0, 0, 0), (8.0, 0, 0), _STATE, _FREE._replace(
            rollback=False, projections=False)),
}


@pytest.mark.parametrize("path", POOLS, ids=lambda p: str(p).split("/")[-1])
def test_a_description_sizes_itself_and_says_what_its_pool_can_do(path):
    """``MoEServeConfig.sized_for_serving`` through ``serve._moe_cfg`` gives
    what the parent's ``_moe_cfg`` gave, field by field, and
    ``inference.pool_traits`` of it the one answer the engine, the pool's
    row shims and the disaggregated wire refuse from."""
    import argparse
    import os

    from uccl_tpu import serve

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    chunk_4, chunk_128, stay, want = POOLS[path]

    def sized(chunk, spec_k):
        return serve._moe_cfg(argparse.Namespace(
            model_config=path and os.path.join(root, path), ckpt_dir="",
            prefill_chunk=chunk, spec_k=spec_k, vocab=512, dim=128, layers=2,
            heads=4, kv_heads=2, experts=8, ffn=256))

    for cfg, fields in ((sized(4, 0), chunk_4), (sized(128, 3), chunk_128)):
        assert (cfg.capacity_factor, cfg.window_ring, cfg.conv_ring) == fields
        assert cfg.capacity_factor >= cfg.drop_free_factor
    got = inference.pool_traits(sized(4, 0))
    assert got.rows_stay is None if stay is None \
        else got.rows_stay.startswith(stay)
    assert got._replace(rows_stay=None) == want
    # the three statements of the ring rule are one: the sized ring takes
    # the widest write it was sized for, and the shims' sentence is the
    # traits' own
    kinds = sized(4, 0).layer_kinds
    assert got.rows_stay == (inference.rows_stay(kinds) if kinds else None)
    wide = inference.pool_traits(sized(128, 3)).widest_write
    assert wide is None or wide >= 128


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


def test_prefill_then_cached_decode_for_40_steps(model):
    """Chunked prefill into the slot pool (the second chunk padded), then
    one token at a time: the recurrence, 40 times."""
    cfg, params, srv, placed = model
    toks = _tokens(47, seed=1)
    want = np.asarray(ref.forward_logits(params, toks, cfg))
    got, cache = _serve(srv, placed, srv.slot_cache(2, MAX_SEQ), toks[:7])
    np.testing.assert_allclose(got, want[:7], atol=LOGIT_TOL)
    on = np.array([True, False])
    both = np.zeros((2, 47), np.int32)
    both[0] = toks
    for i in range(7, 47):
        one, cache = _slot_logits(srv, placed, both[:, i:i + 1], cache,
                                  [i, 0], on)
        np.testing.assert_allclose(one[0, 0], want[i], atol=LOGIT_TOL)


def test_chunked_prefill_with_a_padded_last_chunk_is_one_shot(model):
    """Prompts of 19 and 30 in chunks of 4, the last right-padded with
    token 0 and the rows told how many positions are real; then decode."""
    cfg, params, srv, placed = model
    a, b = _tokens(19 + 6, seed=2), _tokens(30 + 6, seed=3)
    want = [np.asarray(ref.forward_logits(params, t, cfg)) for t in (a, b)]
    lens = np.array([19, 30])
    padded = np.zeros((2, 32), np.int32)
    padded[0, :19], padded[1, :30] = a[:19], b[:30]
    cache = srv.slot_cache(2, MAX_SEQ)
    parts = []
    for lo in range(0, 32, 4):
        part, cache = _slot_logits(
            srv, placed, padded[:, lo:lo + 4], cache, [lo, lo], lo < lens,
            valid=np.clip(lens - lo, 0, 4))
        parts.append(part)
    got = np.concatenate(parts, axis=1)
    for r, n in enumerate(lens):
        np.testing.assert_allclose(got[r, :n], want[r][:n], atol=LOGIT_TOL)
    for j in range(6):
        tok = np.array([[a[19 + j]], [b[30 + j]]], np.int32)
        one, cache = _slot_logits(srv, placed, tok, cache,
                                  [19 + j, 30 + j], np.ones(2, bool))
        np.testing.assert_allclose(one[0, 0], want[0][19 + j],
                                   atol=LOGIT_TOL)
        np.testing.assert_allclose(one[1, 0], want[1][30 + j],
                                   atol=LOGIT_TOL)


def test_a_padded_tail_that_is_not_masked_out_moves_a_logit(model):
    """The fault the ``valid`` count prevents: a last chunk of 3 real
    positions and one of padding, run as four real ones, leaves a state
    that has taken the padding in; the next token's logits move."""
    cfg, params, srv, placed = model
    toks = _tokens(8, seed=4)
    want = np.asarray(ref.forward_logits(params, toks, cfg))
    on = np.array([True, False])
    pad = np.zeros((2, 8), np.int32)
    pad[0, :7] = toks[:7]
    nxt = np.zeros((2, 1), np.int32)
    nxt[0] = toks[7]
    got = {}
    for told in (True, False):
        cache = srv.slot_cache(2, MAX_SEQ)
        for lo in (0, 4):
            _, cache = _slot_logits(
                srv, placed, pad[:, lo:lo + 4], cache, [lo, 0], on,
                valid=[min(7 - lo, 4), 0] if told else None)
        got[told], _ = _slot_logits(srv, placed, nxt, cache, [7, 0], on)
    np.testing.assert_allclose(got[True][0, 0], want[7], atol=LOGIT_TOL)
    assert np.abs(got[False][0, 0] - want[7]).max() > 100 * LOGIT_TOL


def _splits(n):
    """Every way to cut ``n`` positions into chunks of 1..n, as lists of
    chunk lengths — 2^(n-1) of them; here the ones of at most five chunks
    and the all-ones one."""
    out = []
    for cuts in range(1 << (n - 1)):
        if bin(cuts).count("1") > 4 and cuts != (1 << (n - 1)) - 1:
            continue
        sizes, run = [], 1
        for i in range(n - 1):
            if cuts >> i & 1:
                sizes.append(run)
                run = 1
            else:
                run += 1
        out.append(sizes + [run])
    return out


def test_every_split_of_a_prompt_into_chunks_is_the_reference(model):
    """A 13-position prompt cut into chunks every way (each chunk run at a
    width of 13, right-padded, so one compiled program serves them all):
    the logits of every position and the state left behind are the same."""
    cfg, params, srv, placed = model
    toks = _tokens(13, seed=5)
    want = np.asarray(ref.forward_logits(params, toks, cfg))
    on = np.array([True, False])
    splits = _splits(13)
    assert len(splits) > 700 and [13] in splits and [1] * 13 in splits
    rng = np.random.default_rng(0)
    picked = [[13], [1] * 13] + [splits[i] for i in
                                 rng.choice(len(splits), 22, replace=False)]
    final = None
    for sizes in picked:
        cache, lo, rows = srv.slot_cache(2, MAX_SEQ), 0, []
        for size in sizes:
            win = np.zeros((2, 13), np.int32)
            win[0, :size] = toks[lo:lo + size]
            part, cache = _slot_logits(srv, placed, win, cache, [lo, 0], on,
                                       valid=[size, 0])
            rows.append(part[0, :size])
            lo += size
        np.testing.assert_allclose(np.concatenate(rows), want,
                                   atol=LOGIT_TOL, err_msg=str(sizes))
        state = _states(cache, 0)
        if final is not None:
            for a, b in zip(state, final):
                np.testing.assert_allclose(a, b, **STATE_TOL)
        final = state


REAL_ROWS = {"none": (), "one": (2,), "some": (1, 3), "all": (0, 1, 2, 3)}


@pytest.mark.parametrize("real", sorted(REAL_ROWS))
@pytest.mark.parametrize("call", ("decode", "chunk", "chunk_from_0"))
def test_the_row_loop_visits_the_rows_with_a_real_position(model, call, real):
    """A call of four rows goes through the operator's row loop, which
    visits the rows with a real position and no other — none, one, some that
    are no prefix of the rows, all: a decode call ``[4, 1]``, a chunk ``[4,
    4]`` whose row 3 has 3 real positions of 4, and the same chunk as a
    prompt's first (position 0, over what a previous occupant left). A
    visited row's logits are the reference's and its state what the row
    reaches ALONE in a pool of its own (one path against another); a row the
    loop never reaches keeps its ``S`` and ``z`` bit for bit, non-zero as
    the previous occupant left them."""
    cfg, params, srv, placed = model
    rows = REAL_ROWS[real]
    on = np.isin(np.arange(4), rows)
    # every slot's previous occupant: 9 positions of its own, 8 of them
    # pool-wide (rows that are all real) and one by a decode call
    before = np.stack([_tokens(9, seed=50 + r) for r in range(4)])
    cache = srv.slot_cache(4, MAX_SEQ)
    for lo in (0, 4):
        _, cache = _slot_logits(srv, placed, before[:, lo:lo + 4], cache,
                                [lo] * 4, np.ones(4, bool), valid=[4] * 4)
    _, cache = _slot_logits(srv, placed, before[:, 8:], cache, [8] * 4,
                            np.ones(4, bool), valid=[1] * 4)
    held = [_states(cache, r) for r in range(4)]
    assert all(np.any(a != 0) for state in held for a in state)
    width = 1 if call == "decode" else 4
    start = 0 if call == "chunk_from_0" else 9
    new = np.stack([_tokens(width, seed=60 + r) for r in range(4)])
    valid = np.where(on, np.where(np.arange(4) == 3, max(width - 1, 1),
                                  width), 0)
    got, cache = _slot_logits(srv, placed, new, cache, [start] * 4, on,
                              valid=valid)
    assert np.all(np.isfinite(got))
    for r in range(4):
        if r not in rows:
            for a, b in zip(_states(cache, r), held[r]):
                assert np.array_equal(a, b)
            continue
        n = int(valid[r])
        prompt = new[r, :n] if start == 0 \
            else np.concatenate([before[r], new[r, :n]])
        want = np.asarray(ref.forward_logits(params, prompt, cfg))
        np.testing.assert_allclose(got[r, :n], want[-n:], atol=LOGIT_TOL)
        alone, pool = _serve(srv, placed, srv.slot_cache(2, MAX_SEQ), prompt)
        np.testing.assert_allclose(got[r, :n], alone[-n:], atol=PATH_TOL)
        for a, b in zip(_states(cache, r), _states(pool, 0)):
            np.testing.assert_allclose(a, b, **STATE_TOL)


@pytest.mark.parametrize("stack", ("dense", "uniform_moe", "layer_kinds"))
def test_the_head_at_one_position_is_the_head_at_all(model, devices, stack):
    """The prefill program contracts the head with the ONE position a row
    whose token it returns (``head_at``): the token is the argmax at that
    position of the head over every position (``head_at=None``, what the
    verify and decode programs read), in both stacks."""
    toks = np.stack([_tokens(8, seed=8), _tokens(8, seed=9)])
    lens = np.asarray([7, 8], np.int32)
    if stack == "dense":
        from uccl_tpu.models import dense

        cfg = dense.DenseConfig(vocab=VOCAB, dim=32, n_layers=2, n_heads=4,
                                n_kv_heads=2, head_dim=8, ffn=64)
        params = dense.init_params(jax.random.PRNGKey(3), cfg)
        pool = functools.partial(SlotKVCache.empty, cfg, 2, MAX_SEQ)
        tok, _ = inference.prefill_slots(
            params, jnp.asarray(toks), jnp.asarray(lens),
            jnp.ones(2, bool), pool(), cfg)
        logits, _ = _forward_slots(
            params, jnp.asarray(toks), pool(), jnp.zeros(2, jnp.int32),
            jnp.ones(2, bool), cfg)
    else:
        if stack == "layer_kinds":
            cfg, params, srv, placed = model
        else:
            cfg = MoEServeConfig(vocab=VOCAB, dim=32, n_layers=2, n_heads=4,
                                 n_kv_heads=2, head_dim=8, moe_experts=4,
                                 moe_ffn=32)
            srv = _server(devices, cfg)
            placed = srv.shard_params(
                init_params(jax.random.PRNGKey(3), cfg))
        tok, _ = srv.prefill_slots(
            placed, jnp.asarray(toks)[None], jnp.asarray(lens)[None],
            jnp.ones((1, 2), bool), srv.slot_cache(2, MAX_SEQ))
        tok = np.asarray(tok)[0]
        logits, _ = _slot_logits(srv, placed, toks, srv.slot_cache(2, MAX_SEQ),
                                 [0, 0], np.ones(2, bool))
    assert np.asarray(logits).shape == (2, 8, VOCAB)
    want = np.argmax(np.asarray(logits)[np.arange(2), lens - 1], axis=-1)
    assert np.array_equal(np.asarray(tok), want)


def test_compact_rungs_are_the_pool_wide_rung(model):
    """The [1 | 2, chunk] compact programs over named slots against the
    pool-wide program: the same logits, and slots not named untouched bit
    for bit."""
    cfg, params, srv, placed = model
    prompts = [_tokens(11, seed=10 + i) for i in range(3)]
    wide = srv.slot_cache(3, MAX_SEQ)
    logits_wide = []
    for s in range(3):
        got, wide = _serve(srv, placed, wide, prompts[s], slot=s, rows=3)
        logits_wide.append(got)
    compact = srv.slot_cache(3, MAX_SEQ)
    three = np.zeros((3, 12), np.int32)
    for s in range(3):
        three[s, :11] = prompts[s]
    got = {}
    for slots in ([2], [0, 1]):
        parts = []
        for lo in (0, 4, 8):
            untouched = [s for s in range(3) if s not in slots]
            before = [_states(compact, s) for s in untouched]
            part, compact = _slot_logits(
                srv, placed, three[slots, lo:lo + 4], compact,
                [lo] * len(slots), np.ones(len(slots), bool),
                valid=[min(11 - lo, 4)] * len(slots), slots=slots)
            for s, rows in zip(untouched, before):
                for a, b in zip(_states(compact, s), rows):
                    assert np.array_equal(a, b)
            parts.append(part)
        for r, s in enumerate(slots):
            got[s] = np.concatenate(parts, axis=1)[r, :11]
    for s in range(3):
        np.testing.assert_allclose(got[s], logits_wide[s], atol=PATH_TOL)
        want = np.asarray(ref.forward_logits(params, prompts[s], cfg))
        np.testing.assert_allclose(got[s], want, atol=LOGIT_TOL)
        for a, b in zip(_states(compact, s), _states(wide, s)):
            np.testing.assert_allclose(a, b, **STATE_TOL)


# -- the state group's invariant ----------------------------------------------

def test_a_readmitted_slot_serves_what_a_fresh_pool_serves(model):
    """A slot's next occupant starts at position 0 over the state a LONGER
    occupant left in every layer: it reads a zero state, from its start, not
    from a scrub. The logits are the reference's and, bit for bit, a fresh
    pool's; run over the old state at a later start they are another
    model's."""
    cfg, params, srv, placed = model
    long, short = _tokens(23, seed=30), _tokens(10, seed=31)
    want = np.asarray(ref.forward_logits(params, short, cfg))
    fresh, _ = _serve(srv, placed, srv.slot_cache(2, MAX_SEQ), short)
    _, used = _serve(srv, placed, srv.slot_cache(2, MAX_SEQ), long)
    assert all(np.any(a != 0) for a in _states(used, 0))
    again, _ = _serve(srv, placed, used, short)
    np.testing.assert_array_equal(again, fresh)
    np.testing.assert_allclose(again, want, atol=LOGIT_TOL)
    # the state not zeroed: the same prompt from a later start (the rotation
    # is relative, so only the state found differs)
    _, used = _serve(srv, placed, srv.slot_cache(2, MAX_SEQ), long)
    pad = np.zeros((2, 12), np.int32)
    pad[0, :10] = short
    on = np.array([True, False])
    stale = []
    for lo in (0, 4, 8):
        part, used = _slot_logits(srv, placed, pad[:, lo:lo + 4], used,
                                  [24 + lo, 0], on,
                                  valid=[min(10 - lo, 4), 0])
        stale.append(part[0])
    assert np.abs(np.concatenate(stale)[:10] - want).max() > 100 * LOGIT_TOL


def test_a_masked_rows_state_is_untouched_by_its_neighbours(model):
    """Slot 1 holds a state; slot 0's prefill chunks (one padded) and decode
    steps, pool-wide and compact, leave it bit for bit what it was."""
    cfg, params, srv, placed = model
    _, cache = _serve(srv, placed, srv.slot_cache(2, MAX_SEQ),
                      _tokens(9, seed=40), slot=1)
    held = _states(cache, 1)
    assert all(np.any(a != 0) for a in held)
    toks = _tokens(20, seed=41)
    _, cache = _serve(srv, placed, cache, toks[:7], slot=0)
    both = np.zeros((2, 20), np.int32)
    both[0] = toks
    for i in range(7, 12):
        _, cache = _slot_logits(srv, placed, both[:, i:i + 1], cache, [i, 0],
                                np.array([True, False]))
    for lo in (12, 16):  # compact, slot 0 alone
        _, cache = _slot_logits(srv, placed, both[:1, lo:lo + 4], cache,
                                [lo], np.ones(1, bool), valid=[4], slots=[0])
    # the server's own programs: a pool-wide decode, and a compact prefill
    # whose padding row's slot index clamps onto slot 1
    tok, cache = srv.decode_step_slots(
        placed, jnp.asarray([[3, 0]], jnp.int32),
        jnp.asarray([[True, False]]),
        MoESlotCache(cache.k, cache.v, jnp.asarray([[20, 9]], jnp.int32)),
        impl="sort")
    _, cache = srv.prefill_slots(
        placed, jnp.zeros((1, 2, 4), jnp.int32),
        jnp.asarray([[25, 1]], jnp.int32), jnp.asarray([[True, False]]),
        cache, start=jnp.asarray([[21, 0]], jnp.int32),
        slots=jnp.asarray([[0, 2]], jnp.int32))
    for a, b in zip(_states(cache, 1), held):
        assert np.array_equal(a, b)


# -- chunk form = recurrence = quadratic form, one layer ---------------------

def _qkvg(x, lp, cfg):
    """One layer's queries [T, H, D], keys and values [T, Hkv, D] and log
    gates [T, Hkv] by the reference's own helpers."""
    t = x.shape[0]
    pos = jnp.arange(t)
    h = ref._norm(x, lp["ln1"], cfg.norm_eps)

    def heads(w, gain, n):
        y = (h @ w).reshape(t, n, cfg.head_dim)
        if gain is None:
            return y
        return jnp.stack([ref._rotate(
            ref._norm(y[:, j], lp[gain], cfg.norm_eps), pos, cfg.rope_theta,
            cfg.head_dim) for j in range(n)], axis=1)

    return (heads(lp["wq"], "q_norm", cfg.n_heads),
            heads(lp["wk"], "k_norm", cfg.n_kv_heads),
            heads(lp["wv"], None, cfg.n_kv_heads),
            jax.nn.log_sigmoid(h @ lp["wg"] + lp["bg"]))


def _recurrence(q, k, v, log_g):
    """y [T, H, D] by the recurrence over the FULL outer product as feature
    map (D^2 numbers: phi(q) . phi(k) = (q . k)^2 with no weights), float64
    numpy, one position at a time."""
    q, k, v, log_g = (np.asarray(a, np.float64) for a in (q, k, v, log_g))
    t, nh, d = q.shape
    hkv = k.shape[1]
    s = np.zeros((hkv, d * d, d))
    z = np.zeros((hkv, d * d))
    out = np.zeros((t, nh, d))
    for i in range(t):
        for g in range(hkv):
            pk = np.outer(k[i, g], k[i, g]).ravel()
            s[g] = np.exp(log_g[i, g]) * s[g] + np.outer(pk, v[i, g])
            z[g] = np.exp(log_g[i, g]) * z[g] + pk
        for j in range(nh):
            g = j // (nh // hkv)
            pq = np.outer(q[i, j], q[i, j]).ravel() / d
            out[i, j] = pq @ s[g] / (pq @ z[g] + 1e-6)
    return out


@pytest.mark.parametrize("chunk", [1, 3, 4, 8])
def test_chunk_form_is_recurrence_is_quadratic_form(model, chunk):
    """One layer on random inputs, three ways: the operator over chunks of
    ``chunk`` positions (1: its recurrence; the last chunk padded), the
    recurrence written out with the full outer product as feature map, and
    the reference's quadratic form."""
    cfg, params, _, _ = model
    t = 19
    lp = jax.tree.map(lambda a: a[1].astype(jnp.float32),
                      params["dense_retention_blocks"])
    x = jnp.asarray(np.random.default_rng(50).normal(size=(t, 32)),
                    jnp.float32)
    quadratic = np.asarray(ref.power_retention(x, lp, cfg) - x)
    wo = np.asarray(lp["wo"], np.float64)
    recurrent = _recurrence(*_qkvg(x, lp, cfg)).reshape(t, -1) @ wo
    np.testing.assert_allclose(recurrent, quadratic, atol=LOGIT_TOL)
    state = [(jnp.zeros((1, 2, 48, 8)),), (jnp.zeros((1, 2, 48)),)]

    write = inference._state_write(0, jnp.arange(1))

    @jax.jit
    def step(xs, k, v, lo, valid):
        return inference._power_retention(
            xs, lp, k, v, lo + jnp.arange(chunk)[None], lo, write, cfg,
            valid=valid)

    parts = []
    padded = jnp.zeros((-(-t // chunk) * chunk, 32)).at[:t].set(x)
    for lo in range(0, t, chunk):
        out, *state = step(padded[None, lo:lo + chunk], *state,
                           jnp.asarray([lo]),
                           jnp.asarray([min(t - lo, chunk)]))
        parts.append(out[0] - padded[lo:lo + chunk])
    np.testing.assert_allclose(np.concatenate(parts)[:t], quadratic,
                               atol=LOGIT_TOL)


# -- the engine ---------------------------------------------------------------

def test_engine_served_tokens_are_generates(model):
    """Five requests through two slots: every slot is re-admitted over a
    previous occupant's state at least once; the decode call of a model
    with no expert layer reports no experts read."""
    cfg, params, srv, placed = model
    backend = MoEBackend(srv, placed, batch_local=2, max_seq=MAX_SEQ,
                         decode_impl="sort")
    assert backend.experts_held == 0
    read = obs.counter("ep_experts_read_total").get()
    eng = ServingEngine(backend, prefill_chunk=4)
    reqs = [eng.submit(_tokens(n, seed=20 + n), max_new_tokens=m)
            for n, m in ((5, 24), (23, 20), (11, 30), (3, 9), (17, 12))]
    eng.drain()
    for r in reqs:
        want = srv.generate(placed, jnp.asarray(r.prompt)[None, None],
                            r.max_new_tokens, MAX_SEQ, impl="sort")
        assert r.out_tokens == np.asarray(want)[0, 0].tolist(), r.rid
    assert eng.pool.leaked() == 0
    assert obs.counter("ep_experts_read_total").get() == read
    out = srv.decode_step_slots(
        placed, jnp.zeros((1, 2), jnp.int32), jnp.zeros((1, 2), bool),
        srv.slot_cache(2, MAX_SEQ), impl="sort")
    assert len(out) == 2  # (token, pool): no count between them


def test_the_whole_prompt_path_serves_generates_tokens(model):
    """Without ``prefill_chunk`` a prompt runs in one right-padded bucket:
    the padding is told apart by the prompt's length."""
    cfg, params, srv, placed = model
    backend = MoEBackend(srv, placed, batch_local=2, max_seq=MAX_SEQ,
                         decode_impl="sort")
    eng = ServingEngine(backend)
    reqs = [eng.submit(_tokens(n, seed=60 + n), max_new_tokens=8)
            for n in (5, 11, 3)]
    eng.drain()
    for r in reqs:
        want = srv.generate(placed, jnp.asarray(r.prompt)[None, None], 8,
                            MAX_SEQ, impl="sort")
        assert r.out_tokens == np.asarray(want)[0, 0].tolist(), r.rid


# -- what a pool with a state group cannot do yet ---------------------------

@pytest.mark.parametrize("what", [
    "spec_k", "preempt", "prefix_cache", "kv_tiers", "disagg", "export_rows",
    "import_rows", "copy_prefix", "adapters", "lora"])
def test_a_state_pool_refuses_what_it_cannot_do(model, what):
    from uccl_tpu.serving import PrefixCache
    from uccl_tpu.serving.adapters import AdapterStore
    from uccl_tpu.serving.disagg import wire_format_for
    from uccl_tpu.serving.kv_tiers import TieredKVCache

    cfg, params, srv, placed = model
    backend = MoEBackend(srv, placed, batch_local=2, max_seq=MAX_SEQ,
                         decode_impl="sort")
    rows = np.zeros((3, 4, 32), np.float32)
    calls = {
        "spec_k": (lambda: ServingEngine(backend, prefill_chunk=4, spec_k=3),
                   "spec_k verifies a window of drafts"),
        "preempt": (lambda: ServingEngine(
            backend, prefill_chunk=4, priority_classes=True, preempt=True),
            "preempt saves a victim's"),
        "prefix_cache": (lambda: ServingEngine(
            backend, prefill_chunk=4, prefix_cache=PrefixCache(4)),
            "prefix_cache copies a donor's rows"),
        "kv_tiers": (lambda: ServingEngine(
            backend, prefill_chunk=4, prefix_cache=PrefixCache(4),
            kv_tiers=TieredKVCache(host_bytes=1 << 20)),
            "kv_tiers demotes and promotes"),
        "disagg": (lambda: wire_format_for(backend),
                   "the disaggregated wire format"),
        "export_rows": (lambda: backend.export_slot_kv(0, 0, 4),
                        "export_rows has no one array"),
        "import_rows": (lambda: backend.import_slot_kv(0, rows, rows,
                                                       length=4),
                        "import_rows would need"),
        "copy_prefix": (lambda: backend.copy_slot_prefix(1, 0, 4),
                        "copy_prefix finds the donor"),
    }
    if what in calls:
        call, says = calls[what]
        with pytest.raises(ValueError, match="a pool with a state group "
                           "keeps ONE state a slot and layer.*" + says):
            call()
    elif what == "adapters":
        store = AdapterStore.__new__(AdapterStore)  # refused before any use
        with pytest.raises(ValueError, match="LoRA adapters beside conv or "
                           "retention layers"):
            ServingEngine(backend, prefill_chunk=4, adapters=store)
    else:
        with pytest.raises(ValueError, match="LoRA adapters beside "
                           "retention layers"):
            inference._power_retention(None, None, None, None, None, None,
                                       None, cfg, lora=lambda h, t: h)


# -- what the tolerance catches ----------------------------------------------

def _faulty_retention(x, lp, cfg, fault=None):
    """The reference's quadratic form with ONE thing another model does
    (``fault`` None: the reference itself, held equal to it below)."""
    t = x.shape[0]
    nh, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = jnp.arange(t)
    h = ref._norm(x, lp["ln1"], cfg.norm_eps)
    q = (h @ lp["wq"]).reshape(t, nh, d)
    k = (h @ lp["wk"]).reshape(t, hkv, d)
    v = (h @ lp["wv"]).reshape(t, hkv, d)
    log_g = jax.nn.log_sigmoid(h @ lp["wg"] + lp["bg"])
    if fault == "no_gate":
        log_g = jnp.zeros_like(log_g)
    since = jnp.cumsum(log_g, axis=0)
    seen = pos[None, :] <= pos[:, None]

    def placed(y, gain):
        if fault != "no_qk_norm":
            y = ref._norm(y, lp[gain], cfg.norm_eps)
        return y if fault == "no_rotation" \
            else ref._rotate(y, pos, cfg.rope_theta, d)

    heads = []
    for j in range(nh):
        g = j // (nh // hkv)
        # a gate a query head: head j decays by the gate of KV head j % Hkv
        c = since[:, j % hkv if fault == "gate_a_query_head" else g]
        upto = c[None, :]
        if fault == "decay_one_position_late":  # G(i, j) skips g at j + 1
            upto = jnp.concatenate([c[1:], c[-1:]])[None, :]
        decay = jnp.where(seen, jnp.exp(jnp.where(
            seen, jnp.minimum(c[:, None] - upto, 0.0), 0.0)), 0.0)
        sc = placed(q[:, j], "q_norm") @ placed(k[:, g], "k_norm").T \
            / math.sqrt(d)
        a = decay * (jnp.abs(sc) if fault == "p_is_1" else sc ** 2)
        norm = 1.0 if fault == "no_normaliser" \
            else jnp.sum(a, axis=-1, keepdims=True) + 1e-6
        heads.append(a @ v[:, g] / norm)
    return x + jnp.concatenate(heads, axis=-1) @ lp["wo"]


REFERENCE_FAULTS = ("no_gate", "gate_a_query_head", "no_normaliser",
                    "p_is_1", "no_qk_norm", "no_rotation",
                    "decay_one_position_late")
PROGRAM_FAULTS = ("phi_without_the_off_diagonal_weight", "bfloat16_state",
                  "unit_qk_gains", "no_gate_bias")


def test_the_faulty_form_without_a_fault_is_the_reference(model):
    cfg, params, _, _ = model
    lp = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                      params["dense_retention_blocks"])
    x = jnp.asarray(np.random.default_rng(51).normal(size=(17, 32)),
                    jnp.float32)
    np.testing.assert_allclose(np.asarray(_faulty_retention(x, lp, cfg)),
                               np.asarray(ref.power_retention(x, lp, cfg)),
                               atol=1e-6)


@pytest.mark.parametrize("fault", REFERENCE_FAULTS + PROGRAM_FAULTS)
def test_what_the_tolerance_catches(model, devices, fault, monkeypatch):
    """Each way the program could be this model almost: program and
    reference move apart by far more than LOGIT_TOL (2e-3 or more)."""
    cfg, params, srv, placed = model
    toks = _tokens(29)
    wrong = params
    if fault == "phi_without_the_off_diagonal_weight":
        monkeypatch.setattr(inference, "_OFF_DIAGONAL", 1.0)
    elif fault == "unit_qk_gains":
        wrong = afmoe._groups_with(params, afmoe._unit("q_norm", "k_norm"))
    elif fault == "no_gate_bias":
        wrong = afmoe._groups_with(params, lfm2._leaf("bg", jnp.zeros_like))
    if fault in ("bfloat16_state", "phi_without_the_off_diagonal_weight"):
        # through chunks, where a state is carried: in a pool kept in
        # bfloat16 (the state, and the rows beside it), or in float32
        srv.__dict__.pop("_ret_logits_fns", None)  # traced under the fault
        cache = MoESlotCache.empty(
            cfg, 1, 2, MAX_SEQ, dtype=jnp.bfloat16
            if fault == "bfloat16_state" else jnp.float32)
        got, _ = _serve(srv, placed, cache, toks)
        got = got.astype(np.float32)
        srv.__dict__.pop("_ret_logits_fns", None)
    else:
        fresh = _server(devices, cfg)  # traced under the fault
        got, _ = fresh._forward(
            fresh.shard_params(wrong), jnp.asarray(toks)[None, None],
            mi.MoEKVCache.empty(cfg, 1, 1, MAX_SEQ), "sort")
        got = np.asarray(got)[0, 0]
    if fault in REFERENCE_FAULTS:
        monkeypatch.setattr(ref, "power_retention", functools.partial(
            _faulty_retention, fault=fault))
    want = np.asarray(ref.forward_logits(params, toks, cfg))
    assert np.abs(got - want).max() > 100 * LOGIT_TOL, fault


# -- the scopes in the compiled programs -------------------------------------

RET_SCOPES = ("ret.qkv", "ret.gate", "ret.intra", "ret.state", "ret.out",
              "embed", "ffn.dense", "head")


@pytest.fixture(scope="module")
def program_text(model):
    cfg, params, srv, placed = model
    return {name: low.compile().as_text()
            for name, low in hybrid._lowered_programs(srv, placed).items()}


@pytest.mark.parametrize("scope", RET_SCOPES)
@pytest.mark.parametrize("program", ("decode", "prefill"))
def test_programs_carry_their_scopes(program_text, program, scope):
    assert f"/{scope}/" in program_text[program], (
        f"{scope} is in no op_name of the compiled {program} program")


@pytest.mark.parametrize("scope", ("attn.", "conv.", "moe."))
@pytest.mark.parametrize("program", ("decode", "prefill"))
def test_programs_carry_nothing_of_another_block(program_text, program,
                                                 scope):
    assert f"/{scope}" not in program_text[program]


# -- the five accepted descriptions are what they were -----------------------

# sha256 (16 hex digits) of each accepted tiny preset's seeded leaves and of
# its lowered decode and pool-wide prefill programs. Leaves and decode were
# computed on the parent of the PR that added retention layers (227c55c) and
# equal on its tree: the operator, the state group and the ``valid`` count
# cost a description without them not one operation. The prefill digests
# are PR 48's, which made the head at ONE position a row the only prefill
# head (``inference.prefill_slots``) and so changed all five on purpose. A
# PR that changes an accepted program on purpose writes the new digests here.
ACCEPTED = {
    "mixtral": (lambda: MoEServeConfig(**latent.GQA),
                "a50740ad09eb5ce6", "cd015405e57683e5", "9e9eb1509842f2b7"),
    "glm4_moe_lite": (lambda: MoEServeConfig(**latent.LATENT),
                      "9793a0a1f08dd700", "adf0e0363716091d",
                      "2c5e282c0a475060"),
    "mimo_v2_flash": (lambda: MoEServeConfig(**hybrid.HYBRID),
                      "442d55cd1a0f1355", "a95582a83b6cb367",
                      "d2d75a3471ae2ea9"),
    "afmoe": (lambda: MoEServeConfig.from_hf(afmoe.TINY, **afmoe.OVERRIDES),
              "8ec3830eb98d6533", "287dd824d75a6684", "c2f1737ade3e19cd"),
    "lfm2_moe": (lambda: MoEServeConfig.from_hf(lfm2.TINY, **lfm2.OVERRIDES),
                 "d3a3903f3f1b09bd", "3595552de7c09d5b", "ebc8e67c92dc7e4a"),
}


def _digest(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


@functools.lru_cache(maxsize=None)
def _accepted_digests(preset):
    """{"leaves" | "decode" | "prefill": digest} of one accepted preset."""
    cfg = ACCEPTED[preset][0]()
    with jax.default_matmul_precision("default"):
        srv = _server(jax.devices(), cfg)
        params = jax.jit(lambda key: init_params(key, cfg))(
            jax.random.PRNGKey(13))
        h = hashlib.sha256()
        leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        for path, leaf in sorted(leaves, key=lambda kv: str(kv[0])):
            h.update(str(path).encode())
            h.update(np.asarray(leaf.astype(jnp.float32)).tobytes())
        lowered = hybrid._lowered_programs(srv, srv.shard_params(params))
        return {"leaves": h.hexdigest()[:16],
                **{name: _digest(low.as_text().encode())
                   for name, low in lowered.items()}}


@pytest.mark.parametrize("what", ("leaves", "decode", "prefill"))
@pytest.mark.parametrize("preset", sorted(ACCEPTED))
def test_an_accepted_preset_is_what_the_parent_had(devices, preset, what):
    want = dict(zip(("leaves", "decode", "prefill"), ACCEPTED[preset][1:]))
    assert _accepted_digests(preset)[what] == want[what]


def test_dataclass_fields_are_what_they_were():
    """No field of the description was added for this family: a retention
    layer is a value of ``layer_kinds``, a model with no expert layer a
    value of ``first_k_dense``."""
    names = {f.name for f in dataclasses.fields(MoEServeConfig)}
    assert "layer_kinds" in names and not {n for n in names
                                           if "retention" in n}
