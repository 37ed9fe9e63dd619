"""KV-cache inference for the dense model: prefill / decode / generate.

This is the serving-side path the reference's P2P pillar exists to feed
(KV-cache transfer between prefill and decode workers — README.md:18,
ep/bench/vllm/disagg_proxy.py): the cache produced by :func:`prefill` is a
plain pytree of arrays, registered and moved by ``uccl_tpu.p2p`` (see
examples/disagg_kv.py), then consumed by :func:`decode_step` on another worker.

Single-device (per-replica) implementation with static-shape caches so every
decode step hits the same compiled executable.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from uccl_tpu.models.dense import DenseConfig
from uccl_tpu.models.layers import rms_norm, rope
from uccl_tpu.models.sampling import (
    broadcast_params, sample_tokens, sample_window,
)
from uccl_tpu.utils.lru import LRUFnCache


class KVCache(NamedTuple):
    k: jax.Array  # [L, B, S_max, Hkv, D]
    v: jax.Array  # [L, B, S_max, Hkv, D]
    length: jax.Array  # [] int32 — valid prefix length

    @staticmethod
    def empty(cfg: DenseConfig, batch: int, max_seq: int, dtype=jnp.float32):
        shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
        return KVCache(
            jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), jnp.zeros((), jnp.int32)
        )


def _causal_mask(length, sq: int, smax: int):
    """[Sq, Smax] (scalar ``length``) or [B, Sq, Smax] (per-slot [B]
    ``length``) bool: queries at positions [length, length+Sq) attend at or
    before their own position."""
    kpos = jnp.arange(smax)
    if jnp.ndim(length) == 0:
        qpos = length + jnp.arange(sq)[:, None]  # [Sq, 1]
        return kpos[None, :] <= qpos  # attend at or before own position
    qpos = length[:, None] + jnp.arange(sq)[None, :]  # [B, Sq]
    return kpos[None, None, :] <= qpos[:, :, None]  # [B, Sq, Smax]


def _mask_scores(s, length):
    """Causal mask over cached positions for scores ``s`` [B, H, Sq, Smax]
    of queries at positions [length, length+Sq). ``length`` is a scalar or
    [B] (see :func:`_attend_cached`)."""
    mask = _causal_mask(length, *s.shape[-2:])
    if jnp.ndim(length) == 0:
        return jnp.where(mask[None, None], s, -1e30)
    return jnp.where(mask[:, None], s, -1e30)


def _attend_cached(q, k_cache, v_cache, length, cfg: DenseConfig):
    """q: [B, Sq, H, D] at positions [length, length+Sq); cache: [B, Smax, Hkv, D].
    Masked attention over the cache prefix + the new causal block.

    Query head ``j`` reads KV head ``j // G`` (``G = H / Hkv``): the queries
    are grouped ``[B, Sq, Hkv, G, D]`` and contracted against the cache AS
    IT LIES in the pool — no KV head is repeated, and the program holds no
    array of ``G`` times the cache's size (a repeat writes and reads back
    ``G`` x the pool: 7.4 of the 15.9 ms of Mixtral's ``[16, 1]`` decode
    program over a ``[16, 4096, 8, 128]`` float32 layer on a v5e). On the
    chip each contraction compiles to ONE fusion whose operand is the pool's
    own array, read once at 83-90 % of the HBM's bandwidth (0.36-0.40 ms for
    the 0.27 GB; all of ``attn.*`` 0.80 ms, the program 9.29), for every
    ``Sq`` alike: decode, chunks and verify windows share the form (PERF.md
    section 6, PR 37). At ``G = 1`` (multi-head attention) the group axis
    has length one and the program is the ungrouped one.

    ``Sq = 1`` asks for float32-accurate products. Ungrouped, a single-row
    product never reached the MXU (the compiler keeps it on the VPU in
    float32, whatever precision is asked); with ``G`` rows a KV head it
    does, and at the default precision its operands would be rounded to
    bfloat16. That flips greedy tokens at near-ties often enough to put the
    99th-percentile gap to a full-float32 reference at 0.17 of a limit of
    0.25 in one of 16 seeds (under 0.02 in 12 of 12 with this), so decode
    keeps the precision it had; wider ``Sq`` were MXU products before and
    stay at the default.

    ``length`` is a scalar (one shared prefix — the one-shot path) or [B]
    per-sequence prefixes (the slot-pool serving path): the mask math is the
    same, only its batch rank differs, so both paths produce bit-identical
    rows for equal per-row (length, prefix) — the serving engine's oracle
    guarantee rests on this."""
    b, sq, h, d = q.shape
    hkv = cfg.n_kv_heads
    precision = lax.Precision.HIGHEST if sq == 1 else None
    with jax.named_scope("attn.core"):
        qg = q.reshape(b, sq, hkv, h // hkv, d)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache, precision=precision,
                       preferred_element_type=jnp.float32)
        s = s / jnp.sqrt(jnp.float32(d))
        # [B, Hkv, G, Sq, K] is [B, H, Sq, K] with head j = (j // G, j % G)
        p = jax.nn.softmax(
            _mask_scores(s.reshape(b, h, sq, -1), length), axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd",
                       p.reshape(s.shape).astype(v_cache.dtype), v_cache,
                       precision=precision)
        return o.reshape(b, sq, h, d)


def _attend_latent(q_lat, q_rope, ckv_cache, kr_cache, length, scale):
    """Latent (absorbed) attention over the cache: every head scores against
    the SAME cached row, its 512-wide compressed part through the query
    already multiplied into latent space and its one shared rotary key, and
    sums the compressed rows themselves — a step reads 576 numbers a cached
    position and never expands the pool into per-head keys and values.
    q_lat: [B, Sq, H, R]; q_rope: [B, Sq, H, Dr]; caches [B, Smax, R] and
    [B, Smax, Dr]; ``length`` as in :func:`_attend_cached`. Returns the
    attended latent rows [B, Sq, H, R] (the caller applies W_uv)."""
    b, sq, nh, r = q_lat.shape
    smax = ckv_cache.shape[1]
    with jax.named_scope("attn.core"):
        # heads ride the query axis ([B, Sq*H, .]): the cache row is shared
        # by every head, so scores and values are plain batched products
        # with the cached positions minor — no transposed score layout
        # (which cost the prefill program a 16,383-wide reduce-window a
        # layer, 64 ms, on the chip: PERF.md section 6, PR 26)
        s = jnp.einsum("bmc,bkc->bmk", q_lat.reshape(b, sq * nh, r),
                       ckv_cache, preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bmr,bkr->bmk", q_rope.reshape(b, sq * nh, -1),
                           kr_cache, preferred_element_type=jnp.float32)
        # one mask row per query position, shared by its heads
        mask = _causal_mask(length, sq, smax)[..., None, :]
        s = jnp.where(mask, (s * jnp.float32(scale)).reshape(
            b, sq, nh, smax), -1e30).reshape(b, sq * nh, smax)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bmk,bkc->bmc", p.astype(ckv_cache.dtype), ckv_cache)
        return o.reshape(b, sq, nh, r)


def layer_kinds(cfg) -> Tuple[str, ...]:
    """The per-layer operator kinds ("full" | "window" attention, "conv":
    a gated short convolution, "retention": power retention) of a
    description that names them (``cfg.layer_kinds``); empty where every
    layer is the same attention block (the dense stack, Mixtral, the latent
    family)."""
    return tuple(getattr(cfg, "layer_kinds", ()) or ())


def indexed_groups(names):
    """``[(name, index among the equal names before it)]``: where layer i
    lies in the stacked group its name names."""
    seen: Dict[str, int] = {}
    out = []
    for name in names:
        out.append((name, seen.get(name, 0)))
        seen[name] = seen.get(name, 0) + 1
    return out


def cache_groups(cfg):
    """Where each layer's cached rows live: ``[(group, index in group)]`` by
    layer. Layers of one attention kind share one stacked cache array (a
    GROUP: its own row shape and, in the slot pool, its own number of rows
    a slot — all positions for "full", a ring for "window" and "conv":
    :data:`RING_GROUPS`; a "retention" group has NO position axis, a slot's
    state being one matrix a layer: :data:`STATE_GROUPS`). A description
    without layer kinds has the one group ``None``: the plain stacked
    array."""
    return indexed_groups(layer_kinds(cfg) or [None] * cfg.n_layers)


def group_array(pool, group):
    """A cache group's array of ``pool``: the plain stacked array (group
    ``None``) or ``pool[group]`` — ``None`` where the group keeps no such
    array (a conv group has no value rows: :func:`kv_row_shapes`), a tuple
    of arrays, one a layer, for a state group (:data:`STATE_GROUPS`)."""
    return pool if group is None else pool.get(group)


def _with_group(pool, group, new):
    """``pool`` with the group's array replaced by ``new`` (``None``: the
    group keeps no such array, and ``pool`` is what it was)."""
    if new is None:
        return pool
    return new if group is None else {**pool, group: new}


# The cache groups a slot keeps as a RING by position (position p at row
# p % rows), each with the name of what ``cfg.reach(group)`` counts: a
# window layer's query at p reads positions (p - window, p], a conv layer's
# filter (p - taps, p]. THE invariant, one for both: rows >= reach - 1 + S
# for every write of S positions, so a write at p .. p+S-1 only overwrites
# positions <= p - reach, which nothing at or after p reads. That is what
# makes a chunk after a chunk, a right-padded last chunk and a verify
# window's rejected rows harmless in a ring exactly as they are in a flat
# pool: what they leave behind lies past the slot's length and is rewritten
# before anything reads it, and what they overwrote was already out of reach.
RING_GROUPS = {"window": "window", "conv": "taps"}

# The cache groups that hold a STATE, not rows by position: a slot's array
# of such a group has no position axis (a "retention" layer's ``S [Hkv, F,
# Dv]`` in ``k`` and ``z [Hkv, F]`` in ``v``: :func:`_power_retention`), and
# the group is a TUPLE of arrays ``[B, *state]``, one a layer, not one
# stacked array: a program handed its pool donated advances a layer's state
# in the layer's own buffer, where the update of one layer of a stacked
# array made the compiler copy the whole group in and out (4.5 GB at
# Brumby's size on a described v5e).
# THE invariant: a state group's slot holds the state AT the slot's length;
# a call advances it by its real positions and nothing rolls it back. So a
# call is told how many of a row's positions are real (``valid``:
# :func:`_forward_slots`), a call that starts at position 0 reads a zero
# state whatever the slot holds, and what needs a position's rows back
# (:func:`pool_traits`: ``rows_stay``) is refused until slots keep snapshots. A row
# without a real position keeps its state bit for bit, and at no cost: the
# operator's row loop visits the rows that have one, where they lie in the
# layer's array, and a slot it does not visit is never read
# (:func:`_power_retention`).
STATE_GROUPS = ("retention",)


def has_state_group(cfg) -> bool:
    """Whether a layer of the description keeps a state group."""
    return any(kind in STATE_GROUPS for kind in layer_kinds(cfg))


def ring_rows_for(reach: int, widest: int) -> int:
    """Rows a ring needs to take a write of ``widest`` positions under
    THE invariant of :data:`RING_GROUPS`: ``reach - 1 + widest``."""
    return reach - 1 + widest


def rows_stay(groups) -> str:
    """The sentence that says why a pool of cache groups (``groups``: a
    description's layer kinds, not empty) keeps a slot's rows to itself."""
    if set(groups) & set(STATE_GROUPS):
        return (
            "a pool with a state group keeps ONE state a slot and layer, "
            "the state AT the slot's length (a retention layer's matrix), "
            "and no row by position, so nothing can hand a prefix of it to "
            "another slot, a tier or a peer, or roll it back, until slots "
            "keep snapshots: ")
    return (
        "a pool with ring groups keeps a slot's last reach - 1 positions of "
        "its window and conv layers (window - 1, taps - 1) in a ring and "
        "nothing older, so a slot's rows cannot be handed to another slot, "
        "a tier or a peer as a prefix: ")


class PoolTraits(NamedTuple):
    """What a slot pool can do, in the serving layer's words: the ONE answer
    the engine, the pool's row shims and the disaggregated wire refuse from
    (:func:`pool_traits`). The defaults: one stacked array, every position
    kept, which can do everything."""

    # why a slot's rows cannot leave it or enter it as a prefix
    # (``export_rows`` / ``import_rows`` / ``copy_prefix``); None: they can
    rows_stay: Optional[str] = None
    rollback: bool = True  # a cursor rollback undoes a write
    projections: bool = True  # every layer has the adapters' (wq, wv)
    # the widest write one call may make (None: any; bounded, a whole prompt
    # cannot go in one) and the ring that sets it: (group, what its reach
    # counts, rows, reach)
    widest_write: Optional[int] = None
    tightest: Optional[Tuple[str, str, int, int]] = None
    window: int = 0  # the window layers' reach (0 without them)


def pool_traits(cfg) -> PoolTraits:
    """The :class:`PoolTraits` of a description's slot pool: unrestricted
    without layer kinds (the dense stack, Mixtral, the latent family); with
    them the pool is cache groups and a slot's rows stay, a ring group
    (:data:`RING_GROUPS`) bounds the widest write by its rows, a state group
    (:data:`STATE_GROUPS`) cannot be rolled back, and a "conv" or a state
    layer has no (wq, wv)."""
    kinds = layer_kinds(cfg)
    state = has_state_group(cfg)
    rings = [(cfg.ring_rows(g) - ring_rows_for(cfg.reach(g), 0),
              (g, RING_GROUPS[g], cfg.ring_rows(g), cfg.reach(g)))
             for g in RING_GROUPS if g in kinds]
    widest, tightest = min(rings, key=lambda r: r[0], default=(None, None))
    return PoolTraits(
        rows_stay=rows_stay(kinds) if kinds else None, rollback=not state,
        projections=not state and "conv" not in kinds,
        widest_write=widest, tightest=tightest,
        window=cfg.window if "window" in kinds else 0)


def _ret_block(d: int) -> int:
    """A head's block width in the feature map of :func:`_phi`: 16 where
    the head's size is a multiple of 32 (128: 8 blocks, 36 block pairs),
    else two halves (a tiny head of 8: 2 blocks of 4)."""
    return 16 if d % 32 == 0 else max(d // 2, 1) if d % 2 == 0 else d


def retention_features(d: int) -> int:
    """Numbers :func:`_phi` makes of a head of ``d``: every pair of blocks
    (a <= b) as a full ``block x block`` outer product — 9,216 of a head of
    128 (the 8,256 distinct monomials, with each diagonal block's
    off-diagonal products held twice)."""
    blk = _ret_block(d)
    n = d // blk
    return blk * blk * n * (n + 1) // 2


def kv_row_shapes(cfg, kind=None):
    """Per-position shapes of the two cache arrays of one layer, from the
    model description: ``gqa`` keeps keys and values ``[Hkv, D]`` each;
    ``mla`` keeps ONE latent row as its normalised compressed part
    ``[kv_lora_rank]`` (in ``k``) and the shared rotary key
    ``[qk_rope_dim]`` (in ``v``) — no value row at all. A description with
    layer kinds gives the row of ``kind``, FLAT: that kind's KV heads x
    ``head_dim`` numbers of keys, KV heads x ``v_head_dim`` of values. Flat,
    because the chip's default layout of ``[.., S, 4, 192]`` puts the
    positions minor (no lane padding of 192), and a program that scatters
    rows then copies the whole group in and out (2 x 2.5 ms a program on
    the chip: PERF.md section 6, PR 36); ``[.., S, 768]`` is row-major as
    it stands. The decode program also CONTRACTS the row flat
    (:func:`_grouped_attention`): viewed ``[.., S, Hkv, D]``, a layer
    sliced out of a stacked group of two or more is wanted positions-minor
    again, and the compiler makes a bfloat16 slice and a copy of the whole
    layer for it every step (PERF.md section 6, PR 41). A "conv" layer's
    position is ONE row, the ``dim`` numbers of its gated input ``y = b *
    u`` (:func:`_short_conv`), and it keeps no second array: ``None``. A
    "retention" layer keeps no position at all: the two shapes are its
    slot's STATE, ``S [Hkv, F, Dv]`` and ``z [Hkv, F]`` (:data:`STATE_GROUPS`:
    one array a layer; :func:`retention_features`)."""
    if getattr(cfg, "attn", "gqa") == "mla":
        return (cfg.kv_lora_rank,), (cfg.qk_rope_dim,)
    if kind == "conv":
        return (cfg.dim,), None
    if kind == "retention":
        feats = retention_features(cfg.head_dim)
        return (cfg.n_kv_heads, feats, cfg.v_head_dim or cfg.head_dim), \
            (cfg.n_kv_heads, feats)
    if layer_kinds(cfg):
        hkv = cfg.kv_heads(kind)
        return (hkv * cfg.head_dim,), \
            (hkv * (cfg.v_head_dim or cfg.head_dim),)
    return (cfg.n_kv_heads, cfg.head_dim), (cfg.n_kv_heads, cfg.head_dim)


def kv_wire_dims(cfg):
    """(heads, width) of the two EQUAL arrays one layer's cached position
    leaves a pool as (``export_rows``): gqa's own ``[Hkv, D]``; a latent
    row's two halves, ``[1, (kv_lora_rank + qk_rope_dim) / 2]`` each. A
    pool of cache groups (layer kinds) has no one row to put on a wire."""
    stay = pool_traits(cfg).rows_stay
    if stay:
        raise ValueError(stay + "the disaggregated wire format describes "
                         "one row shape for every layer")
    k_row, v_row = kv_row_shapes(cfg)
    if k_row == v_row:
        return k_row
    return 1, (math.prod(k_row) + math.prod(v_row)) // 2


def _gqa_attention(x, lp, k_pool, v_pool, positions, length, write, cfg,
                   lora=None):
    """One layer's grouped-query attention with its residual: project,
    rotate, ``write`` the new rows into the layer-stacked cache arrays,
    attend over this layer's rows of them. ``write(pool, new)`` returns the
    updated stacked array and the layer's view of it ``[B, Smax, ...]``
    (the callers own where a layer's rows live: :func:`_forward_cached`,
    :func:`_forward_slots`). ``lora(h, target)`` adds the per-slot low-rank
    delta to the query/value projections. Returns (x', k_pool', v_pool')."""
    b, s, _ = x.shape
    d = cfg.head_dim
    with jax.named_scope("attn.qkv"):
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q2 = h @ lp["wq"].astype(h.dtype)
        v2 = h @ lp["wv"].astype(h.dtype)
        if lora is not None:
            q2 = q2 + lora(h, "wq")
            v2 = v2 + lora(h, "wv")
        q = q2.reshape(b, s, cfg.n_heads, d)
        kk = (h @ lp["wk"].astype(h.dtype)).reshape(
            b, s, cfg.n_kv_heads, d)
        v = v2.reshape(b, s, cfg.n_kv_heads, d)
        q = rope(q, positions, cfg.rope_theta)
        kk = rope(kk, positions, cfg.rope_theta)
    with jax.named_scope("attn.kv_write"):
        k_pool, k_cache = write(k_pool, kk)
        v_pool, v_cache = write(v_pool, v)
    attn = _attend_cached(q, k_cache, v_cache, length, cfg)
    with jax.named_scope("attn.out"):
        x = x + attn.reshape(b, s, -1) @ lp["wo"].astype(attn.dtype)
    return x, k_pool, v_pool


def _mla_attention(x, lp, ckv_pool, kr_pool, positions, length, write,
                   cfg, lora=None):
    """One layer's multi-head latent attention with its residual, in the
    absorbed form: queries through a low-rank bottleneck (``wq_a`` -> norm
    -> ``wq_b``), keys and values through ONE shared one (``wkv_a`` -> norm)
    whose output plus a single shared rotary key is all the cache holds.
    ``W_kvb = [W_uk | W_uv]`` per head never touches the cache: W_uk is
    multiplied into the query (``q_nope W_uk^T``), W_uv into the attended
    latent rows — the same mathematics as expanding every cached row into
    per-head keys and values, at 576 numbers read a cached position."""
    if lora is not None:
        raise ValueError("LoRA adapters target the gqa projections "
                         "(wq/wv); latent attention has none")
    b, s, _ = x.shape
    nh, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    with jax.named_scope("attn.latent_q"):
        cq = rms_norm(h @ lp["wq_a"].astype(h.dtype), lp["q_a_norm"],
                      cfg.norm_eps)
    with jax.named_scope("attn.latent_kv"):
        ckr = h @ lp["wkv_a"].astype(h.dtype)
        ckv = rms_norm(ckr[..., :r], lp["kv_a_norm"], cfg.norm_eps)
        # one rotary key shared by every head
        kr = rope(ckr[..., None, r:], positions, cfg.rope_theta)[..., 0, :]
    with jax.named_scope("attn.qkv"):
        q = (cq @ lp["wq_b"].astype(cq.dtype)).reshape(b, s, nh, dn + dr)
        q_rope = rope(q[..., dn:], positions, cfg.rope_theta)
        w_kvb = lp["wkv_b"].astype(q.dtype).reshape(r, nh, dn + dv)
        q_lat = jnp.einsum("bshd,chd->bshc", q[..., :dn], w_kvb[..., :dn])
    with jax.named_scope("attn.kv_write"):
        ckv_pool, ckv_cache = write(ckv_pool, ckv)
        kr_pool, kr_cache = write(kr_pool, kr)
    o_lat = _attend_latent(q_lat, q_rope, ckv_cache, kr_cache, length,
                           1.0 / math.sqrt(dn + dr))
    with jax.named_scope("attn.out"):
        o = jnp.einsum("bshc,chd->bshd", o_lat, w_kvb[..., dn:])
        x = x + o.reshape(b, s, nh * dv) @ lp["wo"].astype(o.dtype)
    return x, ckv_pool, kr_pool


# One batch row of scores at a time once all rows together pass this many
# numbers (0.5 GB of float32): the pool-wide prefill rung over a 16,384-row
# pool is [8, 64, 128, 16384] = 4.3 GB at once and 0.54 GB a row.
_SCORES_AT_ONCE = 1 << 27


def _project_qkv(x, lp, positions, cfg, kind):
    """What every operator of a description with layer kinds starts with:
    the normed input ``h``, and of it the queries ``[B, S, H, D]``, keys
    ``[B, S, Hkv, D]`` and values ``[B, S, Hkv, Dv]`` of a layer of
    ``kind`` — values scaled by ``value_scale``, each query and key head
    RMS-normed where the layer has ``q_norm`` / ``k_norm``, then rotated
    unless the kind is in ``cfg.unrotated``. Returns (h, q, k, v)."""
    b, s, _ = x.shape
    nh, d = cfg.n_heads, cfg.head_dim
    dv = cfg.v_head_dim or d
    hkv = cfg.kv_heads(kind)
    theta = cfg.theta(kind)
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    q = (h @ lp["wq"].astype(h.dtype)).reshape(b, s, nh, d)
    kk = (h @ lp["wk"].astype(h.dtype)).reshape(b, s, hkv, d)
    v = (h @ lp["wv"].astype(h.dtype)).reshape(b, s, hkv, dv)
    if cfg.value_scale != 1.0:
        v = v * jnp.asarray(cfg.value_scale, v.dtype)
    if "q_norm" in lp:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        kk = rms_norm(kk, lp["k_norm"], cfg.norm_eps)
    if kind not in cfg.unrotated:
        q = rope(q, positions, theta, cfg.rotary_dim)
        kk = rope(kk, positions, theta, cfg.rotary_dim)
    return h, q, kk, v


def _grouped_attention(x, lp, k_pool, v_pool, positions, length, write, cfg,
                       lora=None, *, kind):
    """One layer's grouped-query attention of a description with layer
    kinds, with its residual — ONE function for "full" and "window" layers,
    which differ in numbers the description gives by kind (KV heads, theta,
    whether the softmax has a sink) and in the mask.

    Queries are ``n_heads`` x ``head_dim``; keys ``Hkv`` x ``head_dim``,
    values ``Hkv`` x ``v_head_dim`` scaled by ``value_scale``; the leading
    ``rotary_dim`` numbers of each query and key head rotate. Query head j
    reads KV head ``j // (n_heads / Hkv)``: the queries are reshaped
    ``[B, Sq, Hkv, G, D]`` against the cache ``[B, K, Hkv, .]`` — the pool
    is never repeated. Scores ``q.k / sqrt(head_dim)``.

    One query a row (``Sq == 1``: the decode program, a verify window of
    one, a one-shot decode step) contracts the rows FLAT instead, ``[B, K,
    Hkv * D]`` as ``write`` hands them over: head (h, g)'s query stands in
    KV head h's ``D`` columns of a ``Hkv * D``-wide row of zeros, scores are
    ``bmc,bkc->bmk`` over all ``n_heads`` rows, values ``bmk,bkc->bmc``
    against ``[B, K, Hkv * Dv]``, and each head keeps its own ``Dv`` columns
    (what :func:`_attend_latent` does with its one shared row). The
    products with zeros are exact, so mask, sink, softmax and the operands'
    rounding are the grouped form's. Why: on the chip the view ``[B, K, Hkv,
    D]`` of a layer sliced out of a stacked group of two or more layers is
    wanted in another layout (positions minor), and the compiler
    materialises it — a ``slice`` and a ``copy`` ``bf16[1, B, K, Hkv * D]``
    of the WHOLE layer, keys and values, every step: 5.06 of the 6.30 ms of
    MiMo-V2-Flash's two full layers and 3.19 of the 4.86 ms of Trinity's
    four rings (ledger, PR 40), whatever the row's width, a dynamic index or
    float32 products (PERF.md section 6, PR 41). Flat, the program reads the
    rows once where they lie, no temporaries: 2.12 and 2.54 ms on the chip
    (my chip runs, PR 41). It costs ``Hkv`` times the multiply-adds: 43
    GFLOP a step (0.2 ms of the MXU's peak) beside a 1.34 GB read (1.79 ms,
    92 % of the HBM's bandwidth) there — hidden; at a chunk's ``Sq`` = 128
    it is 128 times that and not hidden, and a compact prefill program's
    copy is of one slot's rows only, so every wider call keeps the grouped
    form. The rule is the call's shape; nothing sets it.

    The cache rows ``K`` of this layer's group are a RING: position ``q``
    lives at row ``q % K``, so for a query at position ``p`` row ``r`` holds
    position ``p - ((p - r) mod K)`` — the newest position at or before
    ``p`` that maps to it (whatever was written there later, by a padded
    chunk or a rejected draft, is a position past ``p`` and reads as one
    ``K`` older: masked). A "full" layer's group has a row for every
    position (``K = S_max``) and the formula reduces to ``q = r``, ``r <=
    p``; a "window" layer sees ``max(0, p - window + 1) <= q <= p``. A kind
    in ``cfg.sink`` adds one column to the softmax that holds the head's
    learned logit ``lp["sink"]`` and carries no value:
    ``P_k = exp(s_k) / (exp(sink) + sum_j exp(s_j))``.

    What else a description's layers may carry is read from the layer's own
    leaves and costs a layer without them nothing: ``q_norm`` / ``k_norm``
    (each query and key head RMS-normed before the rotation), ``wg`` (the
    heads' output times ``sigmoid(h wg)`` before the output projection) and
    ``ln1_post`` (the branch's output normed before it joins the residual).
    A kind in ``cfg.unrotated`` carries no rotary embedding.

    ``write(pool, new)`` as in :func:`_gqa_attention`. Returns (x', k_pool',
    v_pool')."""
    if lora is not None:
        raise ValueError("LoRA adapters target one (wq, wv) shape for every "
                         "layer; layer kinds have their own")
    b, s, _ = x.shape
    nh, d = cfg.n_heads, cfg.head_dim
    dv = cfg.v_head_dim or d
    hkv = cfg.kv_heads(kind)
    with jax.named_scope("attn.qkv." + kind):
        h, q, kk, v = _project_qkv(x, lp, positions, cfg, kind)
    with jax.named_scope("attn.kv_write." + kind):
        # rows are cached flat (:func:`kv_row_shapes`): [.., Hkv * D]
        k_pool, k_cache = write(k_pool, kk.reshape(b, s, hkv * d))
        v_pool, v_cache = write(v_pool, v.reshape(b, s, hkv * dv))
    rows = k_cache.shape[1]
    flat = s == 1
    if not flat:
        k_cache = k_cache.reshape(b, rows, hkv, d)
        v_cache = v_cache.reshape(b, rows, hkv, dv)
    qpos = positions if positions.ndim == 2 else positions[None]  # [B|1, Sq]
    sink = None
    if kind in cfg.sink:
        sink = lp["sink"].astype(jnp.float32).reshape(hkv, nh // hkv, 1)

    def core(qg, kc, vc, qp):
        """[B', Sq, Hkv, G, D] queries over the layer's rows: [B', K, Hkv,
        .], or where ``flat`` [B', K, Hkv * .] as they lie."""
        if flat:
            # [Hkv, 1, Hkv, 1]: KV head h' is query head (h, g)'s own; its
            # query stands in that head's columns of a flat row
            own = jnp.eye(hkv, dtype=bool)[:, None, :, None]
            qf = jnp.where(own, qg[:, 0, :, :, None], 0)
            sc = jnp.einsum("bmc,bkc->bmk", qf.reshape(-1, nh, hkv * d), kc,
                            preferred_element_type=jnp.float32)
            sc = sc.reshape(-1, hkv, nh // hkv, 1, rows)
        else:
            sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kc,
                            preferred_element_type=jnp.float32)
        sc = sc / jnp.sqrt(jnp.float32(d))
        held = qp[..., None] - jnp.mod(qp[..., None] - jnp.arange(rows), rows)
        seen = held >= 0
        if kind == "window":
            seen = seen & (held > qp[..., None] - cfg.window)
        sc = jnp.where(seen[:, None, None], sc, -1e30)
        top = jnp.max(sc, axis=-1)
        if sink is not None:
            top = jnp.maximum(top, sink)
        e = jnp.exp(sc - top[..., None])
        den = jnp.sum(e, axis=-1)
        if sink is not None:
            den = den + jnp.exp(sink - top)
        p = (e / den[..., None]).astype(vc.dtype)
        if not flat:
            return jnp.einsum("bhgqk,bkhd->bqhgd", p, vc)
        # every head against the whole flat row; it keeps its own columns
        o = jnp.einsum("bmk,bkc->bmc", p.reshape(-1, nh, rows), vc)
        o = o.reshape(-1, hkv, nh // hkv, hkv, dv)
        return jnp.sum(jnp.where(own, o, 0), axis=3)[:, None]

    with jax.named_scope("attn.core." + kind):
        qg = q.reshape(b, s, hkv, nh // hkv, d)
        qp = jnp.broadcast_to(qpos, (b, s))
        if b > 1 and b * nh * s * rows > _SCORES_AT_ONCE:
            attn = lax.map(
                lambda a: core(*(t[None] for t in a))[0],
                (qg, k_cache, v_cache, qp))
        else:
            attn = core(qg, k_cache, v_cache, qp)
    if "wg" in lp:
        with jax.named_scope("attn.gate." + kind):
            gate = jax.nn.sigmoid(h @ lp["wg"].astype(h.dtype))
            attn = attn * gate.reshape(b, s, hkv, nh // hkv, dv)
    with jax.named_scope("attn.out." + kind):
        out = attn.reshape(b, s, nh * dv) @ lp["wo"].astype(attn.dtype)
        if "ln1_post" in lp:
            out = rms_norm(out, lp["ln1_post"], cfg.norm_eps)
        x = x + out
    return x, k_pool, v_pool


def _short_conv(x, lp, k_pool, v_pool, positions, length, write, cfg,
                lora=None):
    """One layer's gated short convolution with its residual — the operator
    of a "conv" layer, with the signature the layer loops call an attention
    by. ``[b | c | u] = h w_in`` (three ``dim``-wide parts of the normed
    input's projection), ``y = b * u``, a causal depthwise filter of
    ``cfg.conv_taps`` taps over ``y`` along the positions (``w_conv [dim,
    taps]``, the last tap on the position itself), ``out = (c * z) w_out``.
    No scores, no softmax, no value row: what a slot keeps of the layer is
    ``y`` at its last ``taps - 1`` positions.

    It keeps them BY POSITION, as attention keeps its rows: ``write`` puts
    the call's ``y`` rows into the layer's group (position p at row ``p %
    rows``: a ring in the slot pool, ``max_seq`` rows on the one-shot path)
    and the call's first position reads the ``taps - 1`` positions before it
    from there; the positions of the call itself are read from ``y`` as it
    was just computed, which is what the ring now holds of them. So a chunk
    after a chunk, a padded last chunk and a verify window's rejected rows
    leave the state exact by the ring's invariant (:data:`RING_GROUPS`):
    rollback is the cursor, and no program hands back a snapshot. A tap
    that reaches before position 0 reads ZERO whatever the ring holds (a
    slot's previous occupant left rows there): from ``positions``, not from
    a scrub at admission. Returns (x', k_pool', v_pool as given: None)."""
    if lora is not None:
        raise ValueError("LoRA adapters target the attention projections "
                         "(wq/wv); a conv layer has none")
    b, s, _ = x.shape
    taps = cfg.conv_taps
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    with jax.named_scope("conv.in_proj"):
        gate_b, gate_c, u = jnp.split(h @ lp["w_in"].astype(h.dtype), 3,
                                      axis=-1)
        y = gate_b * u
    with jax.named_scope("conv.state"):
        k_pool, ring = write(k_pool, y)
        rows = ring.shape[1]
        first = jnp.broadcast_to(positions[..., :1], (b, 1))
        back = first - jnp.arange(taps - 1, 0, -1)  # [B, taps - 1] positions
        before = jnp.take_along_axis(ring, (back % rows)[..., None], axis=1)
        before = jnp.where((back >= 0)[..., None], before, 0)
        ys = jnp.concatenate([before.astype(y.dtype), y], axis=1)
    with jax.named_scope("conv.mix"):
        w = lp["w_conv"].astype(y.dtype)
        z = sum(w[:, j] * ys[:, j:j + s] for j in range(taps))
        out = gate_c * z
    with jax.named_scope("conv.out_proj"):
        x = x + out @ lp["w_out"].astype(out.dtype)
    return x, k_pool, v_pool


_OFF_DIAGONAL = math.sqrt(2.0)  # a product x_i x_j, i != j, stands for two
_RET_EPS = 1e-6  # the normaliser's


def _phi(x):
    """The degree-2 symmetric feature map of the heads ``x [..., D]``,
    scaled so that ``phi(q) . phi(k) = (q . k)^2``: the head in blocks of
    :func:`_ret_block`, every pair of blocks ``a <= b`` as the full outer
    product of block a with block b — times sqrt(2) where ``a < b``, that
    pair standing for (b, a) too. Built block row by block row: block a
    against the head's tail from a on, ``[..., blk, D - a]`` flattened. No
    gather, and :func:`retention_features` numbers a head."""
    d = x.shape[-1]
    blk = _ret_block(d)
    parts = []
    for a in range(0, d, blk):
        weight = jnp.where(jnp.arange(d - a) < blk, 1.0, _OFF_DIAGONAL)
        tail = x[..., a:] * weight.astype(x.dtype)
        parts.append((x[..., a:a + blk, None] * tail[..., None, :]).reshape(
            x.shape[:-1] + (blk * (d - a),)))
    return jnp.concatenate(parts, axis=-1)


def _power_retention(x, lp, k_pool, v_pool, positions, length, write, cfg,
                     lora=None, *, valid=None):
    """One layer's power retention (degree 2) with its residual — the
    operator of a "retention" layer, with the signature the layer loops call
    an attention by. Queries, keys and values as a grouped attention layer's
    (:func:`_project_qkv`: QK-norm, rotation; query head j reads KV head ``j
    // G``), and one gate a KV head and position, ``log g = log sigmoid(h wg
    + bg)``. With ``G(i, j) = exp(sum of log g over j+1 .. i)``:

        a(i, j) = G(i, j) (q_i . k_j)^2 / D,   j <= i
        y_i = sum_j a(i, j) v_j / (sum_j a(i, j) + 1e-6)

    and the heads' ``y`` through ``wo``. No softmax, and no row a position:
    what a slot keeps of the layer is a STATE (:data:`STATE_GROUPS`), ``S =
    sum_j G(t, j) phi(k_j) v_j^T`` ``[Hkv, F, Dv]`` in the layer's ``k``
    group and ``z = sum_j G(t, j) phi(k_j)`` ``[Hkv, F]`` in its ``v`` group
    (:func:`_phi`; the queries carry the ``1 / sqrt(D)``, so ``phi(q) .
    phi(k)`` is ``a`` without its decay), float32 both.

    A call of ``S`` positions a row is ONE chunk: with ``c`` the running
    sum of ``log g`` over the call and ``(S0, z0)`` the state it found,

        y_i = [sum_{j<=i in the call} a(i, j) v_j + e^{c_i} phi(q_i)^T S0]
              / [sum a(i, j) + e^{c_i} phi(q_i)^T z0 + 1e-6]
        S'  = e^{c_last} S0 + sum_j e^{c_last - c_j} phi(k_j) v_j^T;  z' alike

    which at ``S == 1`` is the recurrence ``S' = g S0 + phi(k) v^T``. The
    products that read the state run at the default precision like every
    other product here; the state accumulates and is stored in float32.

    ``write(pool, None)`` hands over the layer's array of EVERY slot's
    state with the places of the call's rows in it, ``(at [B], states
    [B_slots, ...])`` (no row is written first, as an attention's are; row
    r is slot ``at[r]``: itself pool-wide, the slot a compact call names),
    and ``write(pool, new)`` replaces the array. ``valid [B]``: how many of
    a row's positions are REAL (None: all). A position at or past it
    contributes no key and no decay, so a right-padded last chunk leaves
    the state exactly what the unpadded prompt leaves, and a row with none
    keeps the state it had, bit for bit. A call that starts at position 0
    (``length``) reads a ZERO state whatever the pool holds (a slot's
    previous occupant left its own there; it enters with the coefficient 0,
    so what it left is finite).

    The operator passes over the state of the rows that have a real
    position, and of no other, and a state never leaves the layer's array.
    A call of ONE row (the one-row prefill rung, the one-shot path at batch
    1) advances its row where it lies. Every call of more rows goes row by
    row through a loop whose trip count is the NUMBER of rows with a real
    position, read from ``valid`` inside the program (the rows ordered
    real-first): a visited row's state is read from and put back into the
    carried array at its slot — the product ``phi(q) S`` and the update
    slice the array themselves, three passes over the row's state and no
    copy of it —, and a slot the loop never visits (a masked row's, a
    padding row's, one no row names) is NEVER READ and never written: its
    state is what it was bit for bit at no cost; such a row's numerator and
    normaliser stay zero (``y = 0``: finite, and nobody keeps its logits).
    So a decode step over 6 of 16 slots moves 6 rows' state, not 16, and a
    compact call gathers and scatters nothing (PERF.md section 6, PR 46).
    A visit makes its row's feature maps itself (all rows' ``phi(q)`` at
    once would be 3 GB at ``[16, 128]``; made before the loop at decode,
    where they are small, they cost a step 0.9 ms more than the hundred
    small operations a visit they saved: measured, PERF.md).
    Returns (x', k_pool', v_pool')."""
    if lora is not None:
        raise ValueError("LoRA adapters beside retention layers are not "
                         "built: a slot's state is a sum over its prefix "
                         "under ONE set of projections")
    b, s, _ = x.shape
    nh, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dv = cfg.v_head_dim or d
    with jax.named_scope("ret.qkv"):
        h, q, kk, v = _project_qkv(x, lp, positions, cfg, "retention")
        qg = (q * (1.0 / math.sqrt(d))).reshape(b, s, hkv, nh // hkv, d)
    real = jnp.ones((b, s), bool) if valid is None \
        else jnp.arange(s)[None, :] < valid[:, None]
    with jax.named_scope("ret.gate"):
        log_g = jax.nn.log_sigmoid(
            h @ lp["wg"].astype(h.dtype) + lp["bg"].astype(h.dtype))
        # c_i = log G(i, the position before the call)  [B, S, Hkv]
        cum = jnp.cumsum(jnp.where(real[..., None], log_g, 0.0), axis=1)
        kk = jnp.where(real[..., None, None], kk, 0.0)
    # every slot's state of the layer, and where the call's rows lie in it
    at, s0 = write(k_pool, None)
    _, z0 = write(v_pool, None)
    # a call from position 0 finds a ZERO state: by the coefficients the
    # found state enters with, not by a pass over the state itself
    held = jnp.broadcast_to(length != 0, (b,))[:, None, None]
    # a row with no real position keeps its state as it found it
    moved = jnp.any(real, axis=1)  # [B]

    def core(qr, kr, vr, cr, hr, sr, zr):
        """[B', S, Hkv, G, D] queries over the call's own keys and the
        state [B', Hkv, F, Dv] it found (``hr`` [B', 1, 1]: it counts):
        (numerator [B', S, Hkv, G, Dv], normaliser [B', S, Hkv, G], S',
        z')."""
        with jax.named_scope("ret.intra"):
            sc = jnp.einsum("bqhgd,bkhd->bhgqk", qr, kr,
                            preferred_element_type=jnp.float32)
            ch = jnp.moveaxis(cr, 1, -1)  # [B', Hkv, S]
            since = jnp.where(jnp.tril(jnp.ones((s, s), bool)),
                              ch[..., :, None] - ch[..., None, :], -jnp.inf)
            a = jnp.exp(since)[:, :, None] * sc * sc
            num = jnp.einsum("bhgqk,bkhd->bqhgd", a, vr)
            den = jnp.moveaxis(jnp.sum(a, axis=-1), -1, 1)
        with jax.named_scope("ret.state"):
            pq, pk = _phi(qr), _phi(kr)
            # G(i, the position before the call), 0 where none was
            before = jnp.where(hr, jnp.exp(cr), 0.0)
            num = num + before[..., None, None] * jnp.einsum(
                "bshgf,bhfv->bshgv", pq, sr)
            den = den + before[..., None] * jnp.einsum(
                "bshgf,bhf->bshg", pq, zr)
            last = cr[:, -1]  # [B', Hkv]
            pk = pk * jnp.exp(last[:, None] - cr)[..., None]  # G(last, j)
            s_new = before[:, -1, :, None, None] * sr \
                + jnp.einsum("bshf,bshv->bhfv", pk, vr)
            z_new = before[:, -1, :, None] * zr + jnp.sum(pk, axis=1)
        return num, den, s_new, z_new

    rows = (qg, kk, v, cum, held)

    def row_of(t, p):
        return lax.dynamic_slice_in_dim(t, p, 1, axis=0)

    def put(t, new, p):
        return lax.dynamic_update_slice_in_dim(t, new.astype(t.dtype), p,
                                               axis=0)

    if b == 1:
        sr, zr = row_of(s0, at[0]), row_of(z0, at[0])
        num, den, s_r, z_r = core(*rows, sr, zr)
        with jax.named_scope("ret.state"):
            s_r, z_r = lax.optimization_barrier((
                jnp.where(moved[0], s_r, sr), jnp.where(moved[0], z_r, zr)))
            s_new, z_new = put(s0, s_r, at[0]), put(z0, z_r, at[0])
    else:
        with jax.named_scope("ret.state"):
            # the rows with a real position first, in their order
            order = jnp.argsort(~moved, stable=True)

            def one(i, carry):
                """The ``i``-th row with a real position, its state read
                from and put back into the carried arrays where it lies:
                no second copy of all the rows' states, and a row the loop
                never reaches is neither read nor written."""
                s_all, z_all, num, den = carry
                r = order[i]
                p = at[r]
                n_r, d_r, s_r, z_r = core(
                    *(row_of(t, r) for t in rows), row_of(s_all, p),
                    row_of(z_all, p))
                return (put(s_all, s_r, p), put(z_all, z_r, p),
                        put(num, n_r, r), put(den, d_r, r))

            s_new, z_new, num, den = lax.fori_loop(
                0, jnp.sum(moved), one, (
                    s0, z0, jnp.zeros((b, s, hkv, nh // hkv, dv), jnp.float32),
                    jnp.zeros((b, s, hkv, nh // hkv), jnp.float32)))
    with jax.named_scope("ret.state"):
        # the advanced arrays stay values of their own, under this scope:
        # fused with what follows the layer loop (the shard's leading axis
        # put back) a one-row call's update reads as under no scope at all
        # and its temporaries nearly double (0.20 -> 0.38 GB compiled for a
        # described v5e); a row loop's result is the loop's own
        s_new, z_new = lax.optimization_barrier((s_new, z_new))
        k_pool, _ = write(k_pool, s_new)
        v_pool, _ = write(v_pool, z_new)
    with jax.named_scope("ret.out"):
        y = num / (den[..., None] + _RET_EPS)
        x = x + y.reshape(b, s, nh * dv) @ lp["wo"].astype(y.dtype)
    return x, k_pool, v_pool


def _attention_of(cfg, i: int = 0, valid=None):
    """The operator of layer ``i`` of a model description — an attention, or
    where the layer's kind says so a short convolution or a power retention:
    ``cfg.attn`` ("gqa" | "mla"; descriptions without the field are gqa),
    and where the description has layer kinds, :func:`_short_conv` for a
    "conv" layer, :func:`_power_retention` for a "retention" layer (handed
    ``valid``, the rows' counts of real positions, which no other operator
    needs) and the grouped attention at layer ``i``'s kind for every
    other."""
    kinds = layer_kinds(cfg)
    if kinds:
        if kinds[i] == "conv":
            return _short_conv
        if kinds[i] == "retention":
            return functools.partial(_power_retention, valid=valid)
        return functools.partial(_grouped_attention, kind=kinds[i])
    kind = getattr(cfg, "attn", "gqa")
    if kind == "gqa":
        return _gqa_attention
    if kind == "mla":
        return _mla_attention
    raise ValueError(f"unknown attention kind {kind!r} (want 'gqa' or 'mla')")


def _layer_place(params, i: int, cfg=None):
    """Where layer ``i``'s leaves lie: (its group's stacked leaves, its
    index there). Layers come in stacked groups: the leading
    ``dense_blocks`` (where the model has a dense-FFN prefix: their leading
    dim is how many) and then ``blocks``; a description with layer kinds
    (``cfg.param_groups()``) names each layer's group and its index there."""
    if cfg is not None and layer_kinds(cfg):
        group, j = cfg.param_groups()[i]
        return params[group], j
    dense = params.get("dense_blocks")
    if dense is not None:
        n = jax.tree.leaves(dense)[0].shape[0]
        if i < n:
            return dense, i
        i -= n
    return params["blocks"], i


def _layer_params(params, i: int, cfg=None):
    """Layer ``i``'s leaves, sliced out of their group's stack."""
    group, j = _layer_place(params, i, cfg)
    return jax.tree.map(lambda a: a[j], group)


def _dense_ffn(h2, lp):
    act = jax.nn.silu(h2 @ lp["w_gate"].astype(h2.dtype)) * (
        h2 @ lp["w_up"].astype(h2.dtype)
    )
    return act @ lp["w_down"].astype(act.dtype)


def _embed(params, tokens, cfg, dtype):
    """The embedding's rows of ``tokens`` in the activations' ``dtype``,
    times the description's ``embed_scale`` where it has one."""
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
        scale = getattr(cfg, "embed_scale", 1.0)
        return x if scale == 1.0 else x * jnp.asarray(scale, dtype)


def _ffn_half(x, lp, cfg, ffn, **slot_kw):
    """The FFN half of a layer with its residual: ``ffn(h2, lp)`` (the dense
    SwiGLU without one) of the normed input; a layer with ``ln2_post`` norms
    the branch's output before it joins the residual. ``slot_kw``: what the
    slot loop hands the hook beside them (:func:`_forward_slots`)."""
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    out = _dense_ffn(h2, lp) if ffn is None else ffn(h2, lp, **slot_kw)
    if "ln2_post" in lp:
        with jax.named_scope("ffn.post_norm"):
            out = rms_norm(out, lp["ln2_post"], cfg.norm_eps)
    return x + out


def _head(x, params, cfg):
    """The output norm and the logits. A tree without a ``head`` leaf is a
    model whose head is TIED to its embedding: the rows are contracted
    with ``embed`` as it lies (no transposed copy is kept)."""
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if "head" not in params:
            return jnp.einsum("...h,vh->...v", x.astype(jnp.float32),
                              params["embed"].astype(jnp.float32))
        return x.astype(jnp.float32) @ params["head"].astype(jnp.float32)


def _state_write(gi: int, at):
    """The ``write`` a state group's layer ``gi`` is handed its operator
    with (:data:`STATE_GROUPS`; ``pool`` the group's tuple of arrays, one a
    layer): ``write(pool, None)`` gives the layer's own array ``pool[gi]``
    ``[B_slots, *state]`` with the places ``at`` [B] of the call's rows in
    it, ``write(pool, new)`` the pool with that array replaced by ``new`` —
    the buffer the operator advanced its rows in, pool-wide and compact
    alike: only the slots of rows with a real position were touched, and no
    other was read (a masked row's, a padding row's clamped one, a slot no
    row names)."""
    def write(pool, new):
        if new is None:
            return at, pool[gi]
        return pool[:gi] + (new,) + pool[gi + 1:], None

    return write


def _forward_cached(
    params, tokens, cache: KVCache, cfg, ffn=None
) -> Tuple[jax.Array, KVCache]:
    """Run tokens [B, S] starting at cache.length; returns (logits, cache').

    ``ffn(h2, layer_params) -> [B, S, H]`` overrides the dense SwiGLU block
    — the hook the MoE serving loop uses so the attention/KV-cache math
    exists exactly once (uccl_tpu/models/moe_inference.py). The attention
    kind comes from the model description (:func:`_attention_of`). The
    layer-stacked cache arrays are written in place, as
    :func:`_forward_slots` writes the pool's: carried through the layer
    loop, one ``dynamic_update_slice`` a layer, attention over a slice.
    Where the description has layer kinds, ``cache.k`` / ``cache.v`` are
    ``{group: array}`` (:func:`cache_groups`), every group ``S_max`` rows
    here: the one-shot path keeps a window layer's every position. A state
    group (:data:`STATE_GROUPS`) has no rows: its ``write`` hands the
    layer's state over with the rows' places in it (``new`` None: row b at
    b) or replaces it."""
    b, s = tokens.shape
    k, v = cache.k, cache.v
    x = _embed(params, tokens, cfg, jax.tree.leaves(k)[0].dtype)
    positions = cache.length + jnp.arange(s)
    for i, (group, gi) in enumerate(cache_groups(cfg)):
        def write(pool, new, gi=gi):
            pool = lax.dynamic_update_slice(
                pool, new[None],
                (gi, 0, cache.length) + (0,) * (new.ndim - 2))
            return pool, pool[gi]

        if group in STATE_GROUPS:
            write = _state_write(gi, jnp.arange(b))

        lp = _layer_params(params, i, cfg)
        x, nk, nv = _attention_of(cfg, i)(
            x, lp, group_array(k, group), group_array(v, group), positions,
            cache.length, write, cfg)
        k, v = _with_group(k, group, nk), _with_group(v, group, nv)
        x = _ffn_half(x, lp, cfg, ffn)
    logits = _head(x, params, cfg)
    return logits, KVCache(k, v, cache.length + s)


def prefill(params, tokens, cfg: DenseConfig, max_seq: int) -> Tuple[jax.Array, KVCache]:
    """Process the prompt; returns (last-position logits [B, V], warm cache)."""
    if tokens.shape[1] > max_seq:
        raise ValueError(
            f"prompt length {tokens.shape[1]} exceeds max_seq {max_seq}"
        )
    cache = KVCache.empty(cfg, tokens.shape[0], max_seq, params["embed"].dtype)
    logits, cache = _forward_cached(params, tokens, cache, cfg)
    return logits[:, -1], cache


def decode_step(params, token, cache: KVCache, cfg: DenseConfig):
    """token: [B] — one autoregressive step. Returns (logits [B, V], cache')."""
    logits, cache = _forward_cached(params, token[:, None], cache, cfg)
    return logits[:, 0], cache


def decode_step_elastic(params, token, ekv, cfg: DenseConfig):
    """One autoregressive step over an :class:`uccl_tpu.ep.elastic.ElasticKVCache`.

    Same contract as :func:`decode_step`, but the KV context comes from the
    elastic cache (hot blocks in HBM, cold blocks staged from host memory),
    so decode length is bounded by host memory, not HBM. Returns
    logits [B, V]; the cache is updated in place with the new token's KV.

    The gathered context is a dense [L, B, S_blocks, Hkv, D] view whose
    first ``length`` positions are valid — position ``length`` itself is the
    partial block's next empty slot, which is exactly where
    :func:`_forward_cached` writes the new token. The dense forward path is
    therefore reused verbatim (one compiled step per block-count bucket),
    so the elastic path inherits every dense-path improvement by
    construction.
    """
    k_ctx, v_ctx, length = ekv.kv()
    view = KVCache(k_ctx, v_ctx, jnp.asarray(length, jnp.int32))
    logits, view = _forward_cached(params, token[:, None], view, cfg)
    sl = (slice(None), slice(None), slice(length, length + 1))
    ekv.append_tokens(view.k[sl], view.v[sl])
    return logits[:, 0]


# -- slot-pool serving primitives ------------------------------------------
#
# The continuous-batching engine (uccl_tpu/serving) holds ONE fixed
# [B_slots, S_max] KV cache and reuses rows ("slots") across requests, so
# every sequence sits at its own length and joins/leaves the batch at its own
# time. The primitive that needs is a masked forward: tokens land at per-slot
# positions, cache writes are gated per slot (an inactive or padded slot's
# rows never change), and attention masks per slot. Everything else —
# attention math, rope, the layer stack — is the one-shot code above; rows
# with equal (prefix, length) are bit-identical between the two paths, which
# is what makes the engine's exact-oracle guarantee provable by test rather
# than by tolerance.


class SlotKVCache(NamedTuple):
    k: jax.Array  # [L, B_slots, S_max, Hkv, D]
    v: jax.Array  # [L, B_slots, S_max, Hkv, D]
    lengths: jax.Array  # [B_slots] int32 — per-slot valid prefix

    @staticmethod
    def empty(cfg: DenseConfig, n_slots: int, max_seq: int,
              dtype=jnp.float32) -> "SlotKVCache":
        shape = (cfg.n_layers, n_slots, max_seq, cfg.n_kv_heads, cfg.head_dim)
        return SlotKVCache(
            jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
            jnp.zeros((n_slots,), jnp.int32),
        )

    # -- slot KV export/import views (the disaggregation surface) ----------
    #
    # One slot's rows as host arrays, and the inverse: these are what
    # crosses the p2p wire between a prefill worker and a decode worker
    # (uccl_tpu/serving/disagg.py), and what the prefix-reuse cache copies
    # between slots. Raw float32 rows — bit-exact by construction, so the
    # disaggregated continuation is the oracle's continuation.
    #
    # All three go through module-level jitted helpers whose slot indices
    # and lengths are TRACED scalars, and whole slot rows move at the
    # fixed [L, S_max, Hkv, D] shape: one compiled program per pool shape,
    # instead of one per (slot, offset, length) combination that baked
    # constants would cost. Rows beyond the stamped length carry donor/
    # stale data and are dead by the masked-attention invariant (attention
    # stops at the slot's length; resumed prefill writes [start, start+C)
    # before attending to it).

    def export_rows(self, slot: int, lo: int, hi: int):
        """Host copies of rows [lo, hi): (k, v) each [L, hi-lo, Hkv, D]."""
        import numpy as np

        k_row, v_row = _slot_row_export(self.k, self.v, jnp.int32(slot))
        return (np.asarray(k_row[:, lo:hi]), np.asarray(v_row[:, lo:hi]))

    def import_rows(self, slot: int, k_rows, v_rows, *,
                    length: int) -> "SlotKVCache":
        """Rows [0, n) of ``slot`` replaced by ``k_rows``/``v_rows``
        ([L, n, Hkv, D]); the slot's length becomes ``length``. Callers on
        a hot path should pass full S_max rows (the decode worker's mirror
        does) so every import shares one compiled program."""
        import numpy as np

        smax = self.k.shape[2]
        n = k_rows.shape[1]
        if n < smax:  # pad to the row shape with dead rows
            pad = [(0, 0), (0, smax - n), (0, 0), (0, 0)]
            k_rows = np.pad(np.asarray(k_rows), pad)
            v_rows = np.pad(np.asarray(v_rows), pad)
        k, v, lengths = _slot_row_import(
            self.k, self.v, self.lengths, jnp.int32(slot),
            jnp.asarray(k_rows, self.k.dtype),
            jnp.asarray(v_rows, self.v.dtype), jnp.int32(length),
        )
        return SlotKVCache(k, v, lengths)

    def copy_prefix(self, dst: int, src: int, n: int) -> "SlotKVCache":
        """Copy slot ``src``'s row into slot ``dst`` and stamp dst's
        length to n (the prefix-cache hit path: dst resumes prefill at
        position n; src rows past n are dead weight in dst, never
        readable)."""
        k, v, lengths = _slot_row_copy(
            self.k, self.v, self.lengths, jnp.int32(dst), jnp.int32(src),
            jnp.int32(n),
        )
        return SlotKVCache(k, v, lengths)


@jax.jit
def _slot_row_export(k, v, slot):
    """One slot's full KV row [L, S_max, Hkv, D] (slot is traced: one
    compiled gather per pool shape)."""
    return (lax.dynamic_index_in_dim(k, slot, axis=1, keepdims=False),
            lax.dynamic_index_in_dim(v, slot, axis=1, keepdims=False))


@jax.jit
def _slot_row_import(k, v, lengths, slot, k_row, v_row, length):
    k = lax.dynamic_update_slice(k, k_row[:, None], (0, slot, 0, 0, 0))
    v = lax.dynamic_update_slice(v, v_row[:, None], (0, slot, 0, 0, 0))
    return k, v, lengths.at[slot].set(length)


@jax.jit
def _slot_row_copy(k, v, lengths, dst, src, n):
    k_row = lax.dynamic_index_in_dim(k, src, axis=1, keepdims=True)
    v_row = lax.dynamic_index_in_dim(v, src, axis=1, keepdims=True)
    k = lax.dynamic_update_slice(k, k_row, (0, dst, 0, 0, 0))
    v = lax.dynamic_update_slice(v, v_row, (0, dst, 0, 0, 0))
    return k, v, lengths.at[dst].set(n)


def _lora_delta(h, table, ids, layer):
    """Batched per-slot fused LoRA delta (ISSUE 18): gather each slot's
    rank-padded (A, B) pair from the stacked tables by adapter row id and
    add ``(h @ A) @ B`` beside the base matmul. ``table``: (A [L, T, H,
    R_max], B [L, T, R_max, out]); ``ids``: [B] int32 — row 0 is all
    zeros, so adapter-free slots compute an exact-0.0 delta (the zero-rank
    fast path sharing one compiled program with mixed-rank neighbors)."""
    a, bb = table
    al = a[layer][ids].astype(h.dtype)   # [B, H, R_max]
    bl = bb[layer][ids].astype(h.dtype)  # [B, R_max, out]
    return jnp.einsum("bsr,bro->bso", jnp.einsum("bsh,bhr->bsr", h, al), bl)


def _forward_slots(
    params, tokens, cache: SlotKVCache, start, write_mask, cfg, ffn=None,
    adapters=None, adapter_ids=None, slots=None, valid=None, head_at=None,
) -> Tuple[jax.Array, SlotKVCache]:
    """Masked batched forward: tokens [B, S] at positions [start_b, start_b+S).

    ``write_mask`` [B] bool gates every cache write — a masked slot's KV rows
    come back unchanged (its write positions are redirected out of bounds and
    dropped), so mid-decode neighbors are never corrupted by a prefill or by
    an idle slot's dummy token. Lengths are NOT advanced here; the callers
    own the per-slot length bookkeeping. ``ffn`` is the same dense-block
    override hook as :func:`_forward_cached` (the MoE serving loop uses it),
    handed two things more here, ``ffn(h2, lp, rows=, place=)``: ``rows`` =
    ``write_mask``, the rows whose results the caller keeps (an idle slot's
    dummy token is not one), and ``place`` = (the layer's group of stacked
    leaves, its index there), what ``lp`` was sliced from, for a block that
    reads a leaf where it lies. A block may ignore both; the dense SwiGLU
    (``ffn=None``) does.

    The pool is written IN PLACE: the layer-stacked ``[L, B_slots, S_max,
    ...]`` arrays are carried through the layer loop, layer ``i``'s new rows
    go in by one scatter, and attention reads layer ``i`` as a slice of the
    array just written — nothing is sliced out, collected and re-stacked, so
    a program that is handed its pool donated (every serving program is:
    serving/backend.py) returns the buffers it was given with B x S rows a
    layer changed. Where the description has layer kinds the pool is
    ``{group: array}`` (:func:`cache_groups`): a "full" group ``[L_full,
    B_slots, S_max, ...]`` and the ring groups (:data:`RING_GROUPS`: "window"
    ``[L_win, B_slots, ring, ...]``, "conv" ``[L_conv, B_slots, ring,
    dim]`` in ``k`` alone), each carried and written the same way; a state
    group (:data:`STATE_GROUPS`: "retention", a tuple of ``L_ret`` arrays
    ``[B_slots, Hkv, F, Dv]`` in ``k`` and ``[B_slots, Hkv, F]`` in ``v``)
    has no position axis: a row's whole state is read, advanced and put
    back where it lies in the layer's own array, pool-wide and compact
    alike; a masked row's is never read and left as it is bit for bit
    (:func:`_power_retention`). Such a layer is told how many of a row's
    ``S`` positions are REAL, ``valid`` [B] (None: all ``S`` of a row in
    ``write_mask``; none of another, whatever is passed): a padded position
    that advanced a state would corrupt it, where a padded row by position
    lands past the slot's length and is harmless. No other operator is
    handed it.
    ``head_at`` [B] (None: every position): the one position a row whose
    logits the caller reads; the logits are then ``[B, 1, V]``.

    ``slots`` ([R] int32, B == R) makes the rows COMPACT: row r is slot
    ``slots[r]`` of the pool — its new rows are written there, and attention
    reads that slot's rows as a slice of the layer (R slices side by side);
    ``None`` is row b = slot b. A padding row names an index past the pool:
    its write is dropped (it reads the last slot, to no effect). Real rows
    name distinct slots.

    ``adapters`` = ``{"wq": (A, B), "wv": (A, B)}`` stacked LoRA tables +
    ``adapter_ids`` [B] fuse a per-slot low-rank delta onto the query and
    value projections (:func:`_lora_delta`); None leaves the base program
    byte-identical to the pre-adapter form.
    """
    b, s = tokens.shape
    k, v = cache.k, cache.v
    groups = cache_groups(cfg)
    # rows a slot has in the plain pool, or in the full group of one with
    # cache groups: S_max (ring groups are written below)
    flat = k if groups[0][0] is None else k.get("full")
    smax = flat.shape[2] if flat is not None else 0
    x = _embed(params, tokens, cfg, jax.tree.leaves(k)[0].dtype)
    positions = start[:, None] + jnp.arange(s)[None, :]  # [B, S]
    # masked slots write at index smax → dropped by the scatter; rows beyond
    # the cache end (a bucket overhanging S_max) drop the same way
    pos_write = jnp.where(write_mask[:, None], positions, smax)
    bidx = (jnp.arange(b) if slots is None else slots)[:, None]
    pos_of = {None: pos_write, "full": pos_write}
    for group in RING_GROUPS:
        if not any(g == group for g, _ in groups):
            continue
        # a ring of ``rows``: position p lives at row p % rows, and the
        # invariant (RING_GROUPS) is asked of each ring group by its own
        # rows and reach
        rows, need = k[group].shape[2], ring_rows_for(cfg.reach(group), s)
        if rows < need:
            raise ValueError(
                f"a {group} layer's ring of {rows} rows cannot take a write "
                f"of {s} positions: it must hold {RING_GROUPS[group]} - 1 + "
                f"the widest write = {need} ({group}_ring), or the write "
                f"would overwrite positions still to be read")
        pos_of[group] = jnp.where(write_mask[:, None], positions % rows, rows)
    if has_state_group(cfg):  # a masked row has no real position
        valid = jnp.where(write_mask, s if valid is None else valid, 0)

    def rows_of(pool, gi):
        """Layer ``gi``'s rows (or state) of the call's slots."""
        if slots is None:
            return pool[gi]
        # a dynamic slice a row, straight from the stacked array (its
        # start clamps into the pool): one row is read where it lies;
        # a gather of whole slot rows cost the two-row program 1.6 ms
        # a layer on the chip
        zeros = (0,) * (pool.ndim - 2)
        return jnp.concatenate([
            lax.dynamic_slice(pool, (gi, slots[r]) + zeros,
                              (1, 1) + pool.shape[2:])[0]
            for r in range(b)])

    for i, (group, gi) in enumerate(groups):
        pos = pos_of.get(group)

        def write(pool, new, gi=gi, pos=pos):
            pool = pool.at[gi, bidx, pos].set(new, mode="drop")
            return pool, rows_of(pool, gi)

        if group in STATE_GROUPS:
            write = _state_write(gi, bidx[:, 0])

        lp = _layer_params(params, i, cfg)
        lora = None
        if adapters is not None:
            def lora(h, target, i=i):
                return _lora_delta(h, adapters[target], adapter_ids, i)
        x, nk, nv = _attention_of(cfg, i, valid)(
            x, lp, group_array(k, group), group_array(v, group), positions,
            start, write, cfg, lora=lora)
        k, v = _with_group(k, group, nk), _with_group(v, group, nv)
        x = _ffn_half(x, lp, cfg, ffn, rows=write_mask,
                      place=_layer_place(params, i, cfg))
    if head_at is not None:
        # picked by a masked sum, exact (the other positions add zeros), and
        # not by a gather: x then ends in a fusion as it does under a head
        # over every position, where a gather of the residual stream made
        # the TPU compiler plan a one-row rung's fast memory anew and drop
        # three layers' weight prefetches (+1.1 ms a chunk step on
        # ``.long-short``: PERF.md section 6, PR 48)
        pick = jnp.arange(s)[None, :] == head_at[:, None]
        x = jnp.sum(jnp.where(pick[:, :, None], x, 0), axis=1, keepdims=True)
    logits = _head(x, params, cfg)
    return logits, SlotKVCache(k, v, cache.lengths)


def _flat_extra(sampling, adapters, adapter_ids) -> list:
    """Flatten a slot program's optional sampled/adapted arguments into
    positional jit args of fixed count: 5 per-slot sampling arrays, then 4
    adapter tables + per-slot row ids. The compiled-program cache keys carry
    the two presence flags, so the argmax/-adapter-free programs stay
    byte-identical."""
    extra = []
    if sampling is not None:
        extra.extend(sampling)
    if adapters is not None:
        extra.extend([adapters["wq"][0], adapters["wq"][1],
                      adapters["wv"][0], adapters["wv"][1], adapter_ids])
    return extra


def _split_extra(rest, sampled: bool, adapted: bool):
    """Inverse of :func:`_flat_extra` inside a jitted program: returns
    (sampling tuple | None, adapter tables | None, adapter ids | None)."""
    rest = list(rest)
    samp = None
    if sampled:
        samp = tuple(rest[:5])
        rest = rest[5:]
    adp = ids = None
    if adapted:
        adp = {"wq": (rest[0], rest[1]), "wv": (rest[2], rest[3])}
        ids = rest[4]
    return samp, adp, ids


def prefill_slots(
    params, tokens, prompt_lens, new_mask, cache: SlotKVCache,
    cfg: DenseConfig, start=None, sampling=None, adapters=None,
    adapter_ids=None, slots=None, ffn=None,
) -> Tuple[jax.Array, SlotKVCache]:
    """Masked batched prefill of newly admitted slots — resumable.

    tokens: [B_slots, S] prompt windows right-padded to S (rows of slots NOT
    in ``new_mask`` are ignored); prompt_lens: [B_slots] int32 FULL prompt
    lengths; new_mask: [B_slots] bool; start: [B_slots] int32 per-slot
    offsets (None = all zeros, the whole-prompt path). Row b carries prompt
    positions [start_b, start_b+S): KV is written only there, attention
    covers [0, start_b+S) causally — chunked prefill is the same math split
    along the sequence axis, so resuming in fixed-size chunks is bit-exact
    with the one-shot prefill. Admitted slots starting at 0 overwrite their
    previous occupant from position 0 — rows beyond the new prompt are dead
    (never readable: attention stops at the slot's length, and decode
    overwrites position L before any read of L). Garbage beyond a
    non-dividing final chunk's prompt end is dead the same way.

    Returns (next token [B_slots] — meaningful only for rows whose window
    reaches the prompt end, i.e. start + S >= prompt_lens; callers ignore
    the rest — and cache with lengths set to min(start+S, prompt_lens) on
    admitted slots). The token is the greedy argmax, or — with
    ``sampling`` = per-slot ``(seeds, pos0, temp, top_p, top_k)`` arrays —
    the lockstep-keyed sample at output position ``pos0`` (the engine
    passes zeros: the first token is output index 0; ``temp <= 0`` rows
    stay greedy).

    ``slots`` ([R] int32) makes the call COMPACT: every per-slot argument
    and the returned token are [R], row r belonging to slot ``slots[r]``;
    the program runs the same forward over R rows, writes the chunk's new
    rows at ``(slots[r], start_r)`` and attends over those R slots' rows
    (:func:`_forward_slots`), so slots not named are untouched and the work
    is R rows, not the pool's. Real rows name distinct slots; a padding row
    (``new_mask`` false) names an index past the pool and is dropped.

    This is the one statement of the program: the dense stack jits it as it
    is, and the MoE stack runs it per shard with ``ffn`` the EP block
    (``MoEServer.prefill_slots``; ``cfg`` is then its ``MoEServeConfig``).
    """
    if start is None:
        start = jnp.zeros_like(prompt_lens)
    s = tokens.shape[1]
    valid = None
    if has_state_group(cfg):
        # a state advances by a window's REAL positions: the prompt's own,
        # not a last chunk's right padding
        valid = jnp.where(new_mask, jnp.clip(prompt_lens - start, 0, s), 0)
    # the head runs at ONE position a row, the one whose token is returned:
    # each slot's last valid prompt position WITHIN this window; clipped so
    # mid-prefill rows (prompt end beyond the window) gather in-bounds —
    # their token is garbage by contract and ignored by the engine
    logits, cache = _forward_slots(
        params, tokens, cache, start, new_mask, cfg, ffn=ffn,
        adapters=adapters, adapter_ids=adapter_ids, slots=slots, valid=valid,
        head_at=jnp.clip(prompt_lens - 1 - start, 0, s - 1),
    )
    last = logits[:, 0]  # [B, V]
    if sampling is None:
        tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    else:
        seeds, pos0, temp, top_p, top_k = sampling
        tok = sample_tokens(seeds, pos0, last, temp, top_p, top_k)
    # admitted rows stamp their slot's length; a row not admitted (or a
    # padding row) names an index past the pool and is dropped
    n_slots = cache.lengths.shape[0]
    rows = jnp.arange(n_slots) if slots is None else slots
    lengths = cache.lengths.at[jnp.where(new_mask, rows, n_slots)].set(
        jnp.minimum(start + s, prompt_lens), mode="drop")
    return tok, SlotKVCache(cache.k, cache.v, lengths)


def verify_slots(
    params, tokens, active, cache: SlotKVCache, cfg: DenseConfig,
    sampling=None, adapters=None, adapter_ids=None, ffn=None,
) -> Tuple[jax.Array, jax.Array, SlotKVCache]:
    """Batched draft verification — the speculative-decoding primitive,
    generalizing :func:`decode_step_slots` from one token to a window.

    tokens: [B_slots, S] where column 0 is each slot's last committed token
    and columns 1..S-1 are its k = S-1 drafted continuation tokens; active:
    [B_slots] bool. The window runs at positions [length, length+S) — the
    same masked forward a prefill chunk uses, so per-row results are
    bit-identical to S sequential decode steps over the same tokens. Row j's
    greedy argmax is the target model's next token GIVEN the window prefix
    tokens[:j+1]; greedy acceptance is the longest draft prefix that matches
    those outputs: ``n_accepted[b] = max m such that tokens[b, 1..m] ==
    argmax[b, 0..m-1]``. Active slots advance their length by
    ``n_accepted + 1`` — the accepted draft tokens plus the one
    target-computed token (correction or bonus) every verify yields.

    KV written for rejected positions [length + n_accepted + 1, length + S)
    is dead by the chunked-prefill stale-KV argument: the next window starts
    at the new length and re-writes every stale position before attending to
    it, and attention never reads past its own query position. Rollback is
    the cursor, never a cache scrub.

    With ``sampling`` = per-slot ``(seeds, pos0, temp, top_p, top_k)``
    arrays, window column ``j`` is SAMPLED under the lockstep key for
    output position ``pos0 + j`` instead of argmaxed, and the same
    acceptance rule against the sampled targets IS proper rejection
    sampling for this engine's deterministic drafters: the proposal q is a
    point mass at the draft token d, so the accept probability
    min(1, p(d)/q(d)) = p(d) — exactly the probability the lockstep
    sample t_j equals d — and conditional on rejection the already-drawn
    t_j is distributed as the residual. Committing ``tok`` rows is
    therefore bit-identical to vanilla sampled decode at equal seeds
    (docs/SERVING.md spells out the math).

    Returns (target tokens [B_slots, S], n_accepted [B_slots], cache').
    The one statement of the program, as :func:`prefill_slots` is: ``ffn``
    is the MoE stack's per-shard EP block.
    """
    logits, out = _forward_slots(
        params, tokens, cache, cache.lengths, active, cfg, ffn=ffn,
        adapters=adapters, adapter_ids=adapter_ids,
    )
    if sampling is None:
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, S]
    else:
        seeds, pos0, temp, top_p, top_k = sampling
        tok = sample_window(seeds, pos0, logits, temp, top_p, top_k)
    n_acc = greedy_acceptance(tokens, tok)
    lengths = spec_advance(cache.lengths, active, n_acc)
    return tok, n_acc, SlotKVCache(out.k, out.v, lengths)


def greedy_acceptance(tokens, tok):
    """THE acceptance rule of :func:`verify_slots`: per-row count of the
    longest draft prefix (``tokens[:, 1:]``) matching the window's own
    targets (``tok[:, :-1]``)."""
    if tokens.shape[1] <= 1:
        return jnp.zeros((tokens.shape[0],), jnp.int32)
    match = (tokens[:, 1:] == tok[:, :-1]).astype(jnp.int32)
    return jnp.sum(jnp.cumprod(match, axis=1), axis=1).astype(jnp.int32)


def spec_advance(lengths, active, n_acc):
    """Post-verify cursor advance: active slots move by the accepted
    prefix plus the one target-computed token; inactive slots hold."""
    return lengths + jnp.where(active, n_acc + 1, 0).astype(jnp.int32)


def decode_step_slots(
    params, token, active, cache: SlotKVCache, cfg: DenseConfig,
    sampling=None, adapters=None, adapter_ids=None, ffn=None,
) -> Tuple[jax.Array, SlotKVCache]:
    """One masked autoregressive step over the slot pool — the S=1 case of
    :func:`verify_slots` (no draft: nothing to accept, advance by one;
    ``ffn`` as there).

    token: [B_slots] (inactive slots feed a dummy); active: [B_slots] bool.
    Active slots write their new KV at their own length and advance by one;
    inactive slots neither write nor advance. Returns (next greedy-or-
    sampled token [B_slots], cache'); ``sampling``'s ``pos0`` is each
    slot's output index for the token this step emits.
    """
    tok, _, cache = verify_slots(params, token[:, None], active, cache, cfg,
                                 sampling=sampling, adapters=adapters,
                                 adapter_ids=adapter_ids, ffn=ffn)
    return tok[:, 0], cache


# Compiled-generate cache — the shared LRU-bounded ``_fns`` pattern
# (utils/lru.py): 16 entries comfortably cover a serving process's
# steady-state shape set while letting XLA reclaim evicted programs.
_GEN_CACHE = LRUFnCache(16)


def generate(
    params,
    prompt: jax.Array,
    cfg: DenseConfig,
    *,
    max_new_tokens: int = 32,
    max_seq: int = 256,
    sampling=None,
) -> jax.Array:
    """Greedy (or, with ``sampling``, stochastic) generation.
    prompt: [B, S] → [B, max_new_tokens].

    One jitted program (prefill + a decode ``lax.scan``), cached per
    (cfg, shapes, N): params enter as jit ARGUMENTS, so repeat calls at
    the same shapes are pure cache hits (baked-in constants would
    re-trace on every call and bloat the program).

    ``sampling`` duck-types :class:`~uccl_tpu.serving.sampling.
    SamplingParams` (seed / temperature / top_p / top_k). The scalars
    enter as TRACED jit arguments — one compiled sampled program serves
    every parameter value — and every batch row runs under the request's
    seed with lockstep keys per output index, making this the vanilla
    sampled oracle the serving engine is bit-identical to. ``sampling is
    None`` keeps the greedy program byte-identical to before."""
    if prompt.shape[1] + max_new_tokens > max_seq:
        raise ValueError(
            f"prompt {prompt.shape[1]} + new {max_new_tokens} tokens exceed "
            f"max_seq {max_seq}: the cache would overflow"
        )
    key = (repr(cfg), prompt.shape, max_new_tokens, max_seq,
           sampling is not None)

    def build():
        if sampling is None:
            def uccl_dense_generate(p, t):
                logits, cache = prefill(p, t, cfg, max_seq)

                def body(carry, _):
                    logits, cache = carry
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    logits, cache = decode_step(p, tok, cache, cfg)
                    return (logits, cache), tok

                (_, _), toks = lax.scan(
                    body, (logits, cache), None, length=max_new_tokens
                )
                return toks.T  # [B, T]

            return jax.jit(uccl_dense_generate)

        def uccl_dense_generate_sampled(p, t, seed, temp, top_p, top_k):
            b = t.shape[0]
            seeds, temps, tps, tks = broadcast_params(
                b, seed, temp, top_p, top_k
            )
            logits, cache = prefill(p, t, cfg, max_seq)

            def body(carry, i):
                logits, cache = carry
                # scan step i emits output index i: the lockstep key is a
                # pure function of (seed, i), matching the engine exactly
                tok = sample_tokens(seeds, jnp.full((b,), i, jnp.int32),
                                    logits, temps, tps, tks)
                logits, cache = decode_step(p, tok, cache, cfg)
                return (logits, cache), tok

            (_, _), toks = lax.scan(
                body, (logits, cache),
                jnp.arange(max_new_tokens, dtype=jnp.int32),
            )
            return toks.T  # [B, T]

        return jax.jit(uccl_dense_generate_sampled)

    fn = _GEN_CACHE.get(key, build)
    if sampling is None:
        return fn(params, prompt)
    return fn(params, prompt, jnp.int32(int(sampling.seed)),
              jnp.float32(sampling.temperature),
              jnp.float32(sampling.top_p), jnp.int32(sampling.top_k))
