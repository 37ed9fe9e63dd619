"""Plain reference for the ``afmoe`` block (Trinity-Large-Preview's
``config.json``): the embedding times ``sqrt(hidden_size)`` (``mup_enabled``);
grouped-query attention whose layers are of two kinds by ``layer_types`` —
``sliding_attention`` (position p sees [p - 4095, p]; queries and keys
rotated, theta ``rope_theta``, all of a head) and ``full_attention`` (causal
softmax; NO rotation) — with ``num_key_value_heads`` KV heads in both, each
query and key head RMS-normed (a learned gain of ``head_dim`` numbers)
before the rotation, and the heads' output multiplied by ``sigmoid(h Wg)``
before the output projection; SANDWICH norms: each branch's output is normed
before it joins the residual; ``num_dense_layers`` leading dense SwiGLU
layers, then layers of sigmoid-scored experts chosen with a bias and
weighted without it, renormalised, times ``route_scale``, beside
``num_shared_experts`` shared experts that every token passes; final norm,
untied head. Straight ``jax.numpy``: no cache, no ring, no grouped heads, no
dispatch, no capacity — a loop over (head, block of queries) with a
``[block, T]`` banded mask, and a loop over the experts HELD HERE, every
token through each with its gate weight (zero off its top-k). float32 at
``default_matmul_precision("highest")`` unless asked for less.

The share: the configuration's file gives ``num_experts`` as the experts
this chip holds (``first_expert`` on) of the ``router_experts`` the router
scores, and ``vocab_size`` as its slice. The reference is given the same
share: the gate runs over all ``router_experts``, the routed sum over the
held ones alone, the shared expert once a token, the logits over the slice.

It imports nothing of the program and takes nothing the program made: the
weights are drawn here from the seed by the same draws the program's
``init_params`` makes (checked against the program at a tiny size in
``chipbench/tests``), kept in bfloat16 as published, and upcast where they
are used; every norm's gain is 1 + 0.1 x a seeded normal, float32. Norm,
rotation, SwiGLU, gate and the gap of a served token are the sibling
reference's (``mimo_v2_flash.py``): the same equations. Queries go a block
at a time so that a 14,848-token request's scores are ``[block, 14848]`` a
head and not ``[14848, 14848]``.

``cfg`` is the configuration file's own dict (the published keys, with
``num_hidden_layers``, ``num_dense_layers``, ``num_experts`` and
``vocab_size`` as reduced; ``layer_types`` is read up to
``num_hidden_layers``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.mimo_v2_flash import (  # noqa: F401 — re-exported
    _rms_norm, _rope, _swiglu, gate, served_token_gaps,
)

# the program's draws (uccl_tpu/models/moe_inference.py::init_params): the
# old leaves of the group ``blocks`` from a twelve-way split of the key,
# every other leaf from the key with its group's and its own number folded in
_SPLIT = {"embed": 0, "wq": 1, "wk": 2, "wv": 3, "wo": 4, "router": 5,
          "we_gate": 6, "we_up": 7, "we_down": 8, "head": 9}
_FOLD = {"ws_gate": 25, "ws_up": 26, "ws_down": 27, "router_bias": 28,
         "w_gate": 29, "w_up": 30, "w_down": 31, "wg": 33, "q_norm": 34,
         "k_norm": 35, "ln1_post": 36, "ln2_post": 37, "ln1": 38, "ln2": 39}
_GROUP = {"blocks": 0, "dense_blocks": 64, "window_blocks": 128,
          "dense_window_blocks": 192}
_KIND = {"sliding_attention": "window", "full_attention": "full"}
BIAS_SCALE = 0.01
GAIN_SCALE = 0.1
QUERY_BLOCK = 1856  # a request is padded to a multiple of it


def layers(cfg):
    """[(group, index in group, kind, dense)] by layer: stacked groups by
    (FFN kind, attention kind), as the program stacks them."""
    seen, out = {}, []
    for i, name in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]]):
        kind, dense = _KIND[name], i < cfg["num_dense_layers"]
        group = ("dense_" if dense else "") \
            + ("window_" if kind == "window" else "") + "blocks"
        out.append((group, seen.get(group, 0), kind, dense))
        seen[group] = seen.get(group, 0) + 1
    return out


def _routed(cfg):
    return cfg.get("router_experts", cfg["num_experts"])


def init_weights(key, cfg, dtype=jnp.bfloat16):
    """The seeded weights as the program draws them: normal draws in float32
    scaled 0.02 (embedding) and 1/sqrt(fan-in) elsewhere, stored in
    ``dtype``; every norm gain of a layer 1 + 0.1 x a normal and the gate
    bias a normal of scale 0.01, float32; the final norm ones."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    e, held = _routed(cfg), cfg["num_experts"]
    f, fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    fs = cfg["num_shared_experts"] * f
    k12 = jax.random.split(key, 12)

    def rnd(name, shape, fan, fold):
        kk = k12[_SPLIT[name]] if name in _SPLIT and not fold else \
            jax.random.fold_in(key, fold + (_FOLD.get(name) or _SPLIT[name]))
        return (jax.random.normal(kk, shape, jnp.float32)
                * (1.0 / math.sqrt(fan))).astype(dtype)

    sizes = {}
    for group, _, _, dense in layers(cfg):
        sizes[group] = (sizes.get(group, (0,))[0] + 1, dense)
    out = {}
    for group, (n, dense) in sizes.items():
        fold = _GROUP[group]
        mats = {"wq": ((h, nh * d), h), "wk": ((h, hkv * d), h),
                "wv": ((h, hkv * d), h), "wo": ((nh * d, h), nh * d),
                "wg": ((h, nh * d), h)}
        if dense:
            mats.update({"w_gate": ((h, fd), h), "w_up": ((h, fd), h),
                         "w_down": ((fd, h), fd)})
        else:
            mats.update({"router": ((h, e), h), "we_gate": ((held, h, f), h),
                         "we_up": ((held, h, f), h),
                         "we_down": ((held, f, h), f),
                         "ws_gate": ((h, fs), h), "ws_up": ((h, fs), h),
                         "ws_down": ((fs, h), fs)})
        g = {name: rnd(name, (n,) + shape, fan, fold)
             for name, (shape, fan) in mats.items()}
        for name, width in (("ln1", h), ("ln2", h), ("ln1_post", h),
                            ("ln2_post", h), ("q_norm", d), ("k_norm", d)):
            g[name] = 1.0 + GAIN_SCALE * jax.random.normal(
                jax.random.fold_in(key, fold + _FOLD[name]), (n, width),
                jnp.float32)
        if not dense:
            g["router_bias"] = jax.random.normal(
                jax.random.fold_in(key, fold + _FOLD["router_bias"]),
                (n, e), jnp.float32) * BIAS_SCALE
        out[group] = g
    v = cfg["vocab_size"]
    out["embed"] = (jax.random.normal(k12[_SPLIT["embed"]], (v, h),
                                      jnp.float32) * 0.02).astype(dtype)
    out["final_norm"] = jnp.ones((h,), jnp.float32)
    out["head"] = rnd("head", (h, v), h, 0)
    return out


def _attention(x, lp, cfg, kind):
    """The attention half of a layer on one sequence [T, H] -> [T, H]: a
    loop over (query head, block of queries), each against every key of the
    head's KV head under the kind's mask."""
    nh, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    window = cfg["sliding_window"]
    t = x.shape[0]
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    pos = jnp.arange(t)
    hn = _rms_norm(x, lp["ln1"], eps)
    q = _rms_norm((hn @ lp["wq"].astype(hn.dtype)).reshape(t, nh, d),
                  lp["q_norm"], eps)
    k = _rms_norm((hn @ lp["wk"].astype(hn.dtype)).reshape(t, hkv, d),
                  lp["k_norm"], eps)
    if kind == "window":  # the full layers carry no rotation
        q = _rope(q, pos, float(cfg["rope_theta"]), d)
        k = _rope(k, pos, float(cfg["rope_theta"]), d)
    v = (hn @ lp["wv"].astype(hn.dtype)).reshape(t, hkv, d)
    scale = 1.0 / math.sqrt(d)

    def one(jb):
        j, b = jb
        g = j // (nh // hkv)
        qb = lax.dynamic_slice_in_dim(
            lax.dynamic_index_in_dim(q, j, 1, keepdims=False), b * blk, blk)
        kg = lax.dynamic_index_in_dim(k, g, 1, keepdims=False)
        vg = lax.dynamic_index_in_dim(v, g, 1, keepdims=False)
        s = (qb @ kg.T).astype(jnp.float32) * scale
        qpos = b * blk + jnp.arange(blk)
        seen = pos[None, :] <= qpos[:, None]
        if kind == "window":
            seen = seen & (pos[None, :] > qpos[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        return p.astype(vg.dtype) @ vg  # [blk, d]

    jj, bb = jnp.meshgrid(jnp.arange(nh), jnp.arange(t // blk),
                          indexing="ij")
    out = lax.map(one, (jj.reshape(-1), bb.reshape(-1)))  # [nh*nb, blk, d]
    attn = out.reshape(nh, t, d).transpose(1, 0, 2).reshape(t, nh * d)
    attn = attn * jax.nn.sigmoid(hn @ lp["wg"].astype(hn.dtype))
    return x + _rms_norm(attn @ lp["wo"].astype(attn.dtype), lp["ln1_post"],
                         eps)


_EXPERTS = ("we_gate", "we_up", "we_down")


def _moe(h2, lp, cfg, layer):
    """The expert layer's sum on rows [T, H]: the shared expert, and every
    row through every expert HELD HERE, one at a time, weighted by its gate
    over all the routed experts (zero off the top-k). The expert leaves come
    STACKED over the group's layers, ``[n, held, ...]``, and an expert's
    matrices are read where they lie (``[layer, e]``): a slice of a layer's
    32 experts would be a copy of 0.6 GB a leaf beside the weights."""
    w = gate(h2, lp["router"], lp["router_bias"],
             cfg["num_experts_per_tok"], cfg.get("route_scale") or 1.0)
    first, held = cfg.get("first_expert", 0), cfg["num_experts"]

    def one(acc, ew):
        e, w_e = ew
        wg, wu, wd = (lp[leaf][layer, e] for leaf in _EXPERTS)
        # elementwise weighting: the gate is never rounded to a product's
        # operand precision
        return acc + _swiglu(h2, wg, wu, wd) \
            * w_e[:, None].astype(acc.dtype), None

    routed, _ = lax.scan(one, jnp.zeros_like(h2),
                         (jnp.arange(held), w[:, first:first + held].T))
    return routed + _swiglu(h2, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def _ffn(x, lp, cfg, dense, layer):
    eps = cfg["rms_norm_eps"]
    h2 = _rms_norm(x, lp["ln2"], eps)
    out = _swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"]) if dense \
        else _moe(h2, lp, cfg, layer)
    return x + _rms_norm(out, lp["ln2_post"], eps)


@partial(jax.jit, static_argnames=("cfg_key", "dtype", "precision"))
def _forward(weights, tokens, rows, cfg_key, dtype, precision):
    cfg = {k: (list(v) if isinstance(v, tuple) else v) for k, v in cfg_key}
    with jax.default_matmul_precision(precision):
        x = jnp.take(weights["embed"], tokens, axis=0).astype(dtype)
        if cfg.get("mup_enabled"):
            x = x * jnp.asarray(math.sqrt(cfg["hidden_size"]), dtype)
        for group, i, kind, dense in layers(cfg):
            lp = {leaf: a if leaf in _EXPERTS else a[i]
                  for leaf, a in weights[group].items()}
            x = _ffn(_attention(x, lp, cfg, kind), lp, cfg, dense, i)
        x = _rms_norm(jnp.take(x, rows, axis=0), weights["final_norm"],
                      cfg["rms_norm_eps"])
        return x.astype(jnp.float32) @ weights["head"].astype(jnp.float32)


_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "rope_theta", "sliding_window", "rms_norm_eps",
         "mup_enabled", "num_hidden_layers", "num_dense_layers",
         "layer_types", "num_experts", "router_experts", "first_expert",
         "num_experts_per_tok", "num_shared_experts", "route_scale",
         "moe_intermediate_size", "intermediate_size", "vocab_size")


def cfg_key(cfg):
    """The configuration's numbers as a hashable static argument."""
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                 for k in _KEYS if k in cfg)


def forward_logits(weights, tokens, cfg, rows=None, dtype=jnp.float32,
                   precision="highest"):
    """Logits [R, V] (float32) at positions ``rows`` [R] (all positions if
    None) of one token sequence [T]: the published forward over this chip's
    share. A caller names the rows it compares. ``dtype`` below float32 is
    for the lower-precision control."""
    tokens = jnp.asarray(tokens, jnp.int32)
    rows = jnp.arange(tokens.shape[0]) if rows is None \
        else jnp.asarray(rows, jnp.int32)
    return _forward(weights, tokens, rows, cfg_key(cfg), dtype, precision)
